"""Import hygiene: importing any ``repro`` module leaves ``os.environ`` as
it found it.

A module that writes the environment at import (say ``XLA_FLAGS``) silently
changes the device set of every process that imports it. One subprocess
imports every ``src/repro/**/*.py`` module in turn, found by file path
(``repro.launch`` has no ``__init__.py``, so ``pkgutil.walk_packages`` would
skip it), and reports per module which variables its import changed. JAX
and Pallas set variables of their own when first imported, so the probe
imports them before it starts.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")

SUBPACKAGES = ("checkpoint", "configs", "core", "data", "kernels", "launch",
               "models", "nn", "obs", "optim", "serving", "train")

_PROBE = textwrap.dedent("""
    import importlib, json, os, pathlib, sys, traceback

    import jax, jax.numpy, jax.experimental.pallas, numpy  # noqa: F401

    src = pathlib.Path(sys.argv[1])
    names = []
    for path in sorted((src / "repro").rglob("*.py")):
        parts = path.relative_to(src).with_suffix("").parts
        names.append(".".join(parts[:-1] if parts[-1] == "__init__" else parts))
    report = {}
    for name in names:
        before = dict(os.environ)
        try:
            importlib.import_module(name)
            error = None
        except Exception:  # noqa: BLE001
            error = traceback.format_exc(limit=3)
        after = dict(os.environ)
        changed = sorted(k for k in before.keys() | after.keys()
                         if before.get(k) != after.get(k))
        os.environ.clear()
        os.environ.update(before)
        report[name] = {"changed": changed, "error": error}
    print("REPORT" + json.dumps(report))
""")


@pytest.fixture(scope="module")
def import_report():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", _PROBE, SRC],
                         capture_output=True, text=True, env=env, timeout=120)
    assert out.returncode == 0, out.stderr[-3000:]
    line = next(l for l in out.stdout.splitlines() if l.startswith("REPORT"))
    return json.loads(line[len("REPORT"):])


@pytest.mark.parametrize("subpackage", SUBPACKAGES)
def test_import_leaves_environment_unchanged(import_report, subpackage):
    prefix = f"repro.{subpackage}"
    mods = {name: r for name, r in import_report.items()
            if name == prefix or name.startswith(prefix + ".")}
    assert mods, f"no module found under {prefix}"
    errors = {name: r["error"] for name, r in mods.items() if r["error"]}
    assert not errors, errors
    changed = {name: r["changed"] for name, r in mods.items() if r["changed"]}
    assert not changed, f"importing these changed os.environ: {changed}"
