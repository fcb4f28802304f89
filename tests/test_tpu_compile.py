"""The main-path Pallas kernels compile for a TPU v5e, at real widths.

Nothing runs: each kernel is lowered with ``interpret=False`` and compiled
by the TPU compiler for a described (not attached) ``v5e:2x2`` topology,
which refuses what interpret mode cannot see — unaligned slices, gathers
Mosaic cannot lower, VMEM overruns. Shapes are the served ones: a
minitron-8b FFN contraction, (8, 4096) @ (4096, 16384), and a batch of
eight 1080p frames (1088 rows after bucketing). Each kernel is compiled for
proposed@8 and for an approximate wiring at width 6, whose LUT is padded
from 64 to 128 lanes and whose fused-conv table columns emit
compare-selects.

The wrapper cases (the planned edge path, the per-pixel tap dot and the
edge service's compiled call) go through the ops wrappers, whose padding
and orientation decide the HBM footprint: they must fit the 16 GB of one
v5e chip.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import lut as lut_lib
from repro.kernels import blocking
from repro.kernels.approx_matmul.kernel import approx_matmul_pallas
from repro.kernels.approx_matmul.ops import closed_form_matmul
from repro.kernels.closed_form import make_closed_form
from repro.kernels.fused_conv.kernel import fused_conv_pallas
from repro.kernels.fused_conv.ops import lut_tap_product
from repro.kernels.lut_matmul.kernel import lut_matmul_pallas
from repro.nn import conv
from repro.nn.plan import SubstratePlan
from repro.serving import EdgeDetectService

M, K, N = 8, 4096, 16384
FRAMES, H, W = 8, 1088, 1920
HBM_BYTES = 16 * 2**30  # one v5e chip
WIRINGS = ["proposed@8", "design_du2022@6"]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    # keep the TPU compiler's logs out of the shared /tmp/tpu_logs
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def mosaic(monkeypatch):
    """The ops wrappers take their Mosaic branch although JAX runs on CPU."""
    monkeypatch.setenv(blocking.INTERPRET_ENV, "0")


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _kernels(compiled) -> set:
    """Names of the Mosaic kernels in the compiled program."""
    return set(re.findall(r"%([A-Za-z_]\w*?)(?:\.\d+)? = .*"
                          r'custom_call_target="tpu_custom_call"',
                          compiled.as_text()))


def _hbm_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


@pytest.mark.parametrize("key", WIRINGS)
def test_closed_form_matmul_compiles_at_layer_shape(one_chip, key):
    fn = functools.partial(approx_matmul_pallas,
                           product_fn=make_closed_form(key), block_m=M,
                           interpret=False)
    _compile(fn, jax.ShapeDtypeStruct((M, K), jnp.int32, sharding=one_chip),
             jax.ShapeDtypeStruct((K, N), jnp.int32, sharding=one_chip))


@pytest.mark.parametrize("key", ["exact"] + WIRINGS[1:])
def test_lut_matmul_compiles_at_layer_shape(one_chip, key):
    table = lut_lib.flat_lut(key)
    fn = functools.partial(lut_matmul_pallas, block_m=M, interpret=False)
    _compile(fn, jax.ShapeDtypeStruct((M, K), jnp.int32, sharding=one_chip),
             jax.ShapeDtypeStruct((K, N), jnp.int32, sharding=one_chip),
             jax.ShapeDtypeStruct(table.shape, jnp.int32, sharding=one_chip))


@pytest.mark.parametrize("kind,key", [("closed_form", "proposed"),
                                      ("lut", "exact"),
                                      ("lut", "design_du2022@6")])
def test_fused_conv_compiles_at_1080p(one_chip, kind, key):
    product_fn = (make_closed_form(key) if kind == "closed_form"
                  else lut_tap_product(key))
    taps = tuple(tuple(int(c) for c in row) for row in np.asarray(conv.LAPLACIAN))

    def fn(*views):
        return fused_conv_pallas(views, taps, product_fn, width_out=W,
                                 block_h=64, interpret=False)

    view = jax.ShapeDtypeStruct((FRAMES, H, W + 2), jnp.int32,
                                sharding=one_chip)
    _compile(fn, *([view] * len(taps)))


def test_tap_dot_fits_hbm_at_1080p(one_chip, mosaic):
    """The im2col per-pixel tap dot, (B·H·W, 9) @ (9, 1), stays lane-dense."""
    fn = functools.partial(closed_form_matmul, mult_key="proposed")
    compiled = _compile(
        fn, jax.ShapeDtypeStruct((FRAMES * H * W, 9), jnp.int32,
                                 sharding=one_chip),
        jax.ShapeDtypeStruct((9, 1), jnp.int32, sharding=one_chip))
    assert _kernels(compiled) == {"closed_form_matmul"}
    assert _hbm_bytes(compiled) < HBM_BYTES


def test_planned_edge_fits_hbm_at_1080p(one_chip, mosaic):
    """Center tap on proposed@6, ring on proposed@8, through dot_general."""
    plan = SubstratePlan(default="approx_pallas:proposed@8", rules=(
        ("conv.edge.center", "approx_pallas:proposed@6"),
        ("conv.edge.ring", "approx_pallas:proposed@8")))
    compiled = _compile(
        lambda x: conv.edge_detect_planned(x, plan),
        jax.ShapeDtypeStruct((FRAMES, H, W), jnp.uint8, sharding=one_chip))
    assert _hbm_bytes(compiled) < HBM_BYTES


def test_edge_service_call_runs_fused_conv_at_1080p(one_chip, mosaic):
    """The service's own compiled call on a batch of eight 1080p frames
    runs the fused conv kernel, builds no im2col patch matrix for
    ``closed_form_matmul``, and fits one chip."""
    svc = EdgeDetectService("approx_pallas", start=False)
    assert svc.conv_path == "fused"
    compiled = _compile(
        svc._jit_fn,
        jax.ShapeDtypeStruct((FRAMES, H, W), jnp.uint8, sharding=one_chip))
    assert _kernels(compiled) == {"fused_conv"}
    assert _hbm_bytes(compiled) < HBM_BYTES
