"""Multi-device behaviour, run in subprocesses with 8 fake host devices
(conftest must NOT set the device-count flag globally — smoke tests and
benches see 1 device)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_py(code: str, n_devices: int = 8, extra_env: dict | None = None) -> str:
    """Run a code snippet in a subprocess with N forced host devices.

    Shared harness — ``tests/test_dot_general.py`` reuses it for the
    sharded-contraction parity suite. ``extra_env`` overlays the
    environment (e.g. interpret-mode toggles).
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["JAX_PLATFORMS"] = "cpu"
    if extra_env:
        env.update(extra_env)
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=500)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_compressed_dp_step_matches_uncompressed():
    """int8-compressed gradient all-reduce ≈ exact pmean on 8 devices."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.train.loop import dp_train_step_compressed
        from repro.optim import adamw

        def loss_fn(params, batch):
            pred = batch["tokens"].astype(jnp.float32) @ params["w"]
            tgt = batch["labels"].astype(jnp.float32)
            return jnp.mean((pred - tgt[..., None]) ** 2)

        mesh = make_mesh((8,), ("data",))
        params = {"w": jnp.ones((16, 1), jnp.float32) * 0.1}
        opt = adamw(weight_decay=0.0)
        state = opt.init(params)
        rng = np.random.default_rng(0)
        batch = {"tokens": jnp.asarray(rng.normal(size=(32, 16)), jnp.float32),
                 "labels": jnp.asarray(rng.normal(size=(32,)), jnp.float32)}
        with mesh:
            f_c = dp_train_step_compressed(loss_fn, opt, mesh, compress=True)
            f_u = dp_train_step_compressed(loss_fn, opt, mesh, compress=False)
            lc, pc, _ = f_c(params, state, batch, jnp.float32(1e-2))
            lu, pu, _ = f_u(params, state, batch, jnp.float32(1e-2))
        err = float(jnp.abs(pc["w"] - pu["w"]).max())
        print("loss", float(lc), float(lu), "err", err)
        assert abs(float(lc) - float(lu)) < 1e-5
        assert err < 1e-3, err
    """)
    assert "err" in out


def test_dryrun_cell_on_debug_mesh():
    """lower+compile a reduced arch on a 4x2 mesh; roofline terms emitted."""
    out = run_py("""
        import jax, jax.numpy as jnp, json
        from repro.launch import mesh as mesh_lib, roofline
        from repro.models import registry as reg
        from repro.optim import adamw

        cfg = reg.get_config("minitron-8b", n_layers=2, d_model=128, d_ff=256,
                             vocab=512, n_heads=4, n_kv_heads=2,
                             attn_chunk=64, loss_chunk=64, remat=False)
        bundle = reg._BUILDERS[cfg.family](cfg)
        mesh = mesh_lib.make_mesh((4, 2), ("data", "model"))
        opt = adamw()
        with mesh:
            params_sds = reg.param_specs(bundle)
            p_sh = mesh_lib.param_shardings(params_sds, mesh)
            opt_sds = jax.eval_shape(opt.init, params_sds)
            o_sh = mesh_lib.param_shardings(opt_sds, mesh)
            batch = {"tokens": jax.ShapeDtypeStruct((8, 64), jnp.int32),
                     "labels": jax.ShapeDtypeStruct((8, 64), jnp.int32)}
            b_sh = mesh_lib.batch_shardings(batch, mesh)
            def step(p, o, b):
                loss, grads = jax.value_and_grad(bundle.loss_fn)(p, b)
                np_, no_ = opt.update(grads, o, p, lr=jnp.float32(1e-3))
                return loss, np_, no_
            lowered = jax.jit(step, in_shardings=(p_sh, o_sh, b_sh)).lower(
                params_sds, opt_sds, batch)
            compiled = lowered.compile()
            cost = compiled.cost_analysis() or {}
            hlo = compiled.as_text()
        rf = roofline.derive(cost, hlo, 8, roofline.model_flops_for(
            cfg, reg.SHAPES["train_4k"]))
        stats = roofline.parse_collectives(hlo)
        print(json.dumps({"flops": rf.flops_per_device,
                          "coll": stats.total_bytes,
                          "bottleneck": rf.bottleneck}))
        assert rf.flops_per_device > 0
        assert stats.total_bytes > 0  # sharded training must communicate
    """)
    assert "bottleneck" in out


def test_elastic_checkpoint_restore_across_meshes(tmp_path):
    """Save params sharded on a (4,2) mesh; restore onto (2,4) and 1-device."""
    out = run_py(f"""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint import save_checkpoint, load_checkpoint

        mesh_a = make_mesh((4, 2), ("data", "model"))
        w = jnp.arange(64.0).reshape(8, 8)
        wa = jax.device_put(w, NamedSharding(mesh_a, P("data", "model")))
        save_checkpoint({str(tmp_path)!r}, 1, {{"w": wa}})

        mesh_b = make_mesh((2, 4), ("data", "model"))
        tgt = {{"w": jax.ShapeDtypeStruct((8, 8), jnp.float32)}}
        sh = {{"w": NamedSharding(mesh_b, P("model", "data"))}}
        tree, step, _ = load_checkpoint({str(tmp_path)!r}, {{"w": w}}, shardings=sh)
        np.testing.assert_array_equal(np.asarray(tree["w"]), np.asarray(w))
        tree2, _, _ = load_checkpoint({str(tmp_path)!r}, {{"w": w}})
        np.testing.assert_array_equal(np.asarray(tree2["w"]), np.asarray(w))
        print("elastic ok", tree["w"].sharding)
    """)
    assert "elastic ok" in out


def test_sharding_rules_shard_big_leaves():
    out = run_py("""
        import jax, jax.numpy as jnp
        from repro.launch import mesh as mesh_lib
        from repro.models import registry as reg
        mesh = mesh_lib.make_production_mesh(multi_pod=True)
        assert mesh.shape == {"pod": 2, "data": 16, "model": 16}
        cfg = reg.get_config("kimi-k2-1t-a32b")
        bundle = reg._BUILDERS[cfg.family](cfg)
        sds = reg.param_specs(bundle)
        sh = mesh_lib.param_shardings(sds, mesh)
        # the expert weight must be sharded on expert AND fsdp axes
        leaves = jax.tree_util.tree_flatten_with_path(sh)[0]
        import numpy as np
        total, mx = 0, 0
        for path, s in leaves:
            leaf = jax.tree_util.tree_flatten_with_path(sds)[0]
        flat_sds = {tuple(str(getattr(e,'key',getattr(e,'idx',e))) for e in p): l
                    for p, l in jax.tree_util.tree_flatten_with_path(sds)[0]}
        flat_sh = {tuple(str(getattr(e,'key',getattr(e,'idx',e))) for e in p): s
                   for p, s in jax.tree_util.tree_flatten_with_path(sh)[0]}
        worst = 0
        for k, l in flat_sds.items():
            n_shards = 1
            spec = flat_sh[k].spec
            for dim, d in enumerate(spec):
                if d is None: continue
                names = d if isinstance(d, tuple) else (d,)
                import math
                prod = math.prod(mesh.shape[n] for n in names)
                n_shards *= prod
            per_dev = np.prod(l.shape) * l.dtype.itemsize / n_shards
            worst = max(worst, per_dev)
        print("worst per-device leaf bytes:", worst/2**30, "GiB")
        assert worst < 8 * 2**30, worst  # largest leaf < 8 GiB/device
    """, n_devices=512)
    assert "worst" in out


def test_sharded_edge_detect_matches_unsharded():
    """edge_detect_batched under a Partitioning (serving mesh path) is
    bit-identical to the unsharded path on 8 devices."""
    out = run_py("""
        import jax, numpy as np
        from repro.data import image_batch
        from repro.launch import mesh as mesh_lib
        from repro.nn import conv

        mesh = mesh_lib.make_mesh((4, 2), ("data", "model"))
        part = mesh_lib.contraction_partitioning(mesh)
        imgs = image_batch(4, 24, 24)
        for spec in ("approx_bitexact", "approx_lut:design_strollo2020"):
            ref = np.asarray(conv.edge_detect_batched(imgs, spec))
            got = np.asarray(
                conv.edge_detect_batched(imgs, spec, partitioning=part))
            np.testing.assert_array_equal(got, ref, err_msg=spec)
        print("sharded edge ok", part.m_shards, part.k_shards)
    """)
    assert "sharded edge ok 4 2" in out


def test_dryrun_partitioned_approx_substrate_lowers():
    """--dot-partition mesh path: an approx substrate (approx_stat) lowers
    and compiles on a debug mesh with every dense() through shard_map."""
    out = run_py("""
        import jax, jax.numpy as jnp
        from repro.launch import mesh as mesh_lib
        from repro.models import registry as reg
        from repro.nn import substrate as psub

        cfg = reg.get_config("minitron-8b", n_layers=2, d_model=128, d_ff=256,
                             vocab=512, n_heads=4, n_kv_heads=2,
                             attn_chunk=64, loss_chunk=64, remat=False,
                             dot_mode="approx_stat")
        bundle = reg._BUILDERS[cfg.family](cfg)
        mesh = mesh_lib.make_mesh((4, 2), ("data", "model"))
        part = mesh_lib.contraction_partitioning(mesh)
        assert (part.m_axis, part.k_axis) == ("data", "model")
        with mesh, psub.partitioning_scope(part):
            params_sds = reg.param_specs(bundle)
            p_sh = mesh_lib.param_shardings(params_sds, mesh)
            batch = {"tokens": jax.ShapeDtypeStruct((8, 64), jnp.int32),
                     "labels": jax.ShapeDtypeStruct((8, 64), jnp.int32)}
            b_sh = mesh_lib.batch_shardings(batch, mesh)
            compiled = jax.jit(bundle.loss_fn,
                               in_shardings=(p_sh, b_sh)).lower(
                params_sds, batch).compile()
        assert "psum" in compiled.as_text() or \
            "all-reduce" in compiled.as_text()
        print("partitioned lowering ok")
    """)
    assert "partitioned lowering ok" in out
