"""Cross-pod FL aggregation semantics (single-device numerics) and the
mesh-parallel paths via an 8-fake-device subprocess (XLA_FLAGS must be set
before jax init, hence the subprocess)."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.distributed import fl_aggregate

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _stacked(seed=0, n_pods=4):
    rng = np.random.default_rng(seed)
    return {
        "w": jnp.asarray(rng.normal(size=(n_pods, 6, 700)).astype(np.float32)),
        "b": jnp.asarray(rng.normal(size=(n_pods, 13)).astype(np.float32)),
    }


def test_exact_aggregate_is_masked_mean():
    st = _stacked()
    mask = jnp.asarray([1.0, 0.0, 1.0, 1.0])
    out = fl_aggregate(st, mask, mode="exact")
    for key in ("w", "b"):
        expect = (st[key][0] + st[key][2] + st[key][3]) / 3.0
        for pod in range(4):
            np.testing.assert_allclose(np.asarray(out[key][pod]),
                                       np.asarray(expect), rtol=1e-5,
                                       atol=1e-6)


def test_exact_all_dead_keeps_local():
    """Void round (no pod arrived): each pod keeps its *own* params —
    referencing pod 0 would cost a params-sized broadcast (§Perf)."""
    st = _stacked(1)
    out = fl_aggregate(st, jnp.zeros((4,)), mode="exact")
    for pod in range(4):
        np.testing.assert_allclose(np.asarray(out["w"][pod]),
                                   np.asarray(st["w"][pod]), rtol=1e-6)


def test_approx_static_divisor_bias():
    """approx divides by n_pods regardless of arrivals — the lock-free
    lost-update bias direction (shrinks toward zero when pods miss)."""
    st = _stacked(2)
    mask = jnp.asarray([1.0, 1.0, 0.0, 0.0])
    exact = fl_aggregate(st, mask, mode="exact")
    approx = fl_aggregate(st, mask, mode="approx")
    np.testing.assert_allclose(np.asarray(approx["w"][0]),
                               np.asarray(exact["w"][0]) * 0.5, rtol=1e-5)


def test_approx_equals_exact_with_full_arrivals():
    st = _stacked(3)
    mask = jnp.ones((4,))
    a = fl_aggregate(st, mask, mode="exact")
    b = fl_aggregate(st, mask, mode="approx")
    jax.tree_util.tree_map(
        lambda x, y: np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                                rtol=1e-5, atol=1e-6), a, b)


def test_int8_close_to_exact():
    st = _stacked(4)
    mask = jnp.ones((4,))
    a = fl_aggregate(st, mask, mode="exact")
    b = fl_aggregate(st, mask, mode="int8")
    err = np.abs(np.asarray(a["w"]) - np.asarray(b["w"])).max()
    assert err < 0.05, err


def test_dtype_preserved():
    st = jax.tree_util.tree_map(lambda x: x.astype(jnp.bfloat16), _stacked(5))
    out = fl_aggregate(st, jnp.ones((4,)), mode="exact")
    assert out["w"].dtype == jnp.bfloat16


_MESH_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np, json
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.runtime.sharding import ParallelCtx
    from repro.core.distributed import make_fl_aggregate_step

    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 3)
    ctx = ParallelCtx(mesh=mesh)
    rng = np.random.default_rng(0)
    stacked = {"w": jnp.asarray(rng.normal(size=(2, 8, 704)).astype(np.float32))}
    sh = {"w": NamedSharding(mesh, P("pod", None, None))}
    stacked_d = jax.device_put(stacked, sh)
    results = {}
    for mode in ("exact", "approx", "int8"):
        step = jax.jit(make_fl_aggregate_step(mode, ctx),
                       in_shardings=(sh, None), out_shardings=sh)
        out = step(stacked_d, jnp.ones((2,), jnp.float32))
        results[mode] = np.asarray(out["w"][0])
    expect = np.asarray(stacked["w"]).mean(0)
    assert np.allclose(results["exact"], expect, rtol=1e-5, atol=1e-6)
    assert np.allclose(results["approx"], expect, rtol=1e-5, atol=1e-6)
    assert np.abs(results["int8"] - expect).max() < 0.05
    # collective structure: int8 mode must move int8 (all-gather), exact f32
    step = jax.jit(make_fl_aggregate_step("int8", ctx),
                   in_shardings=(sh, None), out_shardings=sh)
    hlo = step.lower(stacked_d, jnp.ones((2,), jnp.float32)).compile().as_text()
    assert "s8[" in hlo, "int8 wire format missing from HLO"
    print("MESH_OK")
""")

_TRAIN_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import ARCHS, reduced
    from repro.launch.steps import make_train_step, make_ctx
    from repro.launch.mesh import make_mesh_for
    from repro.configs.base import TRAIN_4K
    from repro.models.transformer import init_params
    from repro.optim import sgd
    from repro.data.synthetic import lm_batch_for
    from repro.runtime.sharding import param_shardings

    cfg = reduced(ARCHS["jamba-v0.1-52b"])
    mesh = make_mesh_for(8)
    ctx = make_ctx(mesh, cfg, TRAIN_4K)
    opt = sgd(0.05)
    params = init_params(jax.random.PRNGKey(0), cfg)
    batch = lm_batch_for(cfg, 8, 32, seed=0)

    # single-device reference
    step0 = jax.jit(make_train_step(cfg, None, opt))
    p0, _, m0 = step0(params, opt.init(params), batch)

    # mesh
    shard = param_shardings(jax.eval_shape(lambda p: p, params), ctx)
    params_d = jax.device_put(params, shard)
    step1 = jax.jit(make_train_step(cfg, ctx, opt))
    p1, _, m1 = step1(params_d, opt.init(params_d), batch)
    l0, l1 = float(m0["loss"]), float(m1["loss"])
    assert np.isfinite(l0) and np.isfinite(l1)
    assert abs(l0 - l1) < 0.05 * abs(l0) + 0.05, (l0, l1)
    print("TRAIN_OK", l0, l1)
""")


@pytest.mark.parametrize("script,marker", [(_MESH_SCRIPT, "MESH_OK"),
                                           (_TRAIN_SCRIPT, "TRAIN_OK")])
def test_mesh_subprocess(script, marker):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env.pop("JAX_PLATFORMS", None)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    assert marker in r.stdout
