"""HLO analyzer: FLOPs with loop multiplicity, collective parsing, roofline."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch import analysis
from repro.launch.hlo_analyzer import HLOCostAnalyzer, analyze


def _compiled(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def test_dot_flops_counted():
    m, k, n = 64, 128, 256
    c = _compiled(lambda a, b: a @ b,
                  jax.ShapeDtypeStruct((m, k), jnp.float32),
                  jax.ShapeDtypeStruct((k, n), jnp.float32))
    cost = analyze(c.as_text())
    expected = 2 * m * n * k
    assert 0.9 * expected <= cost.flops <= 1.2 * expected, cost.flops


def test_scan_trip_count_multiplies_flops():
    m = 32
    w = jax.ShapeDtypeStruct((m, m), jnp.float32)

    def once(x, w):
        return x @ w

    def scanned(x, w):
        def body(c, _):
            return c @ w, None
        out, _ = jax.lax.scan(body, x, None, length=10)
        return out

    c1 = _compiled(once, jax.ShapeDtypeStruct((m, m), jnp.float32), w)
    c10 = _compiled(scanned, jax.ShapeDtypeStruct((m, m), jnp.float32), w)
    f1 = analyze(c1.as_text()).flops
    f10 = analyze(c10.as_text()).flops
    assert f1 > 0
    ratio = f10 / f1
    assert 8.0 <= ratio <= 12.0, ratio  # ~10 trips


def test_collective_bytes_parsed():
    """SPMD module with a real all-reduce: bytes must be non-zero and sized."""
    import subprocess, sys, textwrap
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.hlo_analyzer import analyze
        mesh = jax.make_mesh((8,), ("data",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        def f(x):
            return x.sum(axis=0)
        xs = jax.ShapeDtypeStruct((64, 1024), jnp.float32)
        with mesh:
            fn = jax.jit(f, in_shardings=NamedSharding(mesh, P("data", None)))
            c = fn.lower(xs).compile()
        cost = analyze(c.as_text())
        total = cost.collective_total
        assert total > 0, c.as_text()[:2000]
        # all-reduce of a (1024,) f32 partial-sum row: 2 * 4096 bytes expected scale
        assert 1024 * 4 <= total <= 64 * 1024 * 4 * 4, total
        print("OK", total)
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env={**__import__("os").environ,
                                          "PYTHONPATH": "src"},
                         cwd=__import__("os").path.dirname(__import__("os").path.dirname(__file__)))
    assert "OK" in out.stdout, out.stdout + out.stderr


def test_roofline_terms_and_dominance():
    from repro import configs as C

    class Cost:
        flops = 1e15
        hbm_bytes = 1e12
        collective_total = 1e10
        collective_bytes = {"all-reduce": 1e10}

    cfg = C.get_config("gemma2-2b")
    roof = analysis.roofline(Cost(), {}, chips=256, cfg=cfg,
                             shape_kind="train", tokens=1_000_000)
    assert roof["dominant"] == "compute"
    np.testing.assert_allclose(roof["compute_s"], 1e15 / analysis.PEAK_FLOPS)
    np.testing.assert_allclose(roof["memory_s"], 1e12 / analysis.HBM_BW)
    assert roof["model_flops_total"] == 6.0 * cfg.active_param_count() * 1e6


def test_model_flops_moe_uses_active_params():
    from repro import configs as C
    cfg = C.get_config("qwen3-moe-235b-a22b")
    assert cfg.active_param_count() < 0.2 * cfg.param_count()
    mf = analysis.model_flops(cfg, "train", 1000)
    assert mf == 6.0 * cfg.active_param_count() * 1000
