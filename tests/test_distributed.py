"""Distributed-path tests (subprocesses: each needs its own fake device count).

* expert-parallel MoE (shard_map + all_to_all) == the GSPMD no-drop path,
* hierarchical pod-quantized round runs and keeps state finite/replicated,
* a real dry-run (lower + compile + roofline) for the smallest arch.
"""
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_py(code: str, timeout: int = 560) -> str:
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)], capture_output=True,
        text=True, timeout=timeout,
        env={**os.environ, "PYTHONPATH": os.path.join(REPO, "src")}, cwd=REPO)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-4000:]
    return out.stdout


def test_expert_parallel_moe_matches_gspmd():
    run_py("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro import configs as C
        from repro.models import moe as moe_lib
        cfg = C.get_reduced("qwen3-moe-235b-a22b")
        mesh = jax.make_mesh((4, 2), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        moe_lib.set_ep_mesh(mesh)
        params = moe_lib.init_moe(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, cfg.d_model))
        with mesh:
            xs = jax.device_put(x, NamedSharding(mesh, P("data", None, None)))
            ps = dict(params)
            for k in ("w_gate", "w_up", "w_down"):
                ps[k] = jax.device_put(params[k], NamedSharding(mesh, P("data", None, None)))
            ref, _ = jax.jit(lambda p, x: moe_lib.moe_forward(
                cfg, p, x, capacity_factor=float(cfg.n_experts)))(ps, xs)
            out, _ = jax.jit(lambda p, x: moe_lib.moe_forward_ep(
                cfg, p, x, capacity_factor=8.0))(ps, xs)
        err = float(jnp.abs(out - ref).max())
        assert err < 1e-4, err
        print("EP_OK")
    """)


def test_pod_quantized_round_runs():
    run_py("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro import configs as C
        from repro.core.qafel import QAFeLConfig
        from repro.distributed.steps import make_qafel_round, init_round_state
        from repro.data.synthetic import synthetic_batch_for_config
        cfg = C.get_reduced("gemma2-2b")
        qcfg = QAFeLConfig(client_lr=1e-2, server_lr=1.0, buffer_size=4,
                           local_steps=1, client_quantizer="qsgd8",
                           server_quantizer="qsgd8")
        mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 3)
        rng = np.random.default_rng(0)
        raw = synthetic_batch_for_config(cfg, rng, 8, 32)
        batch = {k: jnp.asarray(v).reshape((4, 1, 2) + v.shape[1:])
                 for k, v in raw.items()}
        with mesh:
            state = init_round_state(cfg, jax.random.PRNGKey(0))
            rf = make_qafel_round(cfg, qcfg, remat=False, pod_quantized=True,
                                  mesh=mesh)
            bsh = jax.tree.map(lambda l: NamedSharding(
                mesh, P(*(["pod", None, ("data",)] + [None] * (l.ndim - 3)))), batch)
            st, metrics = jax.jit(rf)(state, jax.device_put(batch, bsh),
                                      jax.device_put(jnp.ones((4,)),
                                                     NamedSharding(mesh, P("pod"))),
                                      jax.random.PRNGKey(1))
        assert all(bool(jnp.isfinite(x).all()) for x in jax.tree.leaves(st.x))
        assert float(metrics["loss"]) > 0
        print("PODQ_OK")
    """)


@pytest.mark.slow
def test_dryrun_smallest_arch_compiles():
    """Real production-mesh dry-run for the smallest assigned arch."""
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "internvl2-1b",
         "--shape", "decode_32k", "--mesh", "single", "--out",
         "/tmp/test_dryrun_out"],
        capture_output=True, text=True, timeout=560,
        env={**os.environ, "PYTHONPATH": os.path.join(REPO, "src")}, cwd=REPO)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    assert "OK  internvl2-1b__decode_32k__pod16x16" in out.stdout
