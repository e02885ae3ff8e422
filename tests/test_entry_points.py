"""Entry-point plumbing: the compile cache, one process per chip, and the
benchmark harness's exit code.

On a TPU host one process owns the chips, so every path that would start a
JAX child there (the benchmark mesh rows, flcheck's virtual-device pass)
runs in-process or refuses. These tests steer ``jax.default_backend`` to
take the TPU branch on the CPU.
"""
import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def on_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def test_compiled_pass_refuses_child_on_tpu(on_tpu, monkeypatch):
    from repro.analysis_static import contracts

    def no_child(ndev):
        raise AssertionError("started a JAX child on a TPU host")

    monkeypatch.setattr(contracts, "_run_subprocess", no_child)
    ndev = jax.device_count() + 1
    res = contracts.run_compiled((ndev,))
    assert res.checks == 0
    assert [f.rule for f in res.findings] == ["compiled-pass-error"]
    assert f"ndev={ndev}" in res.findings[0].message


def test_shard2d_bench_refuses_child_on_tpu(on_tpu, monkeypatch):
    sys.path.insert(0, REPO)
    try:
        from benchmarks import kernel_bench
    finally:
        sys.path.remove(REPO)
    monkeypatch.setattr(subprocess, "run", None)  # any child would crash
    with pytest.raises(RuntimeError, match="need 8 devices"):
        kernel_bench.shard2d_bench(lambda *row: None)


def test_benchmark_run_exits_nonzero_on_suite_error():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(REPO, "src"), REPO,
                    os.environ.get("PYTHONPATH", "")]))
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--only", "no_such_suite"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 2, proc.stderr[-2000:]
    assert "no_such_suite/ERROR" in proc.stdout


@pytest.mark.parametrize("env_dir", [None, "/some/cache"])
def test_enable_compile_cache(monkeypatch, env_dir):
    from repro.common import compile_cache

    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    was = jax.config.jax_compilation_cache_dir
    try:
        compile_cache.enable_compile_cache()
        now = jax.config.jax_compilation_cache_dir
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
    if env_dir is None:
        assert now == os.path.join(REPO, ".jax_cache")
    else:
        assert now == was  # JAX reads the variable itself
