"""Quickstart: QAFeL in ~60 lines on a convex toy problem.

Shows the whole mechanism end to end — clients training from the shared
hidden state, quantized uploads filling the server buffer, the server step,
and the quantized hidden-state broadcast keeping every replica bit-identical.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp

from repro.common.compile_cache import enable_compile_cache
from repro.core import QAFeL, QAFeLConfig, decode_message

D = 2048


def loss_fn(params, batch, key):
    del key
    return jnp.mean((params["w"] - batch["target"]) ** 2)


def trees_equal(a, b) -> bool:
    """Bit-exact tree comparison with ONE host sync, not one per leaf."""
    eqs = [jnp.array_equal(x, y)
           for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))]
    return bool(jnp.all(jnp.stack(eqs)))


def main():
    enable_compile_cache()
    qcfg = QAFeLConfig(
        client_lr=0.2, server_lr=1.0, server_momentum=0.3,
        buffer_size=4, local_steps=2,
        client_quantizer="qsgd4",   # 4-bit stochastic uploads
        server_quantizer="qsgd4")   # 4-bit hidden-state broadcasts
    params0 = {"w": jnp.zeros((D,))}
    algo = QAFeL(qcfg, loss_fn, params0)

    # one simulated client device, holding its own x-hat replica
    replica = jax.tree.map(lambda a: a.copy(), algo.state.hidden.value)

    key = jax.random.PRNGKey(0)
    target = 3.0
    for upload in range(40):
        key, k1, k2, k3 = jax.random.split(key, 4)
        batches = {"target": jnp.full((qcfg.local_steps, D), target)
                   + 0.1 * jax.random.normal(k1, (qcfg.local_steps, D))}
        msg, version = algo.run_client(batches, k2)
        bmsg = algo.receive(msg, k3)
        if bmsg is not None:  # buffer flushed -> server stepped -> broadcast
            q = decode_message(algo.sq, bmsg)
            replica = jax.tree.map(lambda a, d: a + d, replica, q)
            # per-flush progress line: the sync IS the point of the example
            # flcheck: ignore[host-sync-in-loop]
            err = float(jnp.linalg.norm(algo.state.x["w"] - target))
            print(f"server step {algo.state.t:2d}  |x - target| = {err:8.3f}  "
                  f"msg = {msg.wire_bytes / 1e3:.2f} kB (vs "
                  f"{4 * D / 1e3:.2f} kB full precision)")

    same = trees_equal(replica, algo.state.hidden.value)
    # drift=True: the hidden-drift reduction forces a device sync, so it is
    # opt-in — fine here at the end of the run, skipped in hot loops
    print("\nmetrics:", {k: round(v, 3) if isinstance(v, float) else v
                         for k, v in algo.metrics(drift=True).items()})
    print("client x-hat replica bit-identical to server:", same)
    assert same


if __name__ == "__main__":
    main()
