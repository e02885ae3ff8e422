"""QAFeL rounds on an assigned decoder architecture (reduced config, CPU).

Shows the framework scaling past the paper's CNN: the same Algorithm 1-3
round math drives a transformer from the assigned pool, as the compiled
device program used by the multi-pod dry-run — K clients scanned in-graph,
per-client Q_c quantization, server update + Q_s hidden-state update.

    PYTHONPATH=src python examples/federated_llm.py --arch gemma2-2b --rounds 8
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs as config_registry
from repro.common.compile_cache import enable_compile_cache
from repro.core.qafel import QAFeLConfig
from repro.core.staleness import staleness_weight
from repro.data.synthetic import synthetic_batch_for_config
from repro.distributed.steps import init_round_state, make_qafel_round


@jax.jit
def model_drift(x, hidden):
    """|x - x_hat|_1 over the whole tree, reduced on device to one scalar."""
    return sum(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).sum()
               for a, b in zip(jax.tree.leaves(x), jax.tree.leaves(hidden)))


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma2-2b")
    ap.add_argument("--rounds", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--buffer-k", type=int, default=4)
    args = ap.parse_args()

    cfg = config_registry.get_reduced(args.arch)
    qcfg = QAFeLConfig(client_lr=3e-2, server_lr=1.0, server_momentum=0.3,
                       buffer_size=args.buffer_k, local_steps=2,
                       client_quantizer="qsgd4", server_quantizer="qsgd4")
    print(f"arch={cfg.arch_id} family={cfg.family} "
          f"params={sum(x.size for x in jax.tree.leaves(init_round_state(cfg, jax.random.PRNGKey(0)).x)):,}")

    round_fn = jax.jit(make_qafel_round(cfg, qcfg, remat=False))
    state = init_round_state(cfg, jax.random.PRNGKey(0))
    weights = staleness_weight(jnp.zeros((qcfg.buffer_size,)))
    rng = np.random.default_rng(0)
    local = 2

    for step in range(args.rounds):
        raw = synthetic_batch_for_config(
            cfg, rng, qcfg.buffer_size * qcfg.local_steps * local, args.seq)
        batch = {k: jnp.asarray(v).reshape(
            (qcfg.buffer_size, qcfg.local_steps, local) + v.shape[1:])
            for k, v in raw.items()}
        state, metrics = round_fn(state, batch, weights, jax.random.PRNGKey(step))
        # one host sync per round: loss and the device-reduced drift together
        loss, drift = jax.device_get(
            (metrics["loss"], model_drift(state.x, state.hidden)))
        print(f"round {step}: loss={loss:.4f} |x - x_hat|_1={drift:.2f}")


if __name__ == "__main__":
    main()
