"""The paper's experiment, end to end: asynchronous federated training of the
4-layer CNN on (synthetic) CelebA with bidirectional 4-bit quantization,
compared against full-precision FedBuff.

This is the driver behind Figure 3 / Table 1: constant-rate client arrivals,
half-normal training durations, buffer K=10, staleness down-weighting,
real packed wire messages with exact byte metering.

    PYTHONPATH=src python examples/federated_celeba.py [--uploads 400]
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.compile_cache import enable_compile_cache
from repro.core import QAFeL, QAFeLConfig
from repro.data import FederatedPartition, SyntheticCelebA
from repro.models.cnn import cnn_accuracy, cnn_loss, init_cnn
from repro.sim import AsyncFLSimulator, CohortAsyncFLSimulator, SimConfig


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--uploads", type=int, default=400)
    ap.add_argument("--concurrency", type=int, default=16)
    ap.add_argument("--target", type=float, default=0.90)
    ap.add_argument("--engine", choices=["sequential", "cohort"],
                    default="sequential")
    ap.add_argument("--scenario", default="identity",
                    help="scenario name (cohort engine only); see "
                         "repro.sim.scenarios.SCENARIOS")
    ap.add_argument("--cohort-size", type=int, default=8)
    args = ap.parse_args()
    if args.scenario != "identity" and args.engine != "cohort":
        ap.error("--scenario requires --engine cohort")

    ds = SyntheticCelebA(n_samples=3000)
    part = FederatedPartition(labels=ds.labels, n_clients=300)
    params0 = init_cnn(jax.random.PRNGKey(0))
    n_params = sum(x.size for x in jax.tree.leaves(params0))
    print(f"CNN: {n_params} params -> full-precision message "
          f"{4 * n_params / 1e3:.1f} kB")

    def loss_fn(params, batch, key):
        return cnn_loss(params, batch, train=True, key=key)[0]

    rng = np.random.default_rng(0)

    def client_batches(cid, key):
        b = [part.client_batch(ds, cid, 8, rng) for _ in range(2)]
        return {k: jnp.stack([jnp.asarray(bi[k]) for bi in b]) for k in b[0]}

    test_idx = part.split_indices(part.val_clients)[:512]
    test_batch = {k: jnp.asarray(v) for k, v in ds.batch(test_idx).items()}
    eval_fn = jax.jit(lambda p: cnn_accuracy(p, test_batch))

    for name, (cq, sq) in [("QAFeL 4-bit/4-bit", ("qsgd4", "qsgd4")),
                           ("FedBuff (full precision)", ("identity", "identity"))]:
        qcfg = QAFeLConfig(client_lr=0.05, server_lr=1.0, server_momentum=0.3,
                           buffer_size=10, local_steps=2,
                           client_quantizer=cq, server_quantizer=sq)
        algo = QAFeL(qcfg, loss_fn, params0)
        scfg = SimConfig(concurrency=args.concurrency,
                         max_uploads=args.uploads, eval_every_steps=3,
                         target_accuracy=args.target)
        if args.engine == "cohort":
            sim = CohortAsyncFLSimulator(algo, scfg, client_batches, eval_fn,
                                         scenario=args.scenario,
                                         cohort_size=args.cohort_size)
        else:
            sim = AsyncFLSimulator(algo, scfg, client_batches, eval_fn)
        res = sim.run()
        m = res.metrics
        print(f"\n== {name} ==")
        print(f"  reached {args.target:.0%}: {res.reached_target}  "
              f"(final acc {res.final_accuracy:.3f})")
        print(f"  uploads: {res.uploads}   server steps: {res.server_steps}   "
              f"tau_max: {m['tau_max']}")
        print(f"  kB/upload: {m['kB_per_upload']:.2f}   total upload MB: "
              f"{m['upload_MB']:.2f}   broadcast MB: {m['broadcast_MB']:.2f}")
        print(f"  hidden drift: {m['hidden_drift']:.4f}   replicas in sync: "
              f"{m['replicas_in_sync']}")


if __name__ == "__main__":
    main()
