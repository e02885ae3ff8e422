"""Run the vectorized cohort engine under any named heterogeneity scenario.

The cohort engine (repro.sim.cohort) trains whole client cohorts in one
vmap'ed jitted call and encodes all their uploads through one batched
quantize-pack kernel dispatch; the scenario library (repro.sim.scenarios)
supplies the timing/behaviour regime: latency distribution, arrival
process, dropouts, stragglers, per-client quantizer bit-width tiers.

    PYTHONPATH=src python examples/cohort_scenarios.py --list
    PYTHONPATH=src python examples/cohort_scenarios.py \
        --scenario lognormal_dropout --concurrency 8 --cohort-size 4 \
        --uploads 120 --min-acc 0.6
    PYTHONPATH=src python examples/cohort_scenarios.py --devices 8 ...

``--min-acc`` makes the run assert convergence (used by the CI smoke job).
``--trace PATH`` attaches a ``repro.obs.RunTracer`` with in-dispatch metric
taps enabled, writes the full structured event stream (uploads, drops,
flushes with per-flush quantization error, broadcasts, evals, compiles) to
PATH as JSONL, schema-validates it, and prints the telemetry summary table.
``--devices N`` runs the sharded flat substrate on an N-device ("data",)
mesh — cohort members and server flat-state segments shard over it, with
bit-identical results to ``--devices 1``. On CPU, N fake host devices are
forced via XLA_FLAGS (which is why argument parsing here happens BEFORE
jax is imported).
``--engine population`` swaps the event-loop timeline for the
device-resident population engine (repro.sim.population): the whole client
lifecycle — admission, latency/dropout draws, deadline wheel, staleness —
runs as one fused dispatch per macro step, so very large ``--concurrency``
values (10k-1M) stay cheap; eval events additionally carry per-state
population counts.
``--model quad`` swaps the CNN for a d=2048 convex quadratic whose
"accuracy" is the fraction of the distance to the optimum recovered — the
client task that keeps genuine 10k+-concurrency runs (where every pool
member trains once before the first delivery) inside a CI budget. At
large concurrency pass a proportionally large ``--buffer`` (staleness
scales with concurrency/buffer; the population-smoke job uses
concurrency 10000 with buffer 2048).
"""
import argparse
import os


def parse_args():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scenario", default="identity",
                    help="name from repro.sim.scenarios.SCENARIOS")
    ap.add_argument("--list", action="store_true", help="list scenarios")
    ap.add_argument("--concurrency", type=int, default=8)
    ap.add_argument("--cohort-size", type=int, default=4)
    ap.add_argument("--uploads", type=int, default=120)
    ap.add_argument("--buffer", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--samples", type=int, default=1200)
    ap.add_argument("--min-acc", type=float, default=None,
                    help="assert final accuracy >= this (CI smoke)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="enable telemetry taps, write the structured "
                         "event stream to PATH as JSONL (schema-validated)")
    ap.add_argument("--devices", type=int, default=1,
                    help="shard the flat substrate over an N-device mesh "
                         "(fakes N host devices on CPU)")
    ap.add_argument("--engine", choices=("cohort", "population"),
                    default="cohort",
                    help="timeline engine: the event-loop cohort engine or "
                         "the device-resident population engine (scales to "
                         "very large --concurrency)")
    ap.add_argument("--model", choices=("cnn", "quad"), default="cnn",
                    help="client task: the paper's CNN, or a d=2048 convex "
                         "quadratic sized for very large populations (its "
                         "accuracy metric is the fraction of the distance "
                         "to the optimum recovered)")
    return ap.parse_args()


def main():
    args = parse_args()
    if args.devices > 1:
        # must land before the first jax import in this process; APPEND so a
        # user's pre-existing XLA_FLAGS are kept (setdefault would silently
        # drop the device-count flag and --devices would fail)
        flags = os.environ.get("XLA_FLAGS", "")
        if "--xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{args.devices}").strip()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.common.compile_cache import enable_compile_cache
    from repro.core import QAFeL, QAFeLConfig
    from repro.data import FederatedPartition, SyntheticCelebA
    from repro.launch.mesh import make_sim_mesh
    from repro.models.cnn import cnn_accuracy, cnn_loss, init_cnn
    from repro.sim import (SCENARIOS, CohortAsyncFLSimulator,
                           PopulationAsyncFLSimulator, SimConfig)

    enable_compile_cache()
    if args.list:
        for name, cfg in SCENARIOS.items():
            print(f"{name:20s} {cfg}")
        return
    mesh = make_sim_mesh(args.devices) if args.devices > 1 else None

    if args.model == "quad":
        # CI-scale client task: the CNN's conv gradients cost ~0.4s per
        # trained member on a 2-core box, and filling a 10k-client pool
        # trains every member once — the convex task keeps 10k-1M
        # concurrency smokes inside a CI budget while driving the exact
        # same engine, wire and telemetry paths. "Accuracy" is the
        # fraction of the distance from w=0 to the optimum recovered.
        d = 2048
        wstar = jax.random.normal(jax.random.PRNGKey(1), (d,), jnp.float32)
        wstar = wstar / jnp.linalg.norm(wstar) * 10.0
        wstar_np = np.asarray(wstar)
        params0 = {"w": jnp.zeros((d,), jnp.float32)}

        def loss_fn(params, batch, key):
            del key
            return jnp.sum((params["w"] - batch["target"]) ** 2)

        def client_batches(cids, keys):
            # per-client heterogeneous targets: w* + client-seeded noise
            noise = np.stack([np.random.default_rng(int(c)).normal(
                0.0, 0.05, (2, d)).astype(np.float32) for c in cids])
            return {"target": jnp.asarray(wstar_np[None, None, :] + noise)}
        client_batches.batched = True

        def eval_fn(p):
            err = jnp.linalg.norm(p["w"] - wstar) / jnp.linalg.norm(wstar)
            return float(1.0 - err)
    else:
        ds = SyntheticCelebA(n_samples=args.samples)
        part = FederatedPartition(labels=ds.labels,
                                  n_clients=args.samples // 10)
        params0 = init_cnn(jax.random.PRNGKey(0))

        def loss_fn(params, batch, key):
            return cnn_loss(params, batch, train=True, key=key)[0]

        rng = np.random.default_rng(args.seed)

        def client_batches(cid, key):
            b = [part.client_batch(ds, cid, 8, rng) for _ in range(2)]
            return {k: jnp.stack([jnp.asarray(bi[k]) for bi in b])
                    for k in b[0]}

        test_idx = part.split_indices(part.val_clients)[:256]
        test_batch = {k: jnp.asarray(v) for k, v in ds.batch(test_idx).items()}
        eval_fn = jax.jit(lambda p: cnn_accuracy(p, test_batch))

    qcfg = QAFeLConfig(client_lr=0.05, server_lr=1.0, server_momentum=0.3,
                       buffer_size=args.buffer, local_steps=2,
                       client_quantizer="qsgd4", server_quantizer="qsgd4")
    tracer = None
    if args.trace is not None:
        from repro.obs import RunTracer
        tracer = RunTracer(taps=True)
    algo = QAFeL(qcfg, loss_fn, params0, mesh=mesh, telemetry=tracer)
    engine_cls = (PopulationAsyncFLSimulator if args.engine == "population"
                  else CohortAsyncFLSimulator)
    sim = engine_cls(
        algo,
        SimConfig(concurrency=args.concurrency, max_uploads=args.uploads,
                  eval_every_steps=3, seed=args.seed),
        client_batches, eval_fn,
        scenario=args.scenario, cohort_size=args.cohort_size)
    res = sim.run()
    m = res.metrics
    print(f"engine={args.engine}  model={args.model}  "
          f"scenario={args.scenario}  cohort_size={args.cohort_size}  "
          f"concurrency={args.concurrency}  devices={args.devices}")
    print(f"  uploads: {res.uploads}  dropped: {m['dropped_uploads']}  "
          f"server steps: {res.server_steps}  tau_max: {m['tau_max']}")
    print(f"  kB/upload: {m['kB_per_upload']:.2f}  upload MB: "
          f"{m['upload_MB']:.2f}  broadcast MB: {m['broadcast_MB']:.2f}")
    print(f"  final accuracy: {res.final_accuracy:.3f}  replicas in sync: "
          f"{m['replicas_in_sync']}")
    if "population_states" in m:
        states = "  ".join(f"{k}={v}" for k, v in
                           m["population_states"].items())
        print(f"  population: {states}")
    assert m["replicas_in_sync"]
    if args.min_acc is not None:
        assert res.final_accuracy >= args.min_acc, (
            f"accuracy {res.final_accuracy:.3f} < required {args.min_acc}")
        print(f"  convergence check passed (>= {args.min_acc})")
    if tracer is not None:
        from repro.obs import summary_table, validate_jsonl, write_jsonl
        write_jsonl(tracer, args.trace)
        errors = validate_jsonl(args.trace)
        assert not errors, f"trace schema errors: {errors[:5]}"
        print(summary_table(tracer, title=f"telemetry ({args.trace})"))
        print(f"  trace: {len(tracer.events())} events -> {args.trace} "
              f"(schema OK)")


if __name__ == "__main__":
    main()
