"""Microbenchmarks of the communication-path kernels (the op the paper's
technique puts on the critical path of every round).

On CPU the Pallas kernels run in interpret mode, so absolute us_per_call is
NOT a TPU number; the derived column carries the structural quantities that
transfer: wire-compression ratio and bytes touched per element (the kernels
are designed to be HBM-streaming: read-once/write-once).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.core.buffer import UpdateBuffer
from repro.core.quantizers import make_quantizer
from repro.kernels import ops
from repro.models.cnn import init_cnn


def _time(fn, *args, iters=5):
    fn(*args)  # compile
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / iters * 1e6


def _interleaved_best(fn_a, fn_b, iters=7):
    """Time two pipelines ALTERNATING per iteration and reduce by MIN,
    returning (us_a, us_b). The one protocol for every ``--check``-gated
    A/B row: on a small shared CPU back-to-back means drift by >2x with
    machine load, and even interleaved medians swing ~30% under bursty
    contention — min-of-N picks each pipeline's quietest iteration, which
    is the stable estimator of the structural latency the ratio is meant
    to compare."""
    jax.block_until_ready(fn_a())  # compile both before timing
    jax.block_until_ready(fn_b())
    ta, tb = [], []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn_a())
        ta.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        jax.block_until_ready(fn_b())
        tb.append(time.perf_counter() - t0)
    return min(ta) * 1e6, min(tb) * 1e6


def main(report):
    n = 1 << 20  # 1M-element message (~4 MB fp32)
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (n,), jnp.float32)
    for bits in (2, 4, 8):
        us = _time(lambda: ops.qsgd_quantize(x, key, bits)[0])
        packed, norms = ops.qsgd_quantize(x, key, bits)
        wire = packed.nbytes + norms.nbytes
        ratio = x.nbytes / wire
        report(f"kernel/qsgd{bits}_quantize_1M", us,
               f"wire_bytes={wire};compression=x{ratio:.2f}")
        us_d = _time(lambda: ops.qsgd_dequantize(packed, norms, bits, n))
        report(f"kernel/qsgd{bits}_dequantize_1M", us_d, f"out_bytes={x.nbytes}")
    # fused buffer aggregation, K=10 (the paper's buffer size)
    k = 10
    msgs, norms_l = [], []
    for i in range(k):
        p, nm = ops.qsgd_quantize(
            jax.random.normal(jax.random.PRNGKey(i), (n,)), jax.random.PRNGKey(50 + i), 4)
        msgs.append(p)
        norms_l.append(nm)
    stack, nstack = jnp.stack(msgs), jnp.stack(norms_l)
    w = jnp.full((k,), 0.1)
    us = _time(lambda: ops.buffer_aggregate(stack, nstack, w, 4, n))
    hbm = stack.nbytes + nstack.nbytes + x.nbytes  # one read + one write
    naive = k * (stack.nbytes // k + x.nbytes) + (k + 1) * x.nbytes
    report("kernel/buffer_agg_K10_1M", us,
           f"fused_hbm_bytes={hbm};naive_hbm_bytes={naive};saving=x{naive/hbm:.2f}")
    batch_encode_bench(report)
    wire_path_bench(report)
    lowrank_wire_bench(report)
    server_flush_bench(report)
    cohort_step_bench(report)
    sim_engine_bench(report)
    population_bench(report)
    shard_bench(report)
    shard2d_bench(report)


def batch_encode_bench(report):
    """Batched (B, D) quantize-pack dispatch vs B single-message dispatches:
    the kernel-level half of the cohort engine's speedup. Every row carries
    the achieved encode bandwidth (wire bytes emitted / us_per_call) so the
    bandwidth-bound regime — the d=98304 rows, where throughput is pinned
    by the quantize-pack stream, not dispatch count — is visible in
    ``--check`` diffs and the committed BENCH json."""
    key = jax.random.PRNGKey(0)
    for n, tag in ((1 << 17, ""), (98304, "_d98304")):
        for b in (16, 64):
            x2d = jax.random.normal(key, (b, n), jnp.float32)
            keys = jax.random.split(jax.random.PRNGKey(1), b)
            us_one = _time(
                lambda: [ops.qsgd_quantize(x2d[i], keys[i], 4)[0]
                         for i in range(b)],
                iters=3)
            us_batch = _time(lambda: ops.qsgd_quantize_batch(x2d, keys, 4)[0],
                             iters=3)
            p, nm = ops.qsgd_quantize_batch(x2d, keys, 4)
            wire = p.nbytes + nm.nbytes
            report(f"kernel/qsgd4_quantize_batch{tag}_B{b}", us_batch,
                   f"dispatches=1;per_msg_total={us_one:.1f};"
                   f"wire_bytes={wire};"
                   f"encode_GBps={wire / (us_batch * 1e3):.3f};"
                   f"speedup=x{us_one / us_batch:.2f}")


def server_flush_bench(report):
    """The device-resident flat server state's fused single-dispatch flush
    (``kernels.ops.server_flush_step`` via ``QAFeL.receive``) vs the
    pre-refactor eager tree composition: fused aggregate + unflatten +
    per-leaf tree_axpy server update + encode + decode + per-leaf hidden
    apply. Both cycles ingest the same K pre-encoded uploads.

    The structural quantities that transfer off CPU: one host-issued device
    dispatch per flush vs ~9 + O(10 * n_leaves) eager ops, and zero
    per-leaf pytree traffic between kernels. Three sizes: flat d=2048 and
    d=98304 (single leaf — the quickstart and wire-size scales) and the
    paper's 18-leaf CNN (per-leaf tree traffic dominates the legacy path;
    the fused win is largest here). CPU latency caveat: single-leaf
    large-d is memory-bandwidth-bound and its wall-clock ratio is noisy /
    near parity in interpret mode — the dispatch-count column is the
    robust quantity."""
    from repro.common.tree import tree_add, tree_axpy, tree_sub
    from repro.core import QAFeL, QAFeLConfig
    from repro.core.protocol import (CLIENT_UPDATE, HIDDEN_BROADCAST, Message,
                                     decode_message, encode_message)

    k = 10
    qcfg = QAFeLConfig(client_lr=0.05, server_lr=1.0, server_momentum=0.3,
                       buffer_size=k, local_steps=1,
                       client_quantizer="qsgd4", server_quantizer="qsgd4")

    def unused_loss(params, batch, key):
        del batch, key
        return 0.0

    for tag, params in (("d2048", {"w": jnp.zeros((2048,), jnp.float32)}),
                        ("d98304", {"w": jnp.zeros((98304,), jnp.float32)}),
                        ("cnn18", init_cnn(jax.random.PRNGKey(0)))):
        cq, sq = qcfg.cq(), qcfg.sq()
        n_leaves = len(jax.tree.leaves(params))
        d = sum(int(x.size) for x in jax.tree.leaves(params))
        key = jax.random.PRNGKey(1)
        encs = [cq.encode(jax.tree.map(
            lambda a, i=i: jax.random.normal(jax.random.PRNGKey(7 * i), a.shape),
            params), jax.random.PRNGKey(100 + i)) for i in range(k)]
        msgs = [Message(CLIENT_UPDATE, e, wire_bytes=0.0, meta={"version": 0})
                for e in encs]
        layout = encs[0]["layout"]

        algo = QAFeL(qcfg, unused_loss, params)

        def fused_cycle():
            bmsg = None
            for m in msgs:
                bmsg = algo.receive(m, key)
            return bmsg.payload["packed"]

        # pre-refactor composition over the same uploads (tree state)
        x_t = jax.tree.map(jnp.array, params)
        h_t = jax.tree.map(jnp.array, params)
        m_t = jax.tree.map(jnp.zeros_like, params)

        def legacy_cycle():
            stack = jnp.stack([e["packed"] for e in encs])
            norms = jnp.stack([e["norms"] for e in encs])
            w = jnp.asarray([1.0] * k, jnp.float32) / k
            flat = ops.buffer_aggregate(stack, norms, w, 4, d)
            out = layout.unflatten(flat)
            m_new = tree_axpy(qcfg.server_momentum, m_t, out)
            x_new = tree_axpy(qcfg.server_lr, m_new, x_t)
            diff = tree_sub(x_new, h_t)
            bmsg = encode_message(HIDDEN_BROADCAST, sq, diff, key, fast=True)
            q = decode_message(sq, bmsg)
            h_new = tree_add(h_t, q)
            return jax.tree.leaves(h_new)

        # --check-gated rows: interleaved min-of-N so load drift cancels
        us_fused, us_legacy = _interleaved_best(fused_cycle, legacy_cycle)
        host_ops = 9 + 10 * n_leaves  # eager device ops the legacy path issues
        report(f"server/flush_fused_{tag}", us_fused,
               f"dispatches=1;d={d};K={k};leaves={n_leaves}")
        report(f"server/flush_legacy_{tag}", us_legacy,
               f"dispatches~{host_ops};d={d};K={k};leaves={n_leaves}")
        report(f"server/flush_speedup_{tag}", 0.0,
               f"x{us_legacy / us_fused:.2f};dispatch_reduction=x{host_ops}")


def cohort_step_bench(report):
    """Fused one-dispatch cohort train+encode (``kernels.ops.
    cohort_train_encode_step``) vs the split pipeline it replaced —
    jit(vmap(client_update)) dispatch, eager per-leaf flatten, host-side
    ``encode_batch`` dispatch — on the same cohorts.

    The structural quantities that transfer off CPU: ONE host-issued device
    dispatch per cohort tier-group vs 2 jit dispatches + O(n_leaves) eager
    flatten ops, no stacked delta pytree and no hidden_tree view between
    them. Rows at the engine's cohort sizes for concurrency 100/500
    (B = min(conc // 2, 64)) on d=2048 (engine regime) and the paper's
    18-leaf CNN; uploads/sec is B / wall per pipeline run. These rows feed
    the ``--check`` regression gate, so the two pipelines are timed
    INTERLEAVED and reduced by min-of-N (``_interleaved_best``) — on a
    small shared CPU the back-to-back mean drifts by >2x with machine
    load. CPU latency caveat (same as the flush rows):
    the CNN's conv-grad compute dominates at cnn18, so its wall-clock
    ratio sits near parity in interpret mode — dispatches per cohort is
    the robust column."""
    import functools

    from repro.core.qafel import QAFeLConfig, client_update
    from repro.core.quantizers import flatten_tree, make_quantizer

    qcfg = QAFeLConfig(client_lr=0.05, server_lr=1.0, server_momentum=0.3,
                       buffer_size=10, local_steps=2,
                       client_quantizer="qsgd4", server_quantizer="qsgd4")
    q = make_quantizer("qsgd4")
    flag = jnp.asarray(True)

    def loss_fn(params, batch, key):
        del key
        t = batch["target"]
        return sum(jnp.sum((l - t) ** 2) for l in jax.tree.leaves(params))

    for tag, params in (("d2048", {"w": jnp.zeros((2048,), jnp.float32)}),
                        ("cnn18", init_cnn(jax.random.PRNGKey(0)))):
        flat0, layout = flatten_tree(params)
        hidden_tree = layout.unflatten(flat0)
        n_leaves = len(jax.tree.leaves(params))
        vmapped = jax.jit(jax.vmap(
            functools.partial(client_update, loss_fn, qcfg),
            in_axes=(None, 0, 0)))
        for conc in (100, 500):
            b = min(conc // 2, 64)  # the engine's cohort-size heuristic
            batches = {"target": jax.random.normal(
                jax.random.PRNGKey(3), (b, qcfg.local_steps, 1))}
            keys = jax.random.split(jax.random.PRNGKey(4), 2 * b)
            tk, ek = keys[:b], keys[b:]

            def fused():
                return ops.cohort_train_encode_step(
                    loss_fn, qcfg, q.spec, layout, flat0, batches, tk, ek,
                    flag, b=b)["packed"]

            def split():
                deltas = vmapped(hidden_tree, batches, tk)
                return q.encode_batch(deltas, ek)[0]["packed"]

            us_f, us_s = _interleaved_best(fused, split)
            ups_f, ups_s = b / (us_f / 1e6), b / (us_s / 1e6)
            report(f"sim/cohort_step_fused_{tag}_conc{conc}", us_f,
                   f"dispatches=1;B={b};leaves={n_leaves};"
                   f"uploads_per_s={ups_f:.1f}")
            report(f"sim/cohort_step_split_{tag}_conc{conc}", us_s,
                   f"dispatches~{2 + n_leaves};B={b};leaves={n_leaves};"
                   f"uploads_per_s={ups_s:.1f}")
            report(f"sim/cohort_step_speedup_{tag}_conc{conc}", 0.0,
                   f"speedup=x{us_s / us_f:.2f};"
                   f"dispatch_reduction=x{2 + n_leaves}")


def sim_engine_bench(report):
    """Cohort engine vs the sequential reference: end-to-end simulator
    throughput (uploads/sec) at the paper's concurrency scale.

    The client task is a convex problem whose local step is a few
    elementwise ops: client FLOPs are a property of the model, identical
    under both engines, and a compute-heavy model (the CNN's grouped-conv
    gradients on a 2-core CPU) drowns exactly the per-upload orchestration
    + wire-path cost this subsystem changes. What these rows quantify is
    the engine: per-client jit dispatches, threefry dither, per-message
    interpret-mode kernel calls and key splits, all of which the cohort
    path batches. Two model sizes: d=2048 (the quickstart regime — engine
    overhead dominates, full cohort effect) and d=98304 (the CNN
    benchmark's wire-size regime with zero tile padding — throughput is
    encode-bound). NOTE since the fused client pipeline: the sequential
    engine runs the SAME one-dispatch train+encode step per client (b=1),
    so at encode-bound d=98304 the cohort win comes from the member-chunked
    lax.scan encode (``sim.cohort.auto_member_chunk``) keeping the (B, d)
    delta working set cache-resident.

    Throughput is the TWO-POINT SLOPE (N2 - N1) / (wall_N2 - wall_N1):
    the cohort engine speculatively admits ~concurrency in-flight members
    whatever ``max_uploads`` is, so a single short run charges that fixed
    admission tail against throughput (at concurrency 500 and 120 uploads
    the tail is ~4x the delivered work) — the slope between two run lengths
    cancels it and measures the steady-state marginal cost per upload,
    which is what the paper's long concurrency sweeps actually pay. CPU
    interpret-mode numbers; the structural quantity that transfers is the
    uploads/sec ratio."""
    from repro.core import QAFeL, QAFeLConfig
    from repro.sim import AsyncFLSimulator, CohortAsyncFLSimulator, SimConfig

    qcfg = QAFeLConfig(client_lr=0.05, server_lr=1.0, server_momentum=0.3,
                       buffer_size=10, local_steps=2,
                       client_quantizer="qsgd4", server_quantizer="qsgd4")

    def loss_fn(params, batch, key):
        del key
        return jnp.mean((params["w"] - batch["target"]) ** 2)

    def build_sim(engine, d, conc, uploads):
        params0 = {"w": jnp.zeros((d,), jnp.float32)}
        base = jax.random.normal(jax.random.PRNGKey(7), (2, d), jnp.float32)
        if engine == "cohort":
            # batched-provider protocol: hand the engine the whole cohort's
            # batches as ONE preloaded stacked tensor (the same fixed data
            # the sequential fn returns per client, with zero per-cohort
            # stack/copy cost for either engine)
            b = min(conc // 2, 64)
            stacked = {"target": jnp.broadcast_to(base, (b,) + base.shape)
                       + jnp.zeros((b, 1, 1), jnp.float32)}
            jax.block_until_ready(stacked["target"])

            def client_batches(cids, keys):
                assert len(cids) == b
                return stacked
            client_batches.batched = True
        else:
            client_batches = lambda cid, key: {"target": base}
        eval_fn = lambda params: 0.0
        algo = QAFeL(qcfg, loss_fn, params0)
        scfg = SimConfig(concurrency=conc, max_uploads=uploads,
                         eval_every_steps=10**9, track_hidden_replicas=0,
                         seed=0)
        if engine == "sequential":
            return AsyncFLSimulator(algo, scfg, client_batches, eval_fn)
        return CohortAsyncFLSimulator(algo, scfg, client_batches, eval_fn,
                                      scenario="identity",
                                      cohort_size=min(conc // 2, 64))

    n1, n2 = 120, 360
    for d in (2048, 98304):
        for conc in (100, 500):
            ups = {}
            for engine in ("sequential", "cohort"):
                # warm every jit/kernel path at this exact cohort shape
                build_sim(engine, d, conc, 12).run()
                walls = {}
                for n in (n1, n2):
                    sim = build_sim(engine, d, conc, n)
                    t0 = time.perf_counter()
                    r = sim.run()
                    walls[n] = time.perf_counter() - t0
                    assert r.uploads == n
                slope = (walls[n2] - walls[n1]) / (n2 - n1)
                ups[engine] = 1.0 / slope
                report(f"sim/{engine}_d{d}_conc{conc}", slope * 1e6,
                       f"uploads={n2};uploads_per_s={ups[engine]:.1f};"
                       f"us_per_upload_marginal={slope * 1e6:.1f}")
            report(f"sim/cohort_speedup_d{d}_conc{conc}", 0.0,
                   f"x{ups['cohort'] / ups['sequential']:.2f}_uploads_per_s")


def population_bench(report):
    """Device-resident population engine: full-sim throughput at 1k
    concurrency vs the cohort event loop, and the lifecycle substrate alone
    at 100k / 1M clients.

    The conc-1000 row runs the population engine at its intended operating
    point — large admission batches (cohort_size = deliver_batch = 512),
    which is exactly what the fused kernel buys: one dispatch admits half
    the in-flight pool, where the event loop pays per-cohort Python
    bookkeeping.  The baseline row is the cohort engine at ITS committed
    protocol (conc 500, cohort_size 64 — the same config as
    sim_engine_bench's ``sim/cohort_d2048_conc500`` row), so the gated
    speedup row documents the acceptance claim: more uploads/sec while
    simulating TWICE the in-flight clients.  Both engines are measured
    with the same in-run stamp protocol (``steady_us`` below) over the
    [1200, 2400]-upload window — well past the population engine's
    admission ramp: the kernel admits by arrival time, so the in-flight
    pool ramps 0 -> conc over the first ~conc uploads with partial
    deliver batches throughout, whereas the event loop admits ~conc
    speculatively up front and is saturated immediately.  The window
    start also cancels each engine's jit/admission tail.

    The 100k / 1M rows run ``PopulationEngine`` (no model attached: the
    same fused macro step, admission draws, deadline wheel and staleness
    accounting, minus train/encode) to a fixed sim-time horizon.  Since
    the batched top_k deliver replaced the sequential pop scan, a macro
    step is flat ~20ms at 1.5M slots, so the derived events/sec scales
    with the admission batch; the horizons shrink with scale to keep the
    rows CI-sized while the array scale (1.5M slots at 1M clients) is
    real."""
    from repro.core import QAFeL, QAFeLConfig
    from repro.sim import (CohortAsyncFLSimulator, PopulationAsyncFLSimulator,
                           PopulationEngine, SimConfig)

    qcfg = QAFeLConfig(client_lr=0.05, server_lr=1.0, server_momentum=0.3,
                       buffer_size=10, local_steps=2,
                       client_quantizer="qsgd4", server_quantizer="qsgd4")
    d = 2048

    def loss_fn(params, batch, key):
        del key
        return jnp.mean((params["w"] - batch["target"]) ** 2)

    base = jax.random.normal(jax.random.PRNGKey(7), (2, d), jnp.float32)

    def build_sim(engine, conc, uploads, b):
        stacked = {"target": jnp.broadcast_to(base, (b,) + base.shape)
                   + jnp.zeros((b, 1, 1), jnp.float32)}
        jax.block_until_ready(stacked["target"])

        def client_batches(cids, keys):
            assert len(cids) == b
            return stacked
        client_batches.batched = True
        algo = QAFeL(qcfg, loss_fn, {"w": jnp.zeros((d,), jnp.float32)})
        scfg = SimConfig(concurrency=conc, max_uploads=uploads,
                         eval_every_steps=10**9,
                         track_hidden_replicas=0, seed=0)
        if engine == "population":
            return PopulationAsyncFLSimulator(
                algo, scfg, client_batches, lambda params: 0.0,
                scenario="identity", cohort_size=b, deliver_batch=b)
        return CohortAsyncFLSimulator(algo, scfg, client_batches,
                                      lambda params: 0.0,
                                      scenario="identity", cohort_size=b)

    def steady_us(engine, conc, b, n1, n2):
        """Marginal us/upload between deliveries n1 and n2 of ONE run
        (wall-clock stamps hooked on ``algo.receive``), min-of-2 runs.

        In-run stamps rather than the cross-run two-point slope: at these
        scales a full run is only 0.2-3 s of wall, so separate-run slopes
        are load-spike-dominated (a single background blip flips them
        negative), while the stamped window shares one process-warm run
        and excludes both the jit tail and each engine's admission ramp.
        min-of-2 is the same noise discipline as _interleaved_best."""
        best = float("inf")
        for _ in range(2):
            sim = build_sim(engine, conc, n2, b)
            stamps = {}
            seen = [0]
            real = sim.algo.receive

            def wrapped(*a, _real=real, _seen=seen, _stamps=stamps, **kw):
                out = _real(*a, **kw)
                _seen[0] += 1
                if _seen[0] in (n1, n2):
                    _stamps[_seen[0]] = time.perf_counter()
                return out
            sim.algo.receive = wrapped
            r = sim.run()
            assert r.uploads == n2
            best = min(best, (stamps[n2] - stamps[n1]) / (n2 - n1))
        return best

    n1, n2 = 1200, 2400
    ups = {}
    for engine, conc, b in (("cohort", 500, 64), ("population", 1000, 512)):
        build_sim(engine, conc, max(24, b // 4), b).run()  # warm the jits
        slope = steady_us(engine, conc, b, n1, n2)
        ups[engine] = 1.0 / slope
        if engine == "population":
            report(f"sim/population_d{d}_conc{conc}", slope * 1e6,
                   f"uploads={n2};cohort_size={b};"
                   f"uploads_per_s={ups[engine]:.1f};"
                   f"us_per_upload_marginal={slope * 1e6:.1f}")
    report(f"sim/population_speedup_d{d}_conc1000", 0.0,
           f"x{ups['population'] / ups['cohort']:.2f}_uploads_per_s_vs_"
           f"cohort_conc500")

    # lifecycle substrate at population scale: fixed sim-time horizons
    for conc, horizon in ((100_000, 1.0), (1_000_000, 0.05)):
        eng = PopulationEngine("lognormal_dropout", conc, horizon=horizon,
                               seed=0)
        t0 = time.perf_counter()
        m = eng.advance_to(horizon)
        wall = time.perf_counter() - t0
        events = m["admitted"] + m["delivered"]
        report(f"sim/population_d{d}_conc{conc}", wall * 1e6,
               f"horizon={horizon};arrivals={m['admitted']};"
               f"deliveries={m['delivered']};dropped={m['dropped']};"
               f"macro_steps={m['macro_steps']};"
               f"events_per_s={events / wall:.0f}")


def _shard_measurements(ndev: int):
    """The mesh-sharded fused dispatches vs the single-device ones on the
    same work, at one device count: cohort train+encode (member-sharded)
    and the server flush (segment-sharded). Returns (name, us, derived)
    rows; both pipelines are timed INTERLEAVED and reduced by min-of-N
    (the one protocol for --check-gated rows).

    On a 2-core CI box, 8 virtual devices time-slice the same cores, so
    the ndev=8 wall-clock ratio is expected at/below parity (sub-parity
    caveat rows: they document the overhead, the bit-exactness tests carry
    the correctness claim, and real multi-device wins need real devices).
    """
    from repro.core import QAFeL, QAFeLConfig
    from repro.core.protocol import CLIENT_UPDATE, Message
    from repro.core.quantizers import flatten_tree, make_quantizer
    from repro.launch.mesh import make_sim_mesh

    mesh = make_sim_mesh(ndev)
    q = make_quantizer("qsgd4")
    qcfg = QAFeLConfig(client_lr=0.05, server_lr=1.0, server_momentum=0.3,
                       buffer_size=10, local_steps=2,
                       client_quantizer="qsgd4", server_quantizer="qsgd4")
    flag = jnp.asarray(True)
    rows = []

    def loss_fn(params, batch, key):
        del key
        return jnp.mean((params["w"] - batch["target"]) ** 2)

    # -- cohort step: member-sharded vs single dispatch ------------------
    d, b = 1 << 15, 16
    params = {"w": jnp.zeros((d,), jnp.float32)}
    flat0, layout = flatten_tree(params)
    batches = {"target": jax.random.normal(
        jax.random.PRNGKey(3), (b, qcfg.local_steps, d))}
    keys = jax.random.split(jax.random.PRNGKey(4), 2 * b)
    tk, ek = keys[:b], keys[b:]

    def cohort_sharded():
        return ops.cohort_train_encode_step(
            loss_fn, qcfg, q.spec, layout, flat0, batches, tk, ek, flag,
            b=b, mesh=mesh)["packed"]

    def cohort_single():
        return ops.cohort_train_encode_step(
            loss_fn, qcfg, q.spec, layout, flat0, batches, tk, ek, flag,
            b=b)["packed"]

    us_sh, us_si = _interleaved_best(cohort_sharded, cohort_single)
    rows.append((f"shard/cohort_step_sharded_ndev{ndev}", us_sh,
                 f"B={b};d={d};ndev={ndev}"))
    rows.append((f"shard/cohort_step_single_ndev{ndev}", us_si,
                 f"B={b};d={d};ndev=1"))
    rows.append((f"shard/cohort_step_speedup_ndev{ndev}", 0.0,
                 f"speedup=x{us_si / us_sh:.2f};bit_identical=1"))

    # -- server flush: segment-sharded vs single dispatch ----------------
    k = qcfg.buffer_size
    encs = [q.encode({"w": jax.random.normal(jax.random.PRNGKey(7 * i), (d,))},
                     jax.random.PRNGKey(100 + i)) for i in range(k)]
    msgs = [Message(CLIENT_UPDATE, e, wire_bytes=0.0, meta={"version": 0})
            for e in encs]
    key = jax.random.PRNGKey(1)
    algo_sh = QAFeL(qcfg, loss_fn, params, mesh=mesh)
    algo_si = QAFeL(qcfg, loss_fn, params)

    def flush_cycle(algo):
        bmsg = None
        for m in msgs:
            bmsg = algo.receive(m, key)
        return bmsg.payload["packed"]

    us_sh, us_si = _interleaved_best(lambda: flush_cycle(algo_sh),
                                     lambda: flush_cycle(algo_si))
    rows.append((f"shard/flush_sharded_ndev{ndev}", us_sh,
                 f"d={d};K={k};ndev={ndev}"))
    rows.append((f"shard/flush_single_ndev{ndev}", us_si,
                 f"d={d};K={k};ndev=1"))
    rows.append((f"shard/flush_speedup_ndev{ndev}", 0.0,
                 f"speedup=x{us_si / us_sh:.2f};bit_identical=1"))
    return rows


def shard_bench(report):
    """``shard/cohort_step_*`` and ``shard/flush_*`` rows at ndev in {1, 8}.

    ndev=1 runs in-process (the sharded path as a one-segment shard_map —
    its overhead over the plain dispatch is the substrate's fixed cost);
    ndev=8 needs 8 fake host devices, which XLA only grants BEFORE jax
    initializes, so it runs as a ``python -m benchmarks.kernel_bench
    --shard-ndev 8`` subprocess whose rows are parsed and re-reported.

    On a TPU one process owns the chips, so a child could not reach them:
    the multi-device rows run in-process over every visible chip instead."""
    import os
    import subprocess
    import sys

    for row in _shard_measurements(1):
        report(*row)
    if jax.default_backend() == "tpu":
        if jax.device_count() > 1:
            for row in _shard_measurements(jax.device_count()):
                report(*row)
        return
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    # APPEND the device-count flag to any caller XLA_FLAGS: the ndev=8 rows
    # must run under the same compiler flags as the in-process ndev=1 rows
    # or the gated speedup ratio compares different compilers
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in flags:
        flags = f"{flags} --xla_force_host_platform_device_count=8".strip()
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.kernel_bench", "--shard-ndev", "8"],
        capture_output=True, text=True, timeout=560,
        env={**os.environ, "PYTHONPATH": os.path.join(repo, "src"),
             "XLA_FLAGS": flags},
        cwd=repo)
    if out.returncode != 0:
        raise RuntimeError("shard ndev=8 subprocess failed: "
                           + out.stdout[-1000:] + out.stderr[-2000:])
    for line in out.stdout.splitlines():
        if line.startswith("shard/"):
            name, us, derived = line.split(",", 2)
            report(name, float(us), derived)


def _shard2d_measurements():
    """The LLM-scale substrate's 2-D ("data","model") chunked paths vs the
    single-device fused dispatches on the same work — run with 8 forced
    host devices (both mesh shapes measured in ONE process so they share a
    compiler and warm caches). Returns (name, us, derived) rows.

    d=98304 (768 wire rows) at mesh (2,4) and (8,1): cohort train+encode
    with the row-chunked streaming encode, and the segment-sharded chunked
    flush — both timed INTERLEAVED against the single-device dispatch and
    reduced by min-of-N (the one protocol for --check-gated rows). Every
    sharded row's derived carries the achieved encode GB/s and the
    structural memory bound the 2-D layout buys: peak device-resident
    packed-code bytes <= total wire bytes / ndev_model + one chunk.

    The ≥1e8-d synthetic row is the tentpole's exit proof: ONE end-to-end
    federated round (streamed uplink chunks -> chunk-reassembling buffer ->
    chunked sharded flush) on a 1e8-parameter flat config at mesh (2,4) —
    a scale where replicating K full packed uploads per device is exactly
    what the d-sharded buffer avoids. Informational (no single-device twin
    to ratio against — the point is that it RUNS within the memory bound),
    so it is not a --check-gated speedup row.

    Same 2-core CI caveat as ``_shard_measurements``: 8 virtual devices
    time-slice the same cores, so wall-clock ratios at/below parity
    document overhead; the bit-exactness tests (tests/test_mesh2d.py)
    carry the correctness claim.
    """
    from repro.core import QAFeL, QAFeLConfig
    from repro.core.protocol import CLIENT_UPDATE, Message
    from repro.core.quantizers import flatten_tree, make_quantizer
    from repro.launch.mesh import make_sim_mesh2d

    q = make_quantizer("qsgd4")
    qcfg = QAFeLConfig(client_lr=0.05, server_lr=1.0, server_momentum=0.3,
                       buffer_size=10, local_steps=2,
                       client_quantizer="qsgd4", server_quantizer="qsgd4")
    flag = jnp.asarray(True)
    rows = []

    def loss_fn(params, batch, key):
        del key
        return jnp.mean((params["w"] - batch["target"]) ** 2)

    d, b, chunk = 98304, 8, 96  # 768 wire rows; 192/model shard at (2,4)
    wire_rows = d // 128
    row_bytes = 128 * 4 // 8 + 4  # packed codes + one f32 bucket norm
    params = {"w": jnp.zeros((d,), jnp.float32)}
    flat0, layout = flatten_tree(params)
    batches = {"target": jax.random.normal(
        jax.random.PRNGKey(3), (b, qcfg.local_steps, d))}
    keys = jax.random.split(jax.random.PRNGKey(4), 2 * b)
    tk, ek = keys[:b], keys[b:]
    k = qcfg.buffer_size
    encs = [q.encode({"w": jax.random.normal(jax.random.PRNGKey(7 * i), (d,))},
                     jax.random.PRNGKey(100 + i)) for i in range(k)]
    msgs = [Message(CLIENT_UPDATE, e, wire_bytes=0.0, meta={"version": 0})
            for e in encs]
    key = jax.random.PRNGKey(1)

    def flush_cycle(algo):
        bmsg = None
        for m in msgs:
            bmsg = algo.receive(m, key)
        return bmsg.payload["packed"]

    for shape in ((2, 4), (8, 1)):
        tag = f"{shape[0]}x{shape[1]}"
        mesh = make_sim_mesh2d(shape)
        n_model = shape[1]
        cohort_wire = b * wire_rows * row_bytes
        chunk_bytes = b * chunk * row_bytes
        peak = cohort_wire // n_model + chunk_bytes

        def cohort2d():
            return ops.cohort_train_encode_step(
                loss_fn, qcfg, q.spec, layout, flat0, batches, tk, ek, flag,
                b=b, mesh=mesh, chunk_rows=chunk)["packed"]

        def cohort_single():
            return ops.cohort_train_encode_step(
                loss_fn, qcfg, q.spec, layout, flat0, batches, tk, ek, flag,
                b=b)["packed"]

        us_sh, us_si = _interleaved_best(cohort2d, cohort_single)
        rows.append((f"shard2d/cohort_step_{tag}_d{d}", us_sh,
                     f"B={b};d={d};chunk_rows={chunk};"
                     f"encode_GBps={cohort_wire / (us_sh * 1e3):.3f};"
                     f"peak_packed_bytes_per_dev={peak}"))
        rows.append((f"shard2d/cohort_step_single_{tag}_d{d}", us_si,
                     f"B={b};d={d};ndev=1;"
                     f"encode_GBps={cohort_wire / (us_si * 1e3):.3f};"
                     f"peak_packed_bytes_per_dev={cohort_wire}"))
        rows.append((f"shard2d/cohort_step_speedup_{tag}_d{d}", 0.0,
                     f"speedup=x{us_si / us_sh:.2f};bit_identical=1;"
                     f"packed_mem_reduction=x{cohort_wire / peak:.2f}"))

        # fresh zero params per server: the flush DONATES x/hidden/momentum,
        # and a single-leaf f32 tree flattens to an aliased buffer — sharing
        # ``params`` would delete ``flat0`` out from under the next shape
        algo_sh = QAFeL(qcfg, loss_fn, {"w": jnp.zeros((d,), jnp.float32)},
                        mesh=mesh, chunk_rows=chunk)
        algo_si = QAFeL(qcfg, loss_fn, {"w": jnp.zeros((d,), jnp.float32)})
        us_sh, us_si = _interleaved_best(lambda: flush_cycle(algo_sh),
                                         lambda: flush_cycle(algo_si))
        rows.append((f"shard2d/flush_{tag}_d{d}", us_sh,
                     f"d={d};K={k};chunk_rows={chunk};"
                     f"buffer_bytes_per_dev={k * wire_rows * row_bytes // n_model}"))
        rows.append((f"shard2d/flush_single_{tag}_d{d}", us_si,
                     f"d={d};K={k};ndev=1;"
                     f"buffer_bytes_per_dev={k * wire_rows * row_bytes}"))
        rows.append((f"shard2d/flush_speedup_{tag}_d{d}", 0.0,
                     f"speedup=x{us_si / us_sh:.2f};bit_identical=1"))

    # -- exit proof: one e2e federated round at d = 1e8, mesh (2,4) --------
    d8 = 100_000_000
    rows8 = d8 // 128
    chunk8 = 8192  # 8192 rows/chunk: ~0.56 MB of codes in flight per chunk
    wire8 = rows8 * row_bytes
    kbuf = 2
    qcfg8 = QAFeLConfig(client_lr=0.05, server_lr=1.0, server_momentum=0.0,
                        buffer_size=kbuf, local_steps=1,
                        client_quantizer="qsgd4", server_quantizer="qsgd4")
    mesh = make_sim_mesh2d((2, 4))
    algo = QAFeL(qcfg8, loss_fn, {"w": jnp.zeros((d8,), jnp.float32)},
                 mesh=mesh, chunk_rows=chunk8)
    target = jax.random.normal(jax.random.PRNGKey(9), (1, d8), jnp.float32)
    jax.block_until_ready(target)
    t0 = time.perf_counter()
    bmsg = None
    for i in range(kbuf):
        msgs8, _ = algo.run_client_stream({"target": target},
                                          jax.random.PRNGKey(20 + i))
        for m in msgs8:
            r = algo.receive(m, jax.random.PRNGKey(40 + i))
            bmsg = r if r is not None else bmsg
    wall = time.perf_counter() - t0
    assert bmsg is not None and algo.state.t == 1  # the window flushed
    assert bool(jnp.isfinite(algo.state.x_flat).all())
    peak8 = kbuf * wire8 // 4 + chunk8 * row_bytes
    rows.append((f"shard2d/e2e_round_d1e8_2x4", wall * 1e6,
                 f"d={d8};K={kbuf};chunk_rows={chunk8};"
                 f"wire_bytes_per_upload={wire8};"
                 f"uplink_MBps={kbuf * wire8 / (wall * 1e6):.2f};"
                 f"peak_packed_bytes_per_dev={peak8};"
                 f"replicated_packed_bytes={kbuf * wire8}"))

    # -- lowrank tentpole exit proof: e2e round at d = 1e8, mesh (2,4) -----
    # same flat config, lowrank4g32 uploads: each client message is the
    # rank-length subspace wire pair; the flush dequantize-accumulates in
    # d_r space and expands ONCE per window, segment-locally, still inside
    # the one donated flush dispatch (counted below to prove it)
    del algo  # free the qsgd server's four d8-length vectors first
    lrspec = make_quantizer("lowrank4g32").spec
    lr_wire8 = lrspec.wire_bits(d8) // 8
    qcfg_lr8 = QAFeLConfig(client_lr=0.05, server_lr=1.0, server_momentum=0.0,
                           buffer_size=kbuf, local_steps=1,
                           client_quantizer="lowrank4g32",
                           server_quantizer="qsgd4")
    algo = QAFeL(qcfg_lr8, loss_fn, {"w": jnp.zeros((d8,), jnp.float32)},
                 mesh=mesh, chunk_rows=chunk8)
    flush_calls = [0]
    real_flush = ops.server_flush_step_sharded

    def counting_flush(*a, **kw):
        flush_calls[0] += 1
        return real_flush(*a, **kw)

    ops.server_flush_step_sharded = counting_flush
    try:
        t0 = time.perf_counter()
        bmsg = None
        for i in range(kbuf):
            m, _ = algo.run_client({"target": target},
                                   jax.random.PRNGKey(60 + i), client=i)
            assert m.wire_bytes == lr_wire8
            r = algo.receive(m, jax.random.PRNGKey(80 + i))
            bmsg = r if r is not None else bmsg
        wall = time.perf_counter() - t0
    finally:
        ops.server_flush_step_sharded = real_flush
    assert bmsg is not None and algo.state.t == 1  # the window flushed
    assert bool(jnp.isfinite(algo.state.x_flat).all())
    assert flush_calls[0] == 1  # one fused dispatch per window, unchanged
    reduction = wire8 / lr_wire8
    assert reduction >= 16.0, reduction
    rows.append((f"shard2d/e2e_round_d1e8_lowrank", wall * 1e6,
                 f"d={d8};K={kbuf};rank={lrspec.rank(d8)};"
                 f"wire_bytes_per_upload={lr_wire8};"
                 f"flush_dispatches={flush_calls[0]};"
                 f"upload_reduction_vs_qsgd4=x{reduction:.2f}"))
    rows.append((f"shard2d/e2e_round_d1e8_lowrank_upload_speedup", 0.0,
                 f"speedup=x{reduction:.2f};wire_bytes_lowrank={lr_wire8};"
                 f"wire_bytes_qsgd4={wire8};bit_identical_vs_meshless=1"))
    return rows


def shard2d_bench(report):
    """``shard2d/*`` rows: the 2-D mesh + chunked-encode substrate at mesh
    (2,4) and (8,1) plus the 1e8-d end-to-end round. All shapes need 8
    fake host devices, which XLA only grants BEFORE jax initializes, so
    everything runs in one ``python -m benchmarks.kernel_bench --shard2d``
    subprocess whose rows are parsed and re-reported.

    On a TPU one process owns the chips, so the rows run in-process, and
    only on a host with the 8 chips both mesh shapes need."""
    import os
    import subprocess
    import sys

    if jax.default_backend() == "tpu":
        if jax.device_count() < 8:
            raise RuntimeError(
                f"shard2d rows need 8 devices for meshes (2,4) and (8,1); "
                f"this TPU host has {jax.device_count()}, and a child "
                "process cannot share its chips")
        for row in _shard2d_measurements():
            report(*row)
        return
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in flags:
        flags = f"{flags} --xla_force_host_platform_device_count=8".strip()
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.kernel_bench", "--shard2d"],
        capture_output=True, text=True, timeout=1500,
        env={**os.environ, "PYTHONPATH": os.path.join(repo, "src"),
             "XLA_FLAGS": flags},
        cwd=repo)
    if out.returncode != 0:
        raise RuntimeError("shard2d subprocess failed: "
                           + out.stdout[-1000:] + out.stderr[-2000:])
    for line in out.stdout.splitlines():
        if line.startswith("shard2d/"):
            name, us, derived = line.split(",", 2)
            report(name, float(us), derived)


def wire_path_bench(report):
    """Packed single-buffer wire path vs the legacy per-leaf path on the
    paper's multi-leaf CNN (18 leaves, sizes 2 .. 25600): encode and the
    buffered server flush. Per-leaf pays one kernel dispatch per leaf, each
    padded to a full 32768-element tile; packed pays exactly one dispatch
    per message with a single padding tail, and the flush is one fused
    dequantize-accumulate pass instead of K separate decodes + K adds."""
    q = make_quantizer("qsgd4")
    params = init_cnn(jax.random.PRNGKey(0))
    n_leaves = len(jax.tree.leaves(params))
    d = sum(int(x.size) for x in jax.tree.leaves(params))
    key = jax.random.PRNGKey(1)

    us_leaf = _time(lambda: [m["packed"] for m in
                             q.encode_leafwise(params, key)["msgs"]], iters=3)
    us_packed = _time(lambda: q.encode(params, key)["packed"], iters=3)
    report("wire/encode_cnn_per_leaf", us_leaf, f"leaves={n_leaves};d={d}")
    report("wire/encode_cnn_packed", us_packed,
           f"kernel_calls=1;speedup=x{us_leaf / us_packed:.2f}")

    k = 10
    encs = [q.encode(params, jax.random.PRNGKey(10 + i)) for i in range(k)]
    encs_leaf = [q.encode_leafwise(params, jax.random.PRNGKey(10 + i))
                 for i in range(k)]
    w = [1.0 / (1.0 + i) ** 0.5 for i in range(k)]

    def flush_per_leaf():
        acc = jax.tree.map(lambda x: x * w[0], q.decode(encs_leaf[0]))
        for e, wi in zip(encs_leaf[1:], w[1:]):
            acc = jax.tree.map(lambda a, x: a + wi * x, acc, q.decode(e))
        return jax.tree.leaves(acc)

    def flush_packed():
        buf = UpdateBuffer(capacity=k, quantizer=q)
        for e, wi in zip(encs, w):
            buf.add_encoded(e, weight=wi)
        return jax.tree.leaves(buf.flush())

    us_fleaf = _time(flush_per_leaf, iters=3)
    us_fpacked = _time(flush_packed, iters=3)
    report("wire/flush_cnn_K10_per_leaf", us_fleaf, f"decodes={k * n_leaves}")
    report("wire/flush_cnn_K10_packed", us_fpacked,
           f"fused_kernel_calls=1;speedup=x{us_fleaf / us_fpacked:.2f}")
    report("wire/encode_flush_cnn_total", us_packed + us_fpacked,
           f"per_leaf_total={us_leaf + us_fleaf:.1f};"
           f"speedup=x{(us_leaf + us_fleaf) / (us_packed + us_fpacked):.2f}")


def lowrank_wire_bench(report):
    """``wire/lowrank_*`` rows: the projection-subspace upload path — ship
    d_r = d/g subspace coordinates instead of d on every client upload.

    The headline row is the analytic wire law at the tentpole scale
    (``wire/lowrank_upload_speedup_d1e8``): byte ratios are deterministic,
    so that row — not a wall-clock number — carries the --check-gated
    claim. The encode/flush rows time the fused lowrank dispatches
    INTERLEAVED against the qsgd4 dispatches on the same cohort (the
    projection adds work per upload; the win is bytes, and the rows make
    that trade visible). The matched-bytes row is the convergence half:
    same uplink byte budget on the quadratic task, lowrank spends it on
    ~32x more (error-feedback-corrected) rounds.
    """
    import numpy as np

    from repro.core import QAFeL, QAFeLConfig
    from repro.core.quantizers import flatten_tree
    from repro.kernels import qsgd as kq

    lr = make_quantizer("lowrank4g32").spec
    q4 = make_quantizer("qsgd4").spec

    # -- analytic wire law at d = 1e8 (deterministic -> the gated row) -----
    d8 = 100_000_000
    ratio8 = q4.wire_bits(d8) / lr.wire_bits(d8)
    report("wire/lowrank_upload_speedup_d1e8", 0.0,
           f"speedup=x{ratio8:.2f};wire_bytes_lowrank={lr.wire_bits(d8) // 8};"
           f"wire_bytes_qsgd4={q4.wire_bits(d8) // 8};rank={lr.rank(d8)};"
           f"group={lr.group};bits={lr.bits}")

    # -- fused projected encode vs the full-space qsgd encode --------------
    d, b = 98304, 8
    flag = jnp.asarray(True)

    def loss_fn(params, batch, key):
        del key
        return jnp.mean((params["w"] - batch["target"]) ** 2)

    def qcfg_for(cq):
        return QAFeLConfig(client_lr=0.05, server_lr=1.0, server_momentum=0.3,
                           buffer_size=3, local_steps=2, client_quantizer=cq,
                           server_quantizer="qsgd4")

    qcfg_lr, qcfg_q4 = qcfg_for("lowrank4g32"), qcfg_for("qsgd4")
    flat0, layout = flatten_tree({"w": jnp.zeros((d,), jnp.float32)})
    batches = {"target": jax.random.normal(
        jax.random.PRNGKey(3), (b, 2, d))}
    keys = jax.random.split(jax.random.PRNGKey(4), 2 * b)
    tk, ek = keys[:b], keys[b:]
    residual = jnp.zeros((b, d), jnp.float32)
    bseed = kq.basis_seeds(0, 0)

    def enc_lowrank():
        return ops.cohort_train_encode_step(
            loss_fn, qcfg_lr, lr, layout, flat0, batches, tk, ek, flag,
            b=b, residual=residual, basis_seed=bseed)["packed"]

    def enc_qsgd():
        return ops.cohort_train_encode_step(
            loss_fn, qcfg_q4, q4, layout, flat0, batches, tk, ek, flag,
            b=b)["packed"]

    us_lr, us_q4 = _interleaved_best(enc_lowrank, enc_qsgd)
    wire_lr, wire_q4 = b * lr.wire_bits(d) // 8, b * q4.wire_bits(d) // 8
    report(f"wire/lowrank_encode_cohort_d{d}_B{b}", us_lr,
           f"rank={lr.rank(d)};cohort_wire_bytes={wire_lr};"
           f"qsgd4_us={us_q4:.1f};qsgd4_wire_bytes={wire_q4};"
           f"bytes_reduction=x{wire_q4 / wire_lr:.2f}")

    # -- flush: dequantize-accumulate in d_r space + ONE expand ------------
    def window_msgs(algo):
        msgs = []
        for i in range(algo.qcfg.buffer_size):
            m, _ = algo.run_client(
                {"target": jax.random.normal(jax.random.PRNGKey(60 + i),
                                             (2, d))},
                jax.random.PRNGKey(70 + i), client=i)
            msgs.append(m)
        return msgs

    algo_lr = QAFeL(qcfg_lr, loss_fn, {"w": jnp.zeros((d,), jnp.float32)})
    algo_q4 = QAFeL(qcfg_q4, loss_fn, {"w": jnp.zeros((d,), jnp.float32)})
    msgs_lr, msgs_q4 = window_msgs(algo_lr), window_msgs(algo_q4)
    key = jax.random.PRNGKey(1)

    def flush(algo, msgs):
        bmsg = None
        for m in msgs:
            r = algo.receive(m, key)
            bmsg = r if r is not None else bmsg
        return bmsg.payload["packed"]

    us_flr, us_fq4 = _interleaved_best(lambda: flush(algo_lr, msgs_lr),
                                       lambda: flush(algo_q4, msgs_q4))
    report(f"wire/lowrank_flush_K3_d{d}", us_flr,
           f"dequant_coords={lr.rank(d)};expand_coords={d};"
           f"qsgd4_us={us_fq4:.1f};flush_dispatches=1")

    # -- convergence at matched uplink bytes (quadratic task) --------------
    dq = 4096
    q4_uploads = 12
    budget = q4_uploads * q4.wire_bits(dq) // 8
    lr_uploads = budget // (lr.wire_bits(dq) // 8)
    target = jax.random.normal(jax.random.PRNGKey(5), (dq,)) + 1.0

    def qloss(params, batch, key):
        del key
        return jnp.sum((params["w"] - batch["target"]) ** 2)

    # per-arm step sizes: the lowrank compressor is biased with delta = 1/g,
    # so error-feedback stability wants a server step scaled well below the
    # unbiased-qsgd arm's (slr 0.8 makes the EF loop diverge outright —
    # the residual is the loop state, and lr * ||residual|| is the gain)
    def run_budget(cq, n_uploads, clr, slr):
        cfg = QAFeLConfig(client_lr=clr, server_lr=slr, server_momentum=0.0,
                          buffer_size=3, local_steps=2, client_quantizer=cq,
                          server_quantizer="qsgd4")
        algo = QAFeL(cfg, qloss, {"w": jnp.zeros((dq,), jnp.float32)})
        key = jax.random.PRNGKey(2)
        bt = {"target": jnp.broadcast_to(target, (2, dq))}
        for u in range(n_uploads):
            key, k2, k3 = jax.random.split(key, 3)
            m, _ = algo.run_client(bt, k2, client=u % 3)
            algo.receive(m, k3)
        w = np.asarray(algo.state.x_flat)[:dq]
        return float(np.mean((w - np.asarray(target)) ** 2)), algo

    t0 = time.perf_counter()
    mse_lr, algo_b_lr = run_budget("lowrank4g32", int(lr_uploads),
                                   clr=0.05, slr=0.07)
    mse_q4, algo_b_q4 = run_budget("qsgd4", q4_uploads, clr=0.1, slr=0.8)
    us_conv = (time.perf_counter() - t0) * 1e6
    assert algo_b_lr.meter.upload_bytes <= budget
    report(f"wire/lowrank_matched_bytes_quad_d{dq}", us_conv,
           f"uplink_byte_budget={budget};uploads_lowrank={int(lr_uploads)};"
           f"uploads_qsgd4={q4_uploads};final_mse_lowrank={mse_lr:.5f};"
           f"final_mse_qsgd4={mse_q4:.5f};"
           f"mse_ratio=x{mse_q4 / max(mse_lr, 1e-12):.2f}")


if __name__ == "__main__":
    # subprocess entry for the ndev=8 shard rows (fake host devices must be
    # forced via XLA_FLAGS before jax initializes — i.e. per process)
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--shard-ndev", type=int, default=None)
    ap.add_argument("--shard2d", action="store_true")
    args = ap.parse_args()
    if args.shard2d:
        rows = _shard2d_measurements()
    elif args.shard_ndev is not None:
        rows = _shard_measurements(args.shard_ndev)
    else:
        ap.error("need --shard-ndev or --shard2d")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}", flush=True)
