"""Telemetry substrate (repro.obs): in-dispatch metric taps, the structured
run tracer, and the unified metrics pipeline.

The load-bearing claims pinned here:

* taps are FREE when off — a run without a tracer is bit-identical to one
  with taps enabled (same trajectory, same wire bits), because the tap
  vector rides the SAME fused dispatch and taps-off keeps the pre-telemetry
  jit signatures;
* the event stream is ENGINE-INVARIANT — the sequential engine and the
  cohort engine at cohort_size=1 emit identical typed events (modulo wall
  clock and warm-cache-dependent compile events) on the same seed;
* taps are SHARDING-INVARIANT — the segment-sharded flush produces the
  bitwise-identical tap vector to the single-device dispatch (gather to
  replicated + slice to the true n before the shared tap reduction); one
  subprocess test re-runs the comparison under 8 forced virtual devices;
* taps-on is still ONE dispatch per flush / per cohort tier-group
  (trace_guard over the fused-entry counters);
* every emitted stream passes the JSONL schema validator, and the old
  metrics keys survive the pipeline unification bit-for-bit;
* the program's ``qafel.*`` host spans land in the profiler's trace,
  nested where the work happens, its host-link counters count the wire
  bytes, and a run under the profiler is bit-identical to one without.
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import QAFeL, QAFeLConfig
from repro.core.staleness import StalenessMonitor
from repro.obs import (COHORT_TAP_NAMES, FLUSH_TAP_NAMES, AccuracyPoint,
                       CompileWatch, Event, RunTracer, summary_table,
                       validate_events, validate_jsonl, write_jsonl)
from repro.obs import spans
from repro.obs.schema import _selftest
from repro.sim import AsyncFLSimulator, CohortAsyncFLSimulator, SimConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PARAMS0 = {"w": jnp.zeros((300,), jnp.float32),
           "b": jnp.ones((7,), jnp.float32)}
D = 300


def quad_loss(params, batch, key):
    del key
    return jnp.sum((params["w"] - batch["target"]) ** 2)


def make_qcfg(**kw):
    base = dict(client_lr=0.1, server_lr=1.2, server_momentum=0.3,
                buffer_size=3, local_steps=2, client_quantizer="qsgd4",
                server_quantizer="qsgd4")
    base.update(kw)
    return QAFeLConfig(**base)


def client_batches(cid, key):
    del cid  # key-derived so both engines see identical data in RNG order
    return {"target": jnp.broadcast_to(
        jax.random.normal(key, (D,)) + 3.0, (2, D))}


def eval_fn(params):
    # host f64 reduction: a device-side jnp.mean over a SHARDED x would
    # group the f32 sum differently per device count, and the eval event's
    # accuracy would spuriously break stream bit-invariance
    return float(np.asarray(params["w"], dtype=np.float64).mean())


def run_sim(engine="sequential", taps=True, mesh=None, seed=0,
            max_uploads=12, chunk_rows=None, **qkw):
    tracer = RunTracer(taps=True) if taps else None
    algo = QAFeL(make_qcfg(**qkw), quad_loss, PARAMS0, mesh=mesh,
                 telemetry=tracer, chunk_rows=chunk_rows)
    scfg = SimConfig(concurrency=4, max_uploads=max_uploads,
                     eval_every_steps=1, seed=seed, track_hidden_replicas=1)
    if engine == "sequential":
        sim = AsyncFLSimulator(algo, scfg, client_batches, eval_fn)
    else:
        sim = CohortAsyncFLSimulator(algo, scfg, client_batches, eval_fn,
                                     scenario="identity", cohort_size=1)
    return sim.run(), tracer


@pytest.fixture(scope="module")
def traced_run():
    return run_sim(taps=True)


# -- records and registries -------------------------------------------------


def test_accuracy_point_is_a_tuple():
    """The named record type must stay drop-in for the positional tuples it
    replaced: equality, unpacking, and indexing all behave identically."""
    p = AccuracyPoint(1.5, 12, 4, 0.75)
    assert p == (1.5, 12, 4, 0.75)
    assert isinstance(p, tuple)
    t_sim, uploads, step, acc = p
    assert (p[0], p[1], p[2], p[3]) == (t_sim, uploads, step, acc)
    assert p.accuracy == 0.75
    assert p.as_dict() == {"t_sim": 1.5, "uploads": 12, "step": 4,
                           "accuracy": 0.75}


def test_staleness_histogram():
    mon = StalenessMonitor()
    for tau in (0, 0, 1, 2, 3, 4, 8, 100):
        mon.observe(tau)
    mon.record_dropped(7)
    h = mon.histogram(bins=4)
    assert h["edges"] == (0, 1, 2, 4)
    # buckets: [0,1) [1,2) [2,4) [4,inf)
    assert h["accepted"] == (2, 1, 2, 3)
    assert h["dropped"] == (0, 0, 0, 1)
    with pytest.raises(ValueError):
        mon.histogram(bins=1)
    # the histogram is part of the one metrics surface
    assert mon.summary()["tau_hist"] == mon.histogram()


def test_tracer_ring_eviction():
    t = RunTracer(capacity=4)
    for i in range(6):
        t.emit("flush", step=i, window=3)
    assert len(t.events()) == 4
    assert t.dropped_events == 2
    assert t.counters()["events_evicted"] == 2
    assert [e.step for e in t.events()] == [2, 3, 4, 5]


def test_event_comparable_drops_wall_clock():
    t = RunTracer()
    t.emit("eval", step=1, accuracy=0.5)
    (e,) = t.events()
    assert isinstance(e, Event)
    assert "t_wall" in e.as_dict()
    assert "t_wall" not in e.comparable()


def test_tracer_rejects_unknown_kind():
    with pytest.raises(ValueError):
        RunTracer().emit("not_a_kind")


# -- schema -----------------------------------------------------------------


def test_schema_selftest():
    _selftest()


def test_schema_rejects_malformed_streams():
    t = RunTracer()
    t.set_sim_time(1.0)
    t.emit("flush", step=1, window=3)
    rows = [e.as_dict() for e in t.events()]
    assert validate_events(rows) == []
    assert validate_events([]) != []  # empty trace is an error
    bad_seq = [dict(rows[0]), dict(rows[0])]  # duplicated seq
    assert validate_events(bad_seq) != []
    missing = dict(rows[0])
    del missing["window"]
    assert validate_events([missing]) != []
    unknown = dict(rows[0], kind="telemetry")
    assert validate_events([unknown]) != []


def test_run_trace_jsonl_roundtrip(traced_run, tmp_path):
    """A real run's stream serializes to schema-valid JSONL whose rows
    mirror the in-memory events exactly."""
    _, tracer = traced_run
    path = tmp_path / "trace.jsonl"
    write_jsonl(tracer, str(path))
    assert validate_jsonl(str(path)) == []
    rows = [json.loads(line) for line in path.read_text().splitlines()]
    assert rows == [e.as_dict() for e in tracer.events()]
    kinds = {r["kind"] for r in rows}
    assert {"upload", "flush", "broadcast", "eval"} <= kinds


# -- taps: zero-cost when off, correct when on ------------------------------


def test_taps_off_run_is_bit_identical(traced_run):
    """Attaching a taps-enabled tracer must not change a single bit of the
    trajectory: same accuracy trace, same traffic/staleness metrics, same
    final hidden state."""
    res_on, tracer = traced_run
    res_off, _ = run_sim(taps=False)
    assert res_off.accuracy_trace == res_on.accuracy_trace
    m_on = {k: v for k, v in res_on.metrics.items()
            if not (k.startswith("flush/") or k.startswith("upload/")
                    or k.startswith("events_") or k.startswith("traces_"))}
    assert m_on == res_off.metrics
    # the tap series themselves: one point per flush / per accepted upload
    n_flush = len(tracer.events("flush"))
    for name in FLUSH_TAP_NAMES:
        assert len(res_on.metrics[f"flush/{name}"]) == n_flush
    n_up = len(tracer.events("upload"))
    for name in COHORT_TAP_NAMES:
        assert len(res_on.metrics[f"upload/{name}"]) == n_up


def test_flush_tap_values_identity_server():
    """With an identity SERVER quantizer the broadcast quantization error is
    exactly 0.0 and the norm taps are positive and finite; qsgd clients keep
    the packed buffer window, so the staleness-weight taps are live."""
    res, tracer = run_sim(server_quantizer="identity")
    qerr = res.metrics["flush/bcast_qerr_rel"]
    assert qerr and all(v == 0.0 for v in qerr)
    for name in ("delta_norm", "update_norm", "bcast_diff_norm"):
        series = res.metrics[f"flush/{name}"]
        assert all(np.isfinite(v) and v > 0.0 for v in series)
    # buffer-size weights: sum of K staleness weights, each in (0, 1]
    k = make_qcfg().buffer_size
    for s, lo in zip(res.metrics["flush/weight_sum"],
                     res.metrics["flush/weight_min"]):
        assert 0.0 < lo <= 1.0 and lo <= s <= k


def test_upload_tap_qerr_zero_identity_client():
    """Identity CLIENT quantizer -> every upload's relative quantization
    error tap is exactly 0.0 (the uploads bypass the packed stack, so the
    flush weight taps report the documented zeros there)."""
    res, _ = run_sim(client_quantizer="identity")
    up_qerr = res.metrics["upload/upload_qerr_rel"]
    assert up_qerr and all(v == 0.0 for v in up_qerr)
    assert all(v == 0.0 for v in res.metrics["flush/weight_sum"])


def test_qsgd_tap_qerr_in_unit_range(traced_run):
    res, _ = traced_run
    for series in (res.metrics["flush/bcast_qerr_rel"],
                   res.metrics["upload/upload_qerr_rel"]):
        assert series and all(0.0 < v < 1.0 for v in series)


# -- engine and sharding invariance -----------------------------------------


def _comparable_stream(tracer):
    # compile events are warm-cache-dependent (a second same-process run
    # retraces nothing) so they never enter stream comparisons
    return [e.comparable() for e in tracer.events() if e.kind != "compile"]


def test_event_stream_engine_invariant(traced_run):
    """Sequential engine vs cohort engine at cohort_size=1: identical typed
    event stream and identical metrics on the same seed."""
    res_a, tr_a = traced_run
    res_b, tr_b = run_sim(engine="cohort")
    assert _comparable_stream(tr_a) == _comparable_stream(tr_b)
    m_b = dict(res_b.metrics)
    assert m_b.pop("dropped_uploads") == 0
    assert m_b == res_a.metrics
    assert res_b.accuracy_trace == res_a.accuracy_trace


def test_flush_taps_sharding_invariant(traced_run):
    """The segment-sharded flush's tap vector must be BITWISE equal to the
    single-device one (1 segment here; genuinely 8-way under the 8-device
    CI job, where the mesh spans all visible devices)."""
    from repro.launch.mesh import make_sim_mesh
    res_a, tr_a = traced_run
    res_b, tr_b = run_sim(mesh=make_sim_mesh())
    for name in FLUSH_TAP_NAMES:
        key = f"flush/{name}"
        assert res_b.metrics[key] == res_a.metrics[key], key
    assert _comparable_stream(tr_a) == _comparable_stream(tr_b)


def test_flush_taps_mesh2d_chunked_invariant(traced_run):
    """The 2-D ("data","model") mesh with the chunked flush encode must
    produce the same tap series bit for bit: the model-axis tap reduction
    gathers to replicated before reducing along the d-chunks, and the
    chunked encode's counter-hash dither is keyed by global element index,
    so neither sharding nor chunking may show up in the taps."""
    from repro.launch.mesh import make_sim_mesh2d
    res_a, tr_a = traced_run
    res_b, tr_b = run_sim(mesh=make_sim_mesh2d((1, 1)), chunk_rows=1)
    for name in FLUSH_TAP_NAMES:
        key = f"flush/{name}"
        assert res_b.metrics[key] == res_a.metrics[key], key
    assert _comparable_stream(tr_a) == _comparable_stream(tr_b)


def test_eight_virtual_devices_taps_invariant():
    """Force 8 host devices in a subprocess and assert the sharded flush
    tap series and event stream match the single-device run bit for bit."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import tests.test_obs as T
        from repro.launch.mesh import make_sim_mesh, make_sim_mesh2d
        res_a, tr_a = T.run_sim()
        res_b, tr_b = T.run_sim(mesh=make_sim_mesh(8))
        res_c, tr_c = T.run_sim(mesh=make_sim_mesh2d((2, 4)), chunk_rows=1)
        for name in T.FLUSH_TAP_NAMES:
            key = "flush/" + name
            assert res_b.metrics[key] == res_a.metrics[key], key
            assert res_c.metrics[key] == res_a.metrics[key], "2d:" + key
        assert T._comparable_stream(tr_b) == T._comparable_stream(tr_a)
        assert T._comparable_stream(tr_c) == T._comparable_stream(tr_a)
        assert T.validate_events(
            [e.as_dict() for e in tr_b.events()]) == []
        print("OBS_8DEV_OK")
    """)
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=560,
        env={**os.environ,
             "PYTHONPATH": os.path.join(REPO, "src") + os.pathsep + REPO},
        cwd=REPO)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    assert "OBS_8DEV_OK" in out.stdout


# -- single dispatch with taps on -------------------------------------------


def test_taps_on_is_still_one_dispatch():
    """Taps ride the existing fused dispatches: one server_flush call per
    flush, one cohort_step call per client, zero base-kernel calls inside
    either guarded window."""
    from repro.analysis_static.trace_guard import trace_guard
    tracer = RunTracer(taps=True)
    algo = QAFeL(make_qcfg(), quad_loss, PARAMS0, telemetry=tracer)
    key = jax.random.PRNGKey(0)
    flushes = 0
    with trace_guard("server_flush", retraces=None) as gs, \
            trace_guard("cohort_step", retraces=None) as gc:
        while flushes < 2:
            key, k1, k2, k3 = jax.random.split(key, 4)
            with gc.exclusive():
                msg, _ = algo.run_client(client_batches(0, k1), k2)
            with gs.exclusive():
                bmsg = algo.receive(msg, k3)
            if bmsg is not None:
                flushes += 1
    assert gs.calls == 2 and gs.other_calls == 0
    assert gc.calls >= 2 * make_qcfg().buffer_size and gc.other_calls == 0


# -- compile tracking and reporting -----------------------------------------


def test_compile_watch_and_events(traced_run):
    _, tracer = traced_run
    compiles = tracer.events("compile")
    assert compiles, "a cold run must record its fused-entry traces"
    entries = {e.data["entry"] for e in compiles}
    assert "server_flush" in entries
    assert all(e.data["retraces"] >= 1 for e in compiles)
    # counters carry the totals; metrics() deliberately excludes them
    assert tracer.counters()["traces_server_flush"] >= 1
    assert not any(k.startswith("traces_") for k in tracer.metrics())
    # a fresh watch sees the already-warm cache: zero deltas
    w = CompileWatch()
    assert all(v == 0 for v in w.poll().values())


def test_summary_table(traced_run):
    _, tracer = traced_run
    table = summary_table(tracer)
    assert "events_flush" in table and "flush/bcast_qerr_rel" in table


def test_metrics_surface_keeps_legacy_keys(traced_run):
    """The unified metrics() pipeline preserves the pre-PR key set (traffic
    meter, staleness monitor, server step counter) alongside the new
    series."""
    res, _ = traced_run
    for key in ("upload_MB", "broadcast_MB", "kB_per_upload", "tau_max",
                "tau_mean", "tau_hist", "server_steps", "hidden_drift",
                "replicas_in_sync"):
        assert key in res.metrics, key


# -- program spans and host-link counters -------------------------------------

# each qafel.* span and the span it opens inside (None: at top level)
SPAN_PARENTS = {
    "qafel.engine.admit": None,
    "qafel.engine.keys": "qafel.engine.admit",
    "qafel.engine.data": "qafel.engine.admit",
    "qafel.engine.dispatch": "qafel.engine.admit",
    "qafel.engine.codes_to_host": "qafel.engine.admit",
    "qafel.engine.deliver": None,
    "qafel.engine.receive_key": "qafel.engine.deliver",
    "qafel.buffer.add": "qafel.engine.deliver",
    "qafel.flush": "qafel.engine.deliver",
    "qafel.buffer.drain": "qafel.flush",
    "qafel.flush.dispatch": "qafel.flush",
    "qafel.flush.frame": "qafel.flush",
    "qafel.engine.replicas": "qafel.engine.deliver",
}


def _cohort_run(profile_dir=None):
    """A tiny cohort run (qsgd4 both ways, K=2, cohorts of 4), optionally
    under the profiler: its state, broadcasts, trained members and the
    host-link bytes it counted."""
    algo = QAFeL(make_qcfg(buffer_size=2), quad_loss, PARAMS0)
    trained, broadcasts = [], []
    receive0 = algo.receive

    def receive(msg, key, n_receivers=1):
        bmsg = receive0(msg, key, n_receivers)
        if bmsg is not None:
            broadcasts.append((np.asarray(bmsg.payload["packed"]),
                               np.asarray(bmsg.payload["norms"])))
        return bmsg

    def batches(cid, key):
        trained.append(cid)
        return client_batches(cid, key)

    algo.receive = receive
    scfg = SimConfig(concurrency=8, max_uploads=10, eval_every_steps=1 << 30,
                     seed=5, track_hidden_replicas=1)
    sim = CohortAsyncFLSimulator(algo, scfg, batches, eval_fn,
                                 scenario="identity", cohort_size=4)
    link0 = (spans.d2h_bytes, spans.h2d_bytes)
    if profile_dir is None:
        sim.run()
    else:
        opts = jax.profiler.ProfileOptions()
        opts.host_tracer_level = 1
        opts.python_tracer_level = 0
        with jax.profiler.trace(str(profile_dir), profiler_options=opts):
            sim.run()
    st = algo.state
    state = [np.asarray(v) for v in (st.x_flat, st.hidden_flat,
                                     st.momentum_flat)]
    return (state, broadcasts, len(trained),
            (spans.d2h_bytes - link0[0], spans.h2d_bytes - link0[1]))


def _program_spans(profile_dir):
    """The ``qafel.*`` host events of the one XSpace under
    ``profile_dir``: ``(name, start_ns, end_ns)``."""
    from jax.profiler import ProfileData
    path, = profile_dir.glob("plugins/profile/*/*.xplane.pb")
    pd = ProfileData.from_file(str(path))
    return [(e.name, int(e.start_ns), int(e.end_ns))
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith("qafel.")]


def test_program_spans_and_link_counters(tmp_path):
    from repro.kernels import ops as kops
    state, bcasts, trained, (d2h, h2d) = _cohort_run(tmp_path)
    program = _program_spans(tmp_path)
    by_name = {}
    for ev in program:
        by_name.setdefault(ev[0], []).append(ev)
    assert set(by_name) == set(SPAN_PARENTS), sorted(by_name)
    for name, parent in SPAN_PARENTS.items():
        for ev in by_name[name]:
            around = [p for p in program if p is not ev
                      and p[1] <= ev[1] and ev[2] <= p[2]]
            if parent is None:
                assert not around, (name, [p[0] for p in around])
            else:
                assert any(p[0] == parent for p in around), name
    # the wire bytes of one qsgd4 upload: 64 code bytes and one f32 norm
    # per 128-value row; every trained member's codes come to the host once,
    # every flushed upload's codes go back once
    wire = kops.rows_for(D + 7) * (128 * 4 // 8 + 4)
    assert trained % 4 == 0
    assert d2h == trained * wire
    assert h2d == len(bcasts) * 2 * wire
    assert len(by_name["qafel.flush"]) == len(bcasts) >= 2
    # the same seed with the profiler off: the same bits
    state_off, bcasts_off, trained_off, _ = _cohort_run()
    assert trained_off == trained
    for a, b in zip(state, state_off):
        assert np.array_equal(a.view(np.uint32), b.view(np.uint32))
    assert len(bcasts_off) == len(bcasts)
    for (p, n), (p_off, n_off) in zip(bcasts, bcasts_off):
        assert np.array_equal(p, p_off)
        assert np.array_equal(n.view(np.uint32), n_off.view(np.uint32))


def test_tracer_counters_report_link_bytes():
    counters = RunTracer().counters()
    assert counters["d2h_bytes"] == spans.d2h_bytes
    assert counters["h2d_bytes"] == spans.h2d_bytes
