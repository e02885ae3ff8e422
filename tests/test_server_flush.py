"""Device-resident flat server state: the fused single-dispatch flush.

The contract of the refactor is **bit-exactness**: seeded trajectories of
the flat-state server (one jitted, buffer-donated ``server_flush_step`` per
flush) must match the pre-refactor tree path exactly. ``LegacyQAFeL`` below
is a faithful reimplementation of that pre-refactor path — per-flush eager
tree composition (``tree_axpy`` server update, ``unflatten`` per flush,
tree-applied broadcast) over the same kernel entry points — and the tests
pin trajectory equality against it for identity and qsgd quantizers, both
when driven directly and through the async simulator.

Also here: the single-dispatch assertion (compile/trace counter + no other
kernel entries on the flush path), the max_staleness drop policy, the
opt-in hidden_drift metric, and UpdateBuffer coverage for
normalize="weights" packed flushes and mixed packed+decoded fill windows.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.tree import tree_add, tree_axpy, tree_sub
from repro.core import (QAFeL, QAFeLConfig, TrafficMeter, UpdateBuffer,
                        make_quantizer)
from repro.core.protocol import (CLIENT_UPDATE, HIDDEN_BROADCAST,
                                 decode_message, encode_message)
from repro.core.qafel import _jitted_client_update
from repro.core.quantizers import flatten_tree
from repro.core.staleness import StalenessMonitor
from repro.kernels import ops as kops


def quad_loss(params, batch, key):
    del key
    return jnp.sum((params["w"] - batch["target"]) ** 2)


def make_batches(key, d=300, p=1):
    t = jax.random.normal(key, (d,)) + 3.0
    return {"target": jnp.broadcast_to(t, (p, d))}


# ---------------------------------------------------------------------------
# The pre-refactor reference: tree-state server, eager multi-dispatch flush
# ---------------------------------------------------------------------------


class LegacyState:
    def __init__(self, params0):
        self.x = jax.tree.map(lambda a: a.copy(), params0)
        self.hidden = jax.tree.map(lambda a: a.copy(), params0)
        self.momentum = jax.tree.map(jnp.zeros_like, params0)
        self.t = 0

    @property
    def hidden_flat(self):
        # the flat view the (new) simulator reads, derived from the tree
        return flatten_tree(self.hidden)[0]


class LegacyQAFeL:
    """The pre-refactor QAFeL host path, preserved verbatim: tree-valued
    ServerState, per-flush ``unflatten``, eager ``tree_axpy`` server update,
    broadcast decoded to a tree and tree-applied to the hidden state."""

    def __init__(self, qcfg, loss_fn, params0):
        self.qcfg = qcfg
        self.loss_fn = loss_fn
        self.cq = qcfg.cq()
        self.sq = qcfg.sq()
        self.state = LegacyState(params0)
        self.meter = TrafficMeter()
        self.staleness = StalenessMonitor(max_allowed=qcfg.max_staleness)
        self._client_update = _jitted_client_update(loss_fn, qcfg)
        self._packed, self._pweights = [], []  # qsgd wire tensors + weights
        self._count = 0
        self._acc = None  # tree-mode accumulator (tiered uploads)
        self._flat_acc = None  # identity-payload accumulator
        self._layout = None

    def run_client(self, batches, key, client=None):
        # `client` identifies the caller for per-client server state (lowrank
        # error-feedback residuals); the legacy path has none, so it ignores it
        k_train, k_enc = jax.random.split(key)
        delta = self._client_update(self.state.hidden, batches, k_train)
        msg = encode_message(CLIENT_UPDATE, self.cq, delta, k_enc,
                             version=self.state.t)
        return msg, self.state.t

    def receive(self, msg, key, n_receivers=1):
        version = msg.meta["version"]
        self.meter.record(msg)
        tau = self.state.t - version
        self.staleness.observe(tau)
        w = (1.0 / math.sqrt(1.0 + tau)) if self.qcfg.staleness_scaling else 1.0
        payload = msg.payload
        if (payload["kind"] == self.cq.spec.kind
                and payload.get("bits") in (None, self.cq.spec.bits)):
            self._layout = payload["layout"]
            if payload["kind"] == "identity":
                contrib = payload["payload"] * w
                self._flat_acc = (contrib if self._flat_acc is None
                                  else self._flat_acc + contrib)
            else:
                self._packed.append((payload["packed"], payload["norms"]))
                self._pweights.append(w)
        else:  # bit-width-tier upload: eager decode into the tree accumulator
            dec = self.cq.decode(payload)
            self._acc = (jax.tree.map(lambda x: x * w, dec) if self._acc is None
                         else tree_axpy(w, dec, self._acc))
        self._count += 1
        if self._count < self.qcfg.buffer_size:
            return None
        return self._flush(key, n_receivers)

    def _flush(self, key, n_receivers):
        qcfg, st = self.qcfg, self.state
        denom = float(qcfg.buffer_size)
        n = self._layout.total_size if self._layout is not None else None
        out = None
        if self._packed:
            stack = jnp.stack([p for p, _ in self._packed])
            norms = jnp.stack([nm for _, nm in self._packed])
            wvec = jnp.asarray(self._pweights, jnp.float32) / denom
            flat = kops.buffer_aggregate(stack, norms, wvec,
                                         self.cq.spec.bits, n)
            out = self._layout.unflatten(flat)
        if self._flat_acc is not None:  # identity payload accumulator
            flat = self._flat_acc / denom
            dec = self._layout.unflatten(flat)
            out = dec if out is None else tree_add(out, dec)
        if self._acc is not None:
            out = (jax.tree.map(lambda a: (1.0 / denom) * a, self._acc)
                   if out is None else tree_axpy(1.0 / denom, self._acc, out))
        self._packed, self._pweights, self._count = [], [], 0
        self._acc, self._flat_acc, self._layout = None, None, None

        # pre-refactor server_apply: eager tree_axpy chain
        if qcfg.server_momentum:
            momentum = tree_axpy(qcfg.server_momentum, st.momentum, out)
        else:
            momentum = out
        x_new = tree_axpy(qcfg.server_lr, momentum, st.x)
        diff = tree_sub(x_new, st.hidden)
        bmsg = encode_message(HIDDEN_BROADCAST, self.sq, diff, key,
                              fast=True, t=st.t)
        q = decode_message(self.sq, bmsg)
        self.meter.record(bmsg, n_receivers=n_receivers)
        st.x, st.momentum = x_new, momentum
        st.hidden = tree_add(st.hidden, q)
        st.t += 1
        return bmsg

    def metrics(self, drift=False):
        out = dict(self.meter.summary())
        out.update(self.staleness.summary())
        out["server_steps"] = self.state.t
        if drift:
            num = jnp.sqrt(sum(jnp.sum((a - b) ** 2) for a, b in zip(
                jax.tree.leaves(self.state.x), jax.tree.leaves(self.state.hidden))))
            den = jnp.sqrt(sum(jnp.sum(a ** 2)
                               for a in jax.tree.leaves(self.state.x)))
            out["hidden_drift"] = float(num / jnp.maximum(den, 1e-30))
        return out


def drive_pair(cq, sq, *, momentum=0.3, n_uploads=15, buffer_size=3, seed=0,
               d=300):
    """Drive the flat-state QAFeL and the legacy reference through the same
    seeded upload sequence; returns (algo, legacy, broadcast_pairs)."""
    qcfg = QAFeLConfig(client_lr=0.1, server_lr=1.2, server_momentum=momentum,
                       buffer_size=buffer_size, local_steps=2,
                       client_quantizer=cq, server_quantizer=sq)
    params0 = {"w": jnp.zeros((d,), jnp.float32),
               "b": jnp.ones((7,), jnp.float32)}
    algo = QAFeL(qcfg, quad_loss, params0)
    legacy = LegacyQAFeL(qcfg, quad_loss, params0)
    key = jax.random.PRNGKey(seed)
    bpairs = []
    for _ in range(n_uploads):
        key, k1, k2, k3 = jax.random.split(key, 4)
        batches = {"target": jnp.broadcast_to(
            jax.random.normal(k1, (d,)) + 3.0, (2, d))}
        m_new, _ = algo.run_client(batches, k2)
        m_old, _ = legacy.run_client(batches, k2)
        bm_new = algo.receive(m_new, k3)
        bm_old = legacy.receive(m_old, k3)
        assert (bm_new is None) == (bm_old is None)
        if bm_new is not None:
            bpairs.append((bm_new, bm_old))
    return algo, legacy, bpairs


# ---------------------------------------------------------------------------
# Seeded trajectory equivalence vs the pre-refactor path
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("cq,sq,momentum", [
    ("qsgd4", "qsgd4", 0.3),   # the paper's headline config
    ("qsgd8", "qsgd8", 0.0),   # no server momentum branch
    ("identity", "identity", 0.3),  # exact FedBuff limit
    ("identity", "qsgd4", 0.3),     # flat-accumulator client path
    ("qsgd4", "identity", 0.0),     # identity broadcast branch
])
def test_flat_server_matches_prerefactor_tree_path(cq, sq, momentum):
    """x, x-hat, momentum, and every broadcast's wire bits are IDENTICAL to
    the pre-refactor eager tree composition, flush after flush."""
    algo, legacy, bpairs = drive_pair(cq, sq, momentum=momentum)
    assert algo.state.t == legacy.state.t >= 4
    for name, a, b in [
        ("x", algo.state.x_flat, flatten_tree(legacy.state.x)[0]),
        ("hidden", algo.state.hidden_flat, flatten_tree(legacy.state.hidden)[0]),
        ("momentum", algo.state.momentum_flat,
         flatten_tree(legacy.state.momentum)[0]),
    ]:
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=name)
    for bm_new, bm_old in bpairs:
        assert bm_new.wire_bytes == bm_old.wire_bytes
        pn, po = bm_new.payload, bm_old.payload
        assert pn["kind"] == po["kind"]
        if pn["kind"] == "qsgd":
            np.testing.assert_array_equal(np.asarray(pn["packed"]),
                                          np.asarray(po["packed"]))
            np.testing.assert_array_equal(np.asarray(pn["norms"]),
                                          np.asarray(po["norms"]))
        else:
            np.testing.assert_array_equal(np.asarray(pn["payload"]),
                                          np.asarray(po["payload"]))
    # meters agree too (the trajectory includes the byte accounting)
    assert algo.meter.summary() == legacy.meter.summary()


def test_tree_views_match_legacy_trees():
    """The lazily-materialized tree views (eval / client-update boundary)
    reproduce the legacy path's trees leaf for leaf."""
    algo, legacy, _ = drive_pair("qsgd4", "qsgd4")
    for a, b in zip(jax.tree.leaves(algo.state.x),
                    jax.tree.leaves(legacy.state.x)):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(algo.state.hidden.value),
                    jax.tree.leaves(legacy.state.hidden)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("cq,sq", [("qsgd4", "qsgd4"),
                                   ("identity", "identity")])
def test_sequential_engine_matches_prerefactor_through_simulator(cq, sq):
    """The async simulator driven by the flat-state server produces the
    bit-identical trace and meters of the pre-refactor path (LegacyQAFeL is
    a drop-in for the simulator's algo interface)."""
    from repro.sim import AsyncFLSimulator, SimConfig

    def build(algo_cls):
        qcfg = QAFeLConfig(client_lr=0.05, server_lr=1.0, server_momentum=0.3,
                           buffer_size=3, local_steps=1,
                           client_quantizer=cq, server_quantizer=sq)
        algo = algo_cls(qcfg, quad_loss, {"w": jnp.zeros((256,), jnp.float32)})
        def client_batches(cid, key):
            return {"target": jax.random.normal(key, (1, 256)) + 1.0}
        def eval_fn(params):
            return float(-jnp.mean((params["w"] - 1.0) ** 2))
        sim = AsyncFLSimulator(
            algo, SimConfig(concurrency=4, max_uploads=12, eval_every_steps=2,
                            track_hidden_replicas=2, seed=5),
            client_batches, eval_fn)
        return sim.run()

    res_new = build(QAFeL)
    res_old = build(LegacyQAFeL)
    assert res_new.accuracy_trace == res_old.accuracy_trace
    assert res_new.final_accuracy == res_old.final_accuracy
    assert res_new.sim_time == res_old.sim_time
    assert res_new.metrics == res_old.metrics
    assert res_new.metrics["replicas_in_sync"]


def test_cohort_engine_matches_prerefactor_through_simulator():
    """Cohort engine (cohort_size=1, identity scenario) == pre-refactor
    trajectory: the second half of the acceptance anchor."""
    from repro.sim import AsyncFLSimulator, CohortAsyncFLSimulator, SimConfig

    def build(engine_cls, algo_cls, **kw):
        qcfg = QAFeLConfig(client_lr=0.05, server_lr=1.0, server_momentum=0.3,
                           buffer_size=3, local_steps=1,
                           client_quantizer="qsgd4", server_quantizer="qsgd4")
        algo = algo_cls(qcfg, quad_loss, {"w": jnp.zeros((256,), jnp.float32)})
        def client_batches(cid, key):
            return {"target": jax.random.normal(key, (1, 256)) + 1.0}
        def eval_fn(params):
            return float(-jnp.mean((params["w"] - 1.0) ** 2))
        sim = engine_cls(
            algo, SimConfig(concurrency=4, max_uploads=12, eval_every_steps=2,
                            track_hidden_replicas=1, seed=5),
            client_batches, eval_fn, **kw)
        return sim.run()

    res_cohort = build(CohortAsyncFLSimulator, QAFeL,
                       scenario="identity", cohort_size=1)
    res_old = build(AsyncFLSimulator, LegacyQAFeL)
    assert res_cohort.accuracy_trace == res_old.accuracy_trace
    assert res_cohort.final_accuracy == res_old.final_accuracy
    cohort_metrics = dict(res_cohort.metrics)
    assert cohort_metrics.pop("dropped_uploads") == 0  # cohort-engine-only key
    assert cohort_metrics == res_old.metrics


# ---------------------------------------------------------------------------
# Single-dispatch assertion (compile/trace counter)
# ---------------------------------------------------------------------------


def drive_flushes(algo, n_uploads, seed=0, d=300):
    key = jax.random.PRNGKey(seed)
    flushes = 0
    for _ in range(n_uploads):
        key, k1, k2, k3 = jax.random.split(key, 4)
        msg, _ = algo.run_client(make_batches(k1, d=d), k2)
        if algo.receive(msg, k3) is not None:
            flushes += 1
    return flushes


def test_flush_is_one_compiled_dispatch():
    """After the first flush compiles the fused step, further flushes (a)
    never re-trace it and (b) touch NO other kernel entry point — the whole
    server step is one python-level call into one compiled executable.
    Enforced via the shared ``trace_guard`` (the same machinery the flcheck
    compiled pass runs in CI)."""
    from repro.analysis_static import trace_guard

    qcfg = QAFeLConfig(client_lr=0.1, server_lr=1.0, server_momentum=0.3,
                       buffer_size=3, local_steps=1,
                       client_quantizer="qsgd4", server_quantizer="qsgd4")
    params0 = {"w": jnp.zeros((300,), jnp.float32),
               "b": jnp.ones((7,), jnp.float32)}
    algo = QAFeL(qcfg, quad_loss, params0)
    assert drive_flushes(algo, 3) == 1  # warm-up: compile the fused step

    key = jax.random.PRNGKey(99)
    flushes = 0
    with trace_guard("server_flush", retraces=0) as g:  # zero re-traces
        for _ in range(9):
            key, k1, k2, k3 = jax.random.split(key, 4)
            msg, _ = algo.run_client(make_batches(k1), k2)
            # any other kernel entry used during receive would be an extra
            # dispatch on the one-dispatch server path
            with g.exclusive():
                if algo.receive(msg, k3) is not None:
                    flushes += 1
    assert flushes == 3
    assert g.calls == 3  # one dispatch per flush...
    assert g.other_calls == 0  # ...and nothing else on the server path


def test_flush_state_buffers_are_donated():
    """The fused step donates x / x-hat / momentum: the pre-flush device
    buffers are invalidated, i.e. the update really is in-place."""
    qcfg = QAFeLConfig(client_lr=0.1, server_lr=1.0, buffer_size=2,
                       local_steps=1, client_quantizer="qsgd4",
                       server_quantizer="qsgd4")
    algo = QAFeL(qcfg, quad_loss, {"w": jnp.zeros((300,), jnp.float32)})
    old_x = algo.state.x_flat
    assert drive_flushes(algo, 2) == 1
    assert algo.state.x_flat is not old_x
    assert old_x.is_deleted()


# ---------------------------------------------------------------------------
# max_staleness drop policy
# ---------------------------------------------------------------------------


def make_algo(**kw):
    qcfg = QAFeLConfig(client_lr=0.1, server_lr=1.0, buffer_size=2,
                       local_steps=1, client_quantizer="qsgd4",
                       server_quantizer="qsgd4", **kw)
    return QAFeL(qcfg, quad_loss, {"w": jnp.zeros((300,), jnp.float32)})


def test_max_staleness_drops_stale_uploads():
    algo = make_algo(max_staleness=1)
    key = jax.random.PRNGKey(0)
    key, k1, k2 = jax.random.split(key, 3)
    stale_msg, _ = algo.run_client(make_batches(k1), k2)  # version 0
    # advance the server two steps with fresh uploads
    assert drive_flushes(algo, 4, seed=1) == 2
    assert algo.state.t == 2
    count_before = algo.buffer.count
    assert algo.receive(stale_msg, key) is None  # tau = 2 > max_staleness = 1
    assert algo.buffer.count == count_before  # never buffered
    assert algo.meter.uploads_dropped == 1
    assert algo.meter.dropped_bytes == stale_msg.wire_bytes
    assert algo.staleness.dropped == [2]
    m = algo.metrics()
    assert m["uploads_dropped"] == 1
    assert m["stale_dropped"] == 1
    assert m["tau_max_dropped"] == 2
    assert m["tau_max"] <= 1  # the dropped tau never polluted the history


def test_max_staleness_boundary_is_inclusive():
    """tau == max_staleness is still accepted (Assumption 3.4 is a bound)."""
    algo = make_algo(max_staleness=2)
    key = jax.random.PRNGKey(0)
    key, k1, k2 = jax.random.split(key, 3)
    stale_msg, _ = algo.run_client(make_batches(k1), k2)  # version 0
    drive_flushes(algo, 4, seed=1)
    assert algo.state.t == 2
    algo.receive(stale_msg, key)  # tau = 2 == max_staleness: accepted
    assert algo.meter.uploads_dropped == 0
    assert 2 in algo.staleness.history


def test_unbounded_staleness_never_drops():
    algo = make_algo(max_staleness=0)
    key = jax.random.PRNGKey(0)
    key, k1, k2 = jax.random.split(key, 3)
    stale_msg, _ = algo.run_client(make_batches(k1), k2)
    drive_flushes(algo, 8, seed=1)
    algo.receive(stale_msg, key)
    assert algo.meter.uploads_dropped == 0


# ---------------------------------------------------------------------------
# hidden_drift: opt-in, one jitted flat reduction
# ---------------------------------------------------------------------------


def test_hidden_drift_is_opt_in():
    algo = make_algo()
    drive_flushes(algo, 4)
    assert "hidden_drift" not in algo.metrics()  # hot-loop default: no sync
    m = algo.metrics(drift=True)
    x = np.asarray(algo.state.x_flat)
    h = np.asarray(algo.state.hidden_flat)
    want = np.linalg.norm(x - h) / np.linalg.norm(x)
    assert m["hidden_drift"] == pytest.approx(want, rel=1e-6)
    assert algo.hidden_drift() == m["hidden_drift"]


# ---------------------------------------------------------------------------
# UpdateBuffer: normalize="weights" in packed mode; mixed fill windows
# ---------------------------------------------------------------------------


def f32_tree(seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return {"w": jax.random.normal(ks[0], (129, 5), jnp.float32),
            "b": jax.random.normal(ks[1], (37,), jnp.float32)}


def test_packed_flush_weights_normalization_equals_eager_reference():
    """normalize="weights" in packed mode: fused flush == eager per-message
    decode + weighted sum divided by the weight total."""
    q = make_quantizer("qsgd4")
    k = 5
    encs = [q.encode(f32_tree(i), jax.random.PRNGKey(100 + i)) for i in range(k)]
    weights = [1.0 / math.sqrt(1 + i) for i in range(k)]
    buf = UpdateBuffer(capacity=k, quantizer=q)
    for e, w in zip(encs, weights):
        buf.add_encoded(e, weight=w)
    fused = buf.flush(normalize="weights")

    wsum = sum(weights)
    manual = None
    for e, w in zip(encs, weights):
        dec = jax.tree.map(lambda x: x * (w / wsum), q.decode(e))
        manual = dec if manual is None else jax.tree.map(jnp.add, manual, dec)
    for a, b in zip(jax.tree.leaves(fused), jax.tree.leaves(manual)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
    assert buf.count == 0 and buf.flushes == 1


@pytest.mark.parametrize("normalize", ["capacity", "weights"])
def test_mixed_packed_and_tier_window_equals_eager_reference(normalize):
    """A tiered (qsgd2) client landing mid-window among packed qsgd4 uploads:
    the flush folds the eagerly-decoded tier delta into the fused aggregate,
    equal to the all-eager reference."""
    q4, q2 = make_quantizer("qsgd4"), make_quantizer("qsgd2")
    trees = [f32_tree(i) for i in range(4)]
    encs4 = [q4.encode(trees[i], jax.random.PRNGKey(10 + i)) for i in (0, 1, 3)]
    enc2 = q2.encode(trees[2], jax.random.PRNGKey(12))
    weights = [1.0, 0.8, 0.6, 0.9]

    buf = UpdateBuffer(capacity=4, quantizer=q4)
    buf.add_encoded(encs4[0], weight=weights[0])
    buf.add_encoded(encs4[1], weight=weights[1])
    # tier client lands mid-window: decoded flat, straight to the accumulator
    buf.add_decoded_flat(q4.decode_flat(enc2), weight=weights[2],
                         layout=enc2["layout"])
    buf.add_encoded(encs4[2], weight=weights[3])
    assert buf.full
    fused = buf.flush(normalize=normalize)

    denom = 4.0 if normalize == "capacity" else sum(weights)
    all_encs = [encs4[0], encs4[1], enc2, encs4[2]]
    all_qs = [q4, q4, q2, q4]
    manual = None
    for e, qq, w in zip(all_encs, all_qs, weights):
        dec = jax.tree.map(lambda x: x * (w / denom), qq.decode(e))
        manual = dec if manual is None else jax.tree.map(jnp.add, manual, dec)
    for a, b in zip(jax.tree.leaves(fused), jax.tree.leaves(manual)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_tiered_upload_through_receive_matches_legacy():
    """End-to-end: a qsgd2 tier message mid-window through QAFeL.receive —
    the flat accumulator path — is bit-identical to the legacy tree path."""
    qcfg = QAFeLConfig(client_lr=0.1, server_lr=1.0, server_momentum=0.3,
                       buffer_size=3, local_steps=1,
                       client_quantizer="qsgd4", server_quantizer="qsgd4")
    params0 = {"w": jnp.zeros((300,), jnp.float32)}
    algo = QAFeL(qcfg, quad_loss, params0)
    legacy = LegacyQAFeL(qcfg, quad_loss, params0)
    q2 = make_quantizer("qsgd2")
    key = jax.random.PRNGKey(3)
    for i in range(6):
        key, k1, k2, k3 = jax.random.split(key, 4)
        if i % 3 == 1:  # tier client mid-window
            tree = {"w": jax.random.normal(k1, (300,))}
            msg = encode_message(CLIENT_UPDATE, q2, tree, k2, version=algo.state.t)
            msg_l = encode_message(CLIENT_UPDATE, q2, tree, k2,
                                   version=legacy.state.t)
            bm_new = algo.receive(msg, k3)
            bm_old = legacy.receive(msg_l, k3)
        else:
            batches = make_batches(k1)
            m_new, _ = algo.run_client(batches, k2)
            m_old, _ = legacy.run_client(batches, k2)
            bm_new = algo.receive(m_new, k3)
            bm_old = legacy.receive(m_old, k3)
        assert (bm_new is None) == (bm_old is None)
    np.testing.assert_array_equal(np.asarray(algo.state.x_flat),
                                  np.asarray(flatten_tree(legacy.state.x)[0]))
    np.testing.assert_array_equal(
        np.asarray(algo.state.hidden_flat),
        np.asarray(flatten_tree(legacy.state.hidden)[0]))


# ---------------------------------------------------------------------------
# UpdateBuffer.drain: host staging of numpy windows
# ---------------------------------------------------------------------------


def _host_upload(q, seed):
    """A packed upload whose wire arrays are host numpy, as the cohort
    engine frames them for cohorts of more than one member."""
    enc = dict(q.encode(f32_tree(seed), jax.random.PRNGKey(seed)))
    enc["packed"], enc["norms"] = (np.asarray(enc["packed"]),
                                   np.asarray(enc["norms"]))
    return enc


def _fill_window(buf, q, w, tier=False):
    """Fill one K-upload window of host uploads (the second upload a
    decoded qsgd2 tier upload when ``tier``); return what a fresh stack
    of its packed uploads gives: codes, norms, the device division's
    weights and the basis seeds."""
    q2 = make_quantizer("qsgd2")
    encs, weights = [], []
    for i in range(buf.capacity):
        seed = 10 * w + i
        wt = 1.0 / math.sqrt(1 + (seed % 7))
        if tier and i == 1:
            e2 = q2.encode(f32_tree(seed), jax.random.PRNGKey(seed))
            buf.add_decoded_flat(q2.decode_flat(e2), weight=wt,
                                 layout=e2["layout"])
            continue
        enc = _host_upload(q, seed)
        buf.add_encoded(enc, weight=wt)
        encs.append(enc)
        weights.append(wt)
    seeds = ([np.asarray(e["seed"], np.uint32).reshape(2) for e in encs]
             if q.spec.kind == "lowrank" else None)
    return (np.stack([e["packed"] for e in encs]),
            np.stack([e["norms"] for e in encs]),
            np.asarray(jnp.asarray(weights, jnp.float32) / float(buf.capacity)),
            None if seeds is None else np.stack(seeds))


class _Fence:
    """Outputs handed to ``UpdateBuffer.fence``: counts the waits on it."""

    def __init__(self):
        self.waits = 0

    def block_until_ready(self):
        self.waits += 1
        return self


def _bits(a):
    a = np.asarray(a)
    return a.view(np.uint32) if a.dtype == np.float32 else a


@pytest.mark.parametrize("window", ["qsgd", "tier", "lowrank"])
def test_staged_drain_equals_fresh_stack(window):
    """Over consecutive windows of host uploads, the staged drain gives the
    fresh ``np.stack``'s codes and norms and the device division's weights
    bit for bit (a tier upload leaves fewer packed uploads than K). A batch
    shares no memory with the next drain's, is unchanged by it, and the
    drain after next reuses its staging pair, once the outputs last handed
    to ``fence`` are ready."""
    from repro.obs import spans

    q = make_quantizer("lowrank4g32" if window == "lowrank" else "qsgd4")
    buf = UpdateBuffer(capacity=4, quantizer=q)
    allocs0, reuses0 = spans.flush_stage_allocs, spans.flush_stage_reuses
    batches, wants, fences = [], [], []
    for w in range(4):
        want = _fill_window(buf, q, w, tier=window == "tier")
        batch = buf.drain()
        # the first two drains allocate; each later one waits once on the
        # fence handed after the drain before it
        assert [f.waits for f in fences] == [int(j >= 1) for j in range(w)]
        fences.append(_Fence())
        buf.fence(fences[-1])
        assert isinstance(batch.weights, np.ndarray)
        assert batch.stack.shape[0] == (3 if window == "tier" else 4)
        got = (batch.stack, batch.norms, batch.weights, batch.seeds)
        for g, x in zip(got, want):
            if x is None:
                assert g is None
            else:
                assert g.dtype == x.dtype and g.shape == x.shape
                np.testing.assert_array_equal(_bits(g), _bits(x))
        assert (batch.extra is not None) == (window == "tier")
        if batches:
            prev, prev_want = batches[-1], wants[-1]
            assert not np.shares_memory(prev.stack, batch.stack)
            assert not np.shares_memory(prev.norms, batch.norms)
            np.testing.assert_array_equal(prev.stack, prev_want[0])
            np.testing.assert_array_equal(_bits(prev.norms),
                                          _bits(prev_want[1]))
        if len(batches) >= 2:
            assert np.shares_memory(batches[-2].stack, batch.stack)
        batches.append(batch)
        wants.append(want)
    assert spans.flush_stage_allocs - allocs0 == 2
    assert spans.flush_stage_reuses - reuses0 == 2


@pytest.mark.parametrize("name", ["qsgd4", "lowrank4g32"])
def test_host_window_drain_touches_no_device(name):
    """A window of host uploads drains with no host-to-device transfer and
    no device op: the flush dispatch is the first to touch the device."""
    q = make_quantizer(name)
    buf = UpdateBuffer(capacity=3, quantizer=q)
    for w in range(3):
        _fill_window(buf, q, w)
        with jax.transfer_guard("disallow"):
            batch = buf.drain()
        assert all(isinstance(a, np.ndarray)
                   for a in (batch.stack, batch.norms, batch.weights))


def test_cohort_run_stages_every_flush():
    """Cohort-engine flushes (host windows) allocate two staging pairs and
    reuse them for every later flush, and the run's state and broadcasts
    are bit-identical to the same run stacking every window afresh."""
    from repro.obs import spans
    from repro.sim import CohortAsyncFLSimulator, SimConfig

    def run(fresh):
        qcfg = QAFeLConfig(client_lr=0.05, server_lr=1.0, server_momentum=0.3,
                           buffer_size=3, local_steps=1,
                           client_quantizer="qsgd4", server_quantizer="qsgd4")
        algo = QAFeL(qcfg, quad_loss, {"w": jnp.zeros((300,), jnp.float32)})
        if fresh:
            def fresh_stack():
                return (np.stack([p for p, _ in algo.buffer._packed]),
                        np.stack([nm for _, nm in algo.buffer._packed]))
            algo.buffer._stage_window = fresh_stack
        bcasts = []
        receive0 = algo.receive

        def receive(msg, key, n_receivers=1):
            bmsg = receive0(msg, key, n_receivers)
            if bmsg is not None:
                bcasts.append((np.asarray(bmsg.payload["packed"]),
                               np.asarray(bmsg.payload["norms"])))
            return bmsg

        algo.receive = receive

        def client_batches(cid, key):
            return {"target": jax.random.normal(key, (1, 300)) + 1.0}

        sim = CohortAsyncFLSimulator(
            algo, SimConfig(concurrency=8, max_uploads=24,
                            eval_every_steps=1 << 30, seed=5),
            client_batches, lambda params: 0.0, scenario="identity",
            cohort_size=4)
        counts0 = (spans.flush_stage_allocs, spans.flush_stage_reuses)
        sim.run()
        counts = (spans.flush_stage_allocs - counts0[0],
                  spans.flush_stage_reuses - counts0[1])
        st = algo.state
        return ([np.asarray(v) for v in (st.x_flat, st.hidden_flat,
                                         st.momentum_flat)], bcasts, counts)

    state, bcasts, (allocs, reuses) = run(fresh=False)
    assert len(bcasts) >= 4
    assert (allocs, reuses) == (2, len(bcasts) - 2)
    state_f, bcasts_f, counts_f = run(fresh=True)
    assert counts_f == (0, 0)
    for a, b in zip(state, state_f):
        np.testing.assert_array_equal(_bits(a), _bits(b))
    assert len(bcasts) == len(bcasts_f)
    for (p, n), (pf, nf) in zip(bcasts, bcasts_f):
        np.testing.assert_array_equal(p, pf)
        np.testing.assert_array_equal(_bits(n), _bits(nf))
