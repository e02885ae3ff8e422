"""Vectorized cohort engine: the async FL timeline, batched over cohorts.

The sequential ``AsyncFLSimulator`` trains exactly one client per Python
iteration, so host wall-clock grows linearly with concurrency and the
paper's concurrency 100/500/1000 sweeps are out of reach. This engine
admits arrivals in **cohorts** of ``cohort_size`` and runs each cohort
tier-group's ENTIRE client pipeline — unflatten the server's flat x-hat,
vmapped local SGD, delta flatten, batched quantize-pack — as ONE jitted
dispatch (``kernels.ops.cohort_train_encode_step``):

* no stacked delta pytree and no per-step ``hidden_tree`` view ever
  materialize: the flat x-hat goes in, packed wire codes + bucket norms
  come out,
* the packed messages feed ``QAFeL.receive`` / ``UpdateBuffer`` verbatim,
  so the server stays decode-free between flushes exactly as in the
  sequential path (which shares the same fused entry at b=1 through
  ``QAFeL.run_client``).

**Cohort admission model** (see DESIGN.md): whenever the arrival process
reaches the next pending completion, the next ``cohort_size`` arrivals are
admitted *together* and all train from the hidden state as of admission.
Members whose nominal arrival time falls after an intervening broadcast
train on a slightly older state than the sequential engine would give them
— extra staleness bounded by the cohort's arrival span, and exactly zero
for ``cohort_size=1``, where the engine consumes the jax and numpy RNG
streams in the sequential order and reproduces the sequential trajectory
bit for bit (pinned by tests/test_cohort_engine.py).

Timing, dropouts, stragglers and per-client quantizer tiers come from a
``ScenarioConfig`` (``repro.sim.scenarios``). Tier groups are **mask-padded
to the full static cohort shape** — a cohort whose members split 29/3
across two tiers issues two full-size dispatches and slices the real rows
out host-side — so every group hits the same lru-cached jit per
``(quantizer spec, cohort_size)`` and tier membership churn never retraces
(``kernels.ops.COHORT_STEP_TRACES`` pins it). Tiered clients that upload
through a non-default quantizer are decoded eagerly on receipt (the
default-tier majority stays packed).
"""
from __future__ import annotations

import heapq
import math
from typing import Any, Callable, Dict, List, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.protocol import CLIENT_UPDATE, Message, frame_cohort_messages
from repro.core.qafel import QAFeL
from repro.core.quantizers import make_quantizer
from repro.obs import spans
from repro.sim.events import BaseAsyncSimulator, SimConfig, SimResult
from repro.sim.scenarios import ScenarioConfig, ScenarioSampler, get_scenario


# Above this many batched delta elements (b * d), one monolithic vmap over
# cohort members loses to a lax.scan of member chunks: the (b, d) delta
# stack and its padded (b, rows, 128) encode view stop fitting in cache and
# the dispatch goes memory-bound. Scanning small member chunks keeps the
# working set cache-resident at identical bits (the dither is keyed per
# member + global element index, so chunking is invisible on the wire).
# The ~100k-element chunk target is the measured CPU optimum at d=98304
# (member_chunk=1: 1020us/upload vs 1701 monolithic; mc=2: 1332, and larger
# chunks regress monotonically toward the monolithic number).
_MEMBER_CHUNK_THRESHOLD = 4_000_000
_MEMBER_CHUNK_TARGET = 100_000


def auto_member_chunk(b: int, d: int) -> int | None:
    """The engine's member-chunk policy for one cohort dispatch.

    On a TPU every member trains in a chunk of its own. XLA's TPU
    convolution picks its tiling by the batch shape, so a member's f32
    rounding would depend on how many members share its vmap: on the cohort
    size, and on the mesh, whose devices each train only their slice.
    One-member chunks make a member's bits the same on one device and on
    any mesh, at little cost there (CelebA CNN cohort step on a TPU v5e:
    6.20 vs 5.98 ms at b=8, 7.18 vs 9.94 ms at b=32).

    Elsewhere: ``None`` (monolithic vmap) below the threshold, else the
    largest chunk keeping ``chunk * d`` near the cache-resident target."""
    if b <= 1:
        return None
    if jax.default_backend() == "tpu":
        return 1
    if b * d < _MEMBER_CHUNK_THRESHOLD:
        return None
    return max(1, min(b, _MEMBER_CHUNK_TARGET // max(d, 1)))


@jax.jit
def _stack_trees(*trees):
    """One jitted call stacks a whole cohort's batches (B eager
    expand_dims+concat ops per cohort otherwise — dispatch-bound). Module
    level so traces are shared across engine instances."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


class CohortAsyncFLSimulator(BaseAsyncSimulator):
    """Drives a QAFeL instance through the async timeline, cohort-batched."""

    def __init__(self, algo: QAFeL, sim_cfg: SimConfig,
                 client_batches_fn: Callable[[int, Any], Any],
                 eval_fn: Callable[[Any], float],
                 scenario: Union[str, ScenarioConfig] = "identity",
                 cohort_size: int = 32):
        super().__init__(algo, sim_cfg, client_batches_fn, eval_fn)
        self.scenario = get_scenario(scenario)
        self.cohort_size = max(1, int(cohort_size))
        self.sampler = ScenarioSampler(self.scenario, sim_cfg.concurrency,
                                       self.rng)
        self.tier_quantizers = [make_quantizer(name)
                                for _, name in self.scenario.tiers]
        self.dropped = 0
        self._receive_keys: List[Any] = []

    def _next_receive_key(self):
        """Per-delivery key for ``QAFeL.receive`` (used on flushes only).

        cohort_size=1 draws sequentially for the bit-exact replay; larger
        cohorts refill a batch of subkeys in one split so the per-upload
        cost is one numpy pop, not one device op.
        """
        if self.cohort_size == 1:
            return self._next_key()
        if not self._receive_keys:
            with spans.span("qafel.engine.receive_key"):
                subs = jax.random.split(self.key, 65)
                self.key = subs[0]
                self._receive_keys = list(np.asarray(subs[1:]))
        return self._receive_keys.pop()

    # -- cohort admission -------------------------------------------------
    def _train_encode_cohort(self, batches: Any, train_keys, enc_keys,
                             tiers: np.ndarray, *, stacked: bool = False,
                             client0: int | None = None) -> List[Message]:
        """Train + encode one admitted cohort, one fused dispatch per
        tier-group.

        Groups are mask-padded to the full cohort size (padding slots repeat
        the group's first member; their rows are computed and discarded) so
        every group reuses the lru-cached jit for its ``(spec, B)`` — no
        retrace as tier membership varies cohort to cohort. Payload slicing
        is host-numpy views via ``protocol.frame_cohort_messages``
        (``count=`` keeps padding rows off the wire). Note: at b > 1 every
        tier group encodes with the batched counter-hash dither regardless
        of how few real members it has — the pre-fusion per-tier
        ``encode_batch`` happened to delegate SINGLETON groups to the
        threefry path, so seeded tiered trajectories differ from it there
        (same wire format, unbiasedness and error bound; the pinned
        contracts — cohort_size=1 identity replay and within-version
        determinism — are unaffected).
        """
        from repro.kernels import ops as kops  # local import: kernels optional

        b = int(tiers.size) if stacked else len(batches)
        st = self.algo.state
        version = st.t
        msgs: List[Any] = [None] * b
        for tier in sorted(set(tiers.tolist())):
            q = self.algo.cq if tier < 0 else self.tier_quantizers[tier]
            members = np.nonzero(tiers == tier)[0]
            if b == 1:
                grp_batches, gt, ge = batches[0], train_keys[0], enc_keys[0]
            else:
                pad_idx = np.concatenate(
                    [members, np.repeat(members[:1], b - members.size)])
                midx = jnp.asarray(pad_idx)
                if stacked and members.size == b:
                    # single-tier cohort from a batched provider: the
                    # stacked tree IS the group — no per-cohort host stack
                    # (the former 39MB-at-d98304 copy) and no gather
                    grp_batches = batches
                elif stacked:
                    grp_batches = jax.tree.map(lambda x: x[midx], batches)
                else:
                    grp_batches = _stack_trees(*[batches[i] for i in pad_idx])
                if members.size == b:  # identity permutation: skip the gather
                    gt, ge = train_keys, enc_keys
                else:
                    gt, ge = train_keys[midx], enc_keys[midx]
            extra_kw: Dict[str, Any] = {}
            cids = None
            if q.spec.kind == "lowrank":
                # per-member error-feedback residual rides the fused
                # dispatch; padding rows carry the first member's residual
                # and are discarded with the rest of the padding
                if b == 1:
                    cids = [client0]
                else:
                    cids = [None if client0 is None else client0 + int(i)
                            for i in pad_idx]
                extra_kw["residual"] = self.algo.client_residuals(cids)
                extra_kw["basis_seed"] = self.algo.round_basis_seed()
            with spans.span("qafel.engine.dispatch"):
                out = kops.cohort_train_encode_step(
                    self.algo.loss_fn, self.algo.qcfg, q.spec, st.layout,
                    st.hidden_flat, grp_batches, gt, ge, self.algo._flag,
                    b=b, mesh=self.algo.mesh, taps=self.algo._taps,
                    member_chunk=auto_member_chunk(b, st.layout.total_size),
                    chunk_rows=self.algo.chunk_rows, **extra_kw)
            if cids is not None:
                self.algo.store_residuals(cids[:members.size],
                                          out["residual"][:members.size])
            with spans.span("qafel.engine.keys"):
                ekeys = np.asarray(ge).reshape(b, -1) if b > 1 else [ge]
            # the wait for the step, the codes' copy to the host and the
            # framing: the device trace tells the wait from the copy
            with spans.span("qafel.engine.codes_to_host"):
                mlist = frame_cohort_messages(
                    CLIENT_UPDATE, q, out, st.layout, enc_keys=ekeys,
                    version=version, count=members.size, to_numpy=(b > 1),
                    basis_seed=extra_kw.get("basis_seed"))
            tap_rows = None
            if self.algo._taps:
                from repro.obs.taps import named_cohort_taps
                # row j of the fused output is pad_idx[j] == members[j],
                # matching the payload slicing above
                tap_rows = np.asarray(out["taps"])
            for j, i in enumerate(members.tolist()):
                msgs[i] = mlist[j]
                if tap_rows is not None:
                    msgs[i].meta["taps"] = named_cohort_taps(tap_rows[j])
        return msgs

    def _admit_cohort(self, next_arrival: float, next_client: int):
        """Train + encode one cohort starting at ``next_arrival``.

        Returns (messages, arrival_times, durations, drop_mask,
        new_next_arrival). RNG streams are consumed in the sequential
        engine's order (per client: batches key, client key; then the numpy
        tier/duration/dropout draws), so cohort_size=1 replays it exactly.
        """
        b = self.cohort_size
        inter = self.sampler.interarrivals(b)
        arrivals = next_arrival + np.concatenate(
            [[0.0], np.cumsum(inter[:-1])])
        new_next_arrival = float(arrivals[-1] + inter[-1])
        tiers = self.sampler.tier_indices(b)

        with spans.span("qafel.engine.keys"):
            if b == 1:
                # sequential key order (batches key, then client key) so
                # the identity-scenario replay is bit-exact
                batch_keys = [self._next_key()]
                k_train, k_enc = jax.random.split(self._next_key())
                train_keys, enc_keys = [k_train], [k_enc]
            else:
                # one split covers the whole cohort: 2B+1 subkeys in two
                # device ops instead of 2B sequential splits
                subs = jax.random.split(self.key, 2 * b + 1)
                self.key = subs[0]
                batch_keys = np.asarray(subs[1:b + 1])
                te = jax.vmap(jax.random.split)(subs[b + 1:])
                train_keys, enc_keys = te[:, 0], te[:, 1]
        # batched-provider protocol: a batches fn marked ``batched = True``
        # is called ONCE with the cohort's client ids + keys and returns an
        # already-stacked tree (leading dim b) — e.g. a view into a
        # preloaded per-client tensor — instead of b per-client trees the
        # engine must host-stack (a 39MB copy per cohort at d=98304, the
        # dominant non-compute cost of the encode-bound regime)
        stacked = b > 1 and getattr(self.client_batches_fn, "batched", False)
        with spans.span("qafel.engine.data"):
            if stacked:
                batches = self.client_batches_fn(
                    np.arange(next_client, next_client + b), batch_keys)
            else:
                batches = [self.client_batches_fn(next_client + i,
                                                  batch_keys[i])
                           for i in range(b)]
        msgs = self._train_encode_cohort(batches, train_keys, enc_keys, tiers,
                                         stacked=stacked, client0=next_client)
        durations = self.sampler.durations(b)
        drops = self.sampler.dropouts(b)
        return msgs, arrivals, durations, drops, new_next_arrival

    # -- main loop ---------------------------------------------------------
    def run(self) -> SimResult:
        cfg, algo = self.cfg, self.algo
        heap: List[tuple] = []  # (finish_time, seq, client_id)
        pending: Dict[int, Message] = {}
        # speculatively admitted members may have nominal arrival times in
        # the future; broadcast fan-out must only count clients actually
        # training at the flush instant (arrival <= now, not yet delivered)
        arrival_heap: List[float] = []
        started = 0
        delivered = 0
        accuracy_trace: List[tuple] = []
        uploads = 0
        next_client = 0
        next_arrival = 0.0
        now = 0.0
        self._last_eval_step = -1
        reached = False
        seq = 0

        while uploads < cfg.max_uploads and not reached:
            # admit cohorts until the arrival process passes the next
            # completion (a dropped-out cohort may leave the heap empty, in
            # which case admission continues until an upload survives)
            next_finish = heap[0][0] if heap else math.inf
            while next_arrival <= next_finish:
                with spans.span("qafel.engine.admit", client0=next_client):
                    msgs, arrivals, durations, drops, next_arrival = \
                        self._admit_cohort(next_arrival, next_client)
                for i in range(self.cohort_size):
                    if drops[i]:
                        self.dropped += 1
                        if self.tracer is not None:
                            # emitted at the tracer's CURRENT clock (not the
                            # member's future arrival time) so the event
                            # stream stays t_sim-monotone
                            self.tracer.emit("drop", step=algo.state.t,
                                             client=next_client + i, tau=0,
                                             reason="dropout")
                        continue
                    msgs[i].meta["client"] = next_client + i
                    heapq.heappush(heap, (float(arrivals[i] + durations[i]),
                                          seq, next_client + i))
                    heapq.heappush(arrival_heap, float(arrivals[i]))
                    pending[seq] = msgs[i]
                    seq += 1
                next_client += self.cohort_size
                next_finish = heap[0][0] if heap else math.inf

            now, s, cid = heapq.heappop(heap)
            with spans.span("qafel.engine.deliver", client=cid):
                msg = pending.pop(s)
                while arrival_heap and arrival_heap[0] <= now:
                    heapq.heappop(arrival_heap)
                    started += 1
                delivered += 1
                if self.tracer is not None:
                    self.tracer.set_sim_time(now)
                bmsg = algo.receive(msg, self._next_receive_key(),
                                    n_receivers=max(1, started - delivered))
                uploads += 1
                if bmsg is not None:
                    with spans.span("qafel.engine.replicas"):
                        reached = self._apply_broadcast(bmsg, now, uploads,
                                                        accuracy_trace)

        return self._finalize(reached=reached, uploads=uploads, now=now,
                              accuracy_trace=accuracy_trace,
                              dropped_uploads=self.dropped)
