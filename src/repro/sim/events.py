"""Event-driven asynchronous FL simulator (paper Appendix D methodology).

Timing model, matching the paper / FedBuff's FLSim setup:

* clients arrive at a constant rate r (client n starts at time n / r),
* each client's training duration is sampled from a half-normal |N(0, 1)|
  (the best fit to Meta's production FL delay distribution, per FedBuff
  Appendix C); a concurrency level of C is achieved by setting
  r = C / E[|N(0,1)|] = C / (sqrt(2/pi)) — the paper's rates 125/627/1253
  for concurrency 100/500/1000,
* the server consumes uploads in completion-time order; every K-th upload
  triggers a server step + hidden-state broadcast (QAFeL) or a model
  broadcast (FedBuff),
* a client STARTING at time T trains from the hidden state as of T; its
  staleness is the number of server steps between its start and its
  delivery (Assumption 3.4).

The simulator maintains *independent per-client hidden-state replicas*
(Algorithm 3) for a configurable subset of clients and asserts they stay
bit-identical with the server's — the paper's central invariant. Replicas
are held in the server's flat f32 coordinate space: each broadcast is
decoded ONCE to its flat vector and applied with one add per replica, and
the bit-identity check is a single flat comparison against
``state.hidden_flat`` (no per-leaf traversal).

Data: each simulated client holds a non-IID shard (repro.data.federated).
Evaluation runs on the full-precision server model x (never on x-hat).
"""
from __future__ import annotations

import dataclasses
import heapq
import math
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.protocol import decode_message_flat
from repro.core.qafel import QAFeL, QAFeLConfig
from repro.obs.records import AccuracyPoint
from repro.sim.scenarios import HALF_NORMAL_MEAN


@dataclasses.dataclass(frozen=True)
class SimConfig:
    concurrency: int = 100  # average # clients training in parallel
    eval_every_steps: int = 10  # server steps between evals
    max_uploads: int = 10_000
    target_accuracy: Optional[float] = None  # stop early when reached
    track_hidden_replicas: int = 2  # clients whose x-hat replica we verify
    seed: int = 0

    @property
    def arrival_rate(self) -> float:
        return self.concurrency / HALF_NORMAL_MEAN


def _hidden_wire(state):
    """The hidden state in TRUE wire coordinates: a sharded server pads its
    flat vectors to segment alignment, but what clients hold/receive is the
    unpadded [:n] view. Tolerates layout-less states (test doubles)."""
    h = state.hidden_flat
    layout = getattr(state, "layout", None)
    return h[:layout.total_size] if layout is not None else h


@dataclasses.dataclass
class SimResult:
    reached_target: bool
    uploads: int
    server_steps: int
    sim_time: float
    metrics: Dict[str, Any]
    accuracy_trace: List[AccuracyPoint]  # tuple-compatible named records
    final_accuracy: float


class BaseAsyncSimulator:
    """State and bookkeeping shared by the sequential and cohort engines:
    seeded RNG streams, tracked hidden-state replicas, the decode-once
    broadcast application + eval cadence, and final result assembly."""

    def __init__(self, algo: QAFeL, sim_cfg: SimConfig,
                 client_batches_fn: Callable[[int, Any], Any],
                 eval_fn: Callable[[Any], float]):
        """client_batches_fn(client_id, key) -> stacked (P, ...) local batches;
        eval_fn(params) -> accuracy in [0, 1]."""
        self.algo = algo
        self.cfg = sim_cfg
        self.client_batches_fn = client_batches_fn
        self.eval_fn = eval_fn
        self.rng = np.random.default_rng(sim_cfg.seed)
        self.key = jax.random.PRNGKey(sim_cfg.seed)
        # the algorithm's RunTracer, if one is attached: the engine stamps
        # its sim clock before every delivery and adds eval/compile events
        self.tracer = getattr(algo, "telemetry", None)
        # flat replicas of the hidden state held by tracked "clients"
        # (copies: the server's own buffers are donated to the fused flush).
        # Replicas live in the TRUE wire coordinate space (_hidden_wire).
        self.replicas = [jnp.array(_hidden_wire(algo.state))
                         for _ in range(sim_cfg.track_hidden_replicas)]
        self._last_eval_step = -1

    def _next_key(self):
        self.key, sub = jax.random.split(self.key)
        return sub

    def _eval_extra(self) -> Dict[str, Any]:
        """Extra fields merged into every eval event this engine emits.
        The population engine overrides this with its per-state client
        counts; the base engines add nothing."""
        return {}

    def verify_replicas(self) -> bool:
        h = _hidden_wire(self.algo.state)
        if not self.replicas:
            return True
        eqs = jnp.stack([jnp.array_equal(rep, h) for rep in self.replicas])
        return bool(jnp.all(eqs))  # one host sync for all replicas

    def _apply_broadcast(self, bmsg, now: float, uploads: int,
                         accuracy_trace: List[tuple]) -> bool:
        """Decode the packed broadcast ONCE — to its flat vector, no tree
        view — and apply the identical decoded increment to every tracked
        replica (Algorithm 3), which is exactly what keeps them bit-identical
        to the server. Evaluates on the server-step cadence; returns True
        when the target accuracy is hit.
        """
        q = decode_message_flat(self.algo.sq, bmsg,
                                mesh=getattr(self.algo, "mesh", None))
        self.replicas = [rep + q for rep in self.replicas]
        step = self.algo.state.t
        if step - self._last_eval_step >= self.cfg.eval_every_steps:
            acc = float(self.eval_fn(self.algo.state.x))
            accuracy_trace.append(AccuracyPoint(now, uploads, step, acc))
            if self.tracer is not None:
                self.tracer.emit("eval", step=step, accuracy=acc,
                                 uploads=uploads, **self._eval_extra())
            self._last_eval_step = step
            # `is not None`, NOT truthiness: target_accuracy=0.0 is a real
            # target (e.g. "stop at break-even" on signed scores) that a
            # truthy check would silently never fire for
            if (self.cfg.target_accuracy is not None
                    and acc >= self.cfg.target_accuracy):
                return True
        return False

    def _finalize(self, *, reached: bool, uploads: int, now: float,
                  accuracy_trace: List[tuple], **extra_metrics) -> SimResult:
        """Always evaluate the final server model: a run ending between
        flushes (max_uploads < buffer_size, or any tail of uploads since
        the last eval'd flush) would otherwise report a stale accuracy —
        0.0 if no flush ever evaluated."""
        final_acc = float(self.eval_fn(self.algo.state.x))
        if not accuracy_trace or accuracy_trace[-1][1] != uploads:
            accuracy_trace.append(
                AccuracyPoint(now, uploads, self.algo.state.t, final_acc))
            if self.tracer is not None:
                self.tracer.set_sim_time(now)
                self.tracer.emit("eval", step=self.algo.state.t,
                                 accuracy=final_acc, uploads=uploads,
                                 **self._eval_extra())
        if self.tracer is not None:
            # one terminal poll records any (re)compiles of the fused
            # entries that happened during the run (warm-cache dependent,
            # so compile events never enter metrics()/stream comparisons)
            self.tracer.poll_compiles(step=self.algo.state.t)
        # drift=True: hidden_drift is one jitted reduction + sync, paid once
        # per run here rather than inside the hot loop
        metrics = self.algo.metrics(drift=True)
        metrics["replicas_in_sync"] = self.verify_replicas()
        metrics.update(extra_metrics)
        return SimResult(
            reached_target=reached,
            uploads=uploads,
            server_steps=self.algo.state.t,
            sim_time=now,
            metrics=metrics,
            accuracy_trace=accuracy_trace,
            final_accuracy=final_acc,
        )


class AsyncFLSimulator(BaseAsyncSimulator):
    """Drives a QAFeL (or FedBuff) instance through an async event timeline,
    one client per iteration (the reference implementation; the vectorized
    cohort engine lives in repro.sim.cohort).

    The client pipeline itself is shared with the cohort engine:
    ``algo.run_client`` is one fused train+encode dispatch
    (``kernels.ops.cohort_train_encode_step`` at b=1), so this engine and
    the cohort engine differ only in admission batching, never in the
    compiled client math."""

    def run(self) -> SimResult:
        cfg, algo = self.cfg, self.algo
        rate = cfg.arrival_rate
        heap: List[tuple] = []  # (finish_time, seq, client_id)
        accuracy_trace: List[tuple] = []
        uploads = 0
        next_client = 0
        next_arrival = 0.0
        now = 0.0
        self._last_eval_step = -1
        reached = False

        # Pending messages: client trains on the hidden state AS OF its start
        # time, so the client update is computed at start (run_client records
        # the version) and delivered at finish.
        pending: Dict[int, Any] = {}
        seq = 0

        while uploads < cfg.max_uploads and not reached:
            # admit arrivals up to the next completion
            next_finish = heap[0][0] if heap else math.inf
            while next_arrival <= next_finish:
                cid = next_client
                batches = self.client_batches_fn(cid, self._next_key())
                msg, _version = algo.run_client(batches, self._next_key(),
                                                client=cid)
                msg.meta["client"] = cid
                duration = abs(self.rng.normal(0.0, 1.0))
                heapq.heappush(heap, (next_arrival + duration, seq, cid))
                pending[seq] = msg
                seq += 1
                next_client += 1
                next_arrival += 1.0 / rate
                next_finish = heap[0][0] if heap else math.inf

            # deliver the earliest completion; a flush's broadcast fans out to
            # every client still training (in flight) at that instant
            now, s, cid = heapq.heappop(heap)
            msg = pending.pop(s)
            if self.tracer is not None:
                self.tracer.set_sim_time(now)
            bmsg = algo.receive(msg, self._next_key(),
                                n_receivers=max(1, len(heap)))
            uploads += 1

            if bmsg is not None:
                reached = self._apply_broadcast(bmsg, now, uploads,
                                                accuracy_trace)

        return self._finalize(reached=reached, uploads=uploads, now=now,
                              accuracy_trace=accuracy_trace)
