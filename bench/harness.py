"""One run of one benchmark cell: set-up, the measured window, the
reference check, and the result line.

A cell names a configuration (``bench/configs/<config>.json``, whose
``model`` names ``bench/models/<model>.py``) and a traffic mix
(``bench/traffic/<traffic>.json``); its comparison limits are in
``bench/limits/<cell>.json`` and each per-layer metric is read by
``bench/metrics/<metric>.py``. Nothing here depends on which cell,
configuration or metric it is running.

The run drives the public entry: ``repro.sim.CohortAsyncFLSimulator(...)
.run()`` over a ``repro.core.QAFeL``. Set-up makes the weights and every
client's data on the device from the seed, compiles (from the persistent
cache after the first run), and records two blocks of ``CHECK_FLUSHES``
flushes for the reference (``bench.reference``): the run's first, and,
once the pipeline holds nine tenths of the cell's concurrency and a whole
concurrency of uploads has come back since, as many at steady state. The
window then opens right after the steady block's last flush and closes at
the first flush that returns after ``seconds``, so it holds whole buffer
cycles; the server state is then waited on and the window's clock stops;
the engine then applies that receive's broadcast to its replicas, and a
sentinel raised at the next ``receive`` ends ``run()``. A traced run
traces the window's first ``TRACE_SECONDS``, up to a flush, and keeps
what the window saw until then for the per-layer metrics.

Adding a cell takes new files only, besides its entries in
``BENCHMARK.json`` (``bench.selftest``'s ``shrink`` case checks them):

* ``bench/traffic/<traffic>.json``, unless the cell reuses a mix: the keys
  of ``TRAFFIC_KEYS``;
* ``bench/configs/<config>.json``, unless it reuses a configuration: the
  keys of ``CONFIG_KEYS`` (``model``, ``d``, ``client_lr``,
  ``server_lr``, ``server_momentum``, ``local_steps``), optionally
  ``reference_block_rows`` (the rows of one block of the reference's
  flush phase, and of its client phase for a separable model; default
  ``1 << 30``, one block), and whatever the model module reads;
* ``bench/models/<model>.py``, unless it reuses a model, defining
  ``MODEL_NAMES``:

  - ``SEPARABLE``: True where a coordinate's client delta depends on that
    coordinate of x-hat alone;
  - ``init_params(key, cfg)``: the weights as a tree, on the device;
  - ``init_block(key, e0, e1)`` (separable models only): the flat initial
    weights at indices [e0, e1), equal to those of ``init_params``;
  - ``make_data(key, cfg, traffic)``: every client's data, on the device;
  - ``provider(data, cfg, traffic)``: the engine's batched data provider,
    ``batches(cids, keys)`` with ``batches.batched = True``, whose batch
    carries ``cid`` (one row per member, repeated per local step);
  - ``client_batch(data, cfg, cid)``: one client's batches, for the
    reference;
  - ``program_loss(params, batch, key)``: the loss the program trains;
  - ``ref_client_delta(h, batch, k_train, unflatten, cfg, qcfg, dtype)``:
    the plain reference of the client's local training from x-hat ``h``
    (a block of whole rows for a separable model, the n values
    otherwise), returning its f32 delta;
  - ``client_work(cfg)``: ``flops`` and ``data_bytes`` one upload
    requires;
* ``bench/limits/<cell>.json``: the compared numbers' limits, from
  ``python3 -m bench.control`` on the chip;
* ``bench/shrink/<cell>.json``: ``{"config": {...}, "traffic": {...}}``,
  the overrides that shrink the cell for the CPU self-tests;
* ``bench/metrics/<metric>.py`` for each new per-layer metric: ``read(ctx)``
  returns the number, or None where the run has nothing to read.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import math
import os
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CHECK_FLUSHES = 3  # flushes in each block the reference replays
# arrivals and durations come from this seed, so every run of a cell does
# the same work (the same cohorts, deliveries and flushes); the data, the
# weights and the engine's training and dither keys come from --seed
TIMING_SEED = 1549775860
# a traced run traces the window's first flushes up to this many seconds:
# the device keeps a fixed number of trace events and drops the rest,
# which a 51-second window of the CNN cell overruns
TRACE_SECONDS = 10.0
# what a cell's files must hold (see the module docstring)
MODEL_NAMES = ("SEPARABLE", "init_params", "make_data", "provider",
               "client_batch", "program_loss", "ref_client_delta",
               "client_work")
CONFIG_KEYS = ("model", "d", "client_lr", "server_lr", "server_momentum",
               "local_steps")
TRAFFIC_KEYS = ("engine", "concurrency", "cohort_size", "buffer_size",
                "client_quantizer", "server_quantizer", "scenario",
                "replicas")


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


class _WindowClosed(Exception):
    """Raised out of ``receive`` to end ``run()`` when the window closes."""


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    model: Any
    bench: dict

    @property
    def per_layer(self) -> List[dict]:
        """The per-layer metrics this cell reports."""
        return [m for m in self.bench["per_layer"]
                if self.name in m.get("workloads", [self.name])]

    @property
    def end_to_end(self) -> List[dict]:
        return [m for m in self.bench["end_to_end"]
                if self.name in m.get("workloads", [self.name])]


def load_cell(workload: str, overrides: Optional[dict] = None) -> Cell:
    """Find the cell's files by name. ``overrides`` (``{"config": {...},
    "traffic": {...}}``) shrinks a cell for the CPU self-tests."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((ROOT / cfg_entry["file"]).read_text())
    traffic = json.loads((BENCH / "traffic" / f"{w['traffic']}.json")
                         .read_text())
    limits = json.loads((BENCH / "limits" / f"{workload}.json").read_text())
    overrides = overrides or {}
    config.update(overrides.get("config", {}))
    traffic.update(overrides.get("traffic", {}))
    if traffic["engine"] != "cohort":
        raise ValueError(f"traffic {w['traffic']!r}: the harness drives the "
                         f"cohort engine, not {traffic['engine']!r}")
    model = load_module(BENCH / "models" / f"{config['model']}.py",
                        f"bench_model_{config['model']}")
    return Cell(workload, int(w["chips"]), config, traffic, limits, model,
                bench)


def setup_jax() -> None:
    """The persistent compilation cache at a fixed place in the checkout,
    unless ``JAX_COMPILATION_CACHE_DIR`` names one; every program is
    cached, however quickly it compiled."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def device_info(chips: int, require_tpu: bool = True) -> dict:
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if require_tpu and info["platform"] != "tpu":
        raise NoChip(f"JAX found no TPU (platform {info['platform']!r})")
    if info["count"] < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees "
                     f"{info['count']}")
    return info


def peak_memory_bytes() -> Optional[int]:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


class _Compiles:
    """Counts backend compiles by a ``jax.monitoring`` listener, so a
    compile inside the window shows."""

    def __init__(self):
        self.compiles = 0

    def __call__(self, event: str, secs: float, **_):
        if event == COMPILE_EVENT:
            self.compiles += 1


@dataclasses.dataclass
class Window:
    """What the measured window saw, on the host's clock."""
    t_open: float = 0.0
    t_close: float = 0.0
    uploads: int = 0
    trained: int = 0
    receive_s: List[float] = dataclasses.field(default_factory=list)
    flush_s: List[float] = dataclasses.field(default_factory=list)
    step_s: List[float] = dataclasses.field(default_factory=list)
    compiles: int = 0
    counters: Dict[str, int] = dataclasses.field(default_factory=dict)

    @property
    def seconds(self) -> float:
        return self.t_close - self.t_open


def _counters() -> Dict[str, int]:
    """The program's retrace counters (``repro.kernels.ops.*_TRACES``)."""
    from repro.kernels import ops
    return {k: int(getattr(ops, k)) for k in dir(ops)
            if k.endswith("_TRACES") and isinstance(getattr(ops, k), int)}


class Run:
    """One cell at one seed: builds the engine, instruments it, runs
    set-up and the window."""

    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool,
                 trace_dir: Optional[Path] = None,
                 patches: Optional[List[Callable]] = None, t0=None,
                 check_only: bool = False):
        self.cell, self.seed, self.seconds = cell, int(seed), float(seconds)
        self.check_only = check_only
        self.trace, self.trace_dir = trace, trace_dir
        self.patches = patches or []
        self.t0 = time.perf_counter() if t0 is None else t0
        self.phase = "setup"
        self.flushes = 0
        self.received = 0
        self.admitted = 0
        self.fill_received: Optional[int] = None
        self.block = None  # the open bench.reference.Block, if any
        self.window = Window()
        self.traced: Optional[Window] = None  # the traced part, if any
        self.setup_s = None
        self.compile_watch = _Compiles()
        self._keys: Dict[int, tuple] = {}

    # -- building -------------------------------------------------------
    def qafel_config(self) -> dict:
        c, t = self.cell.config, self.cell.traffic
        return {"client_lr": c["client_lr"], "server_lr": c["server_lr"],
                "server_momentum": c["server_momentum"],
                "buffer_size": t["buffer_size"],
                "local_steps": c["local_steps"]}

    def build(self):
        import jax

        from repro.core import QAFeL, QAFeLConfig
        from repro.sim import CohortAsyncFLSimulator, SimConfig

        cell, t, c = self.cell, self.cell.traffic, self.cell.config
        k_w, k_d = jax.random.split(jax.random.PRNGKey(self.seed))
        params0 = cell.model.init_params(k_w, c)
        d = sum(int(np.prod(v.shape)) for v in jax.tree.leaves(params0))
        if d != c["d"]:
            raise ValueError(f"the configuration states d={c['d']}, its "
                             f"shapes give {d}")
        self.data = cell.model.make_data(k_d, c, t)
        provider = cell.model.provider(self.data, c, t)
        self.qcfg = self.qafel_config()
        qc = QAFeLConfig(**self.qcfg, client_quantizer=t["client_quantizer"],
                         server_quantizer=t["server_quantizer"])
        self.algo = QAFeL(qc, cell.model.program_loss, params0)
        del params0
        big = 1 << 62
        self.sim = CohortAsyncFLSimulator(
            self.algo, SimConfig(concurrency=t["concurrency"],
                                 eval_every_steps=big, max_uploads=big,
                                 track_hidden_replicas=t["replicas"],
                                 seed=self.seed),
            self._counting(provider), _no_eval, scenario=t["scenario"],
            cohort_size=t["cohort_size"])
        timing = np.random.default_rng(TIMING_SEED)
        self.sim.rng = self.sim.sampler.rng = timing
        self.bounds = _layout_bounds(self.algo.state.layout)
        from bench.reference import Record
        self.record = Record(n=self.algo.state.n,
                             sbits=self.algo.sq.spec.bits)
        self._open_block(start=None)

    def _counting(self, provider):
        def batches(cids, keys):
            self.admitted += len(cids)
            if self.phase == "window":
                self.window.trained += len(cids)
            return provider(cids, keys)
        batches.batched = True
        return batches

    # -- instrumentation ------------------------------------------------
    def _span(self, label: str):
        if not self.trace or self.phase != "window":
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(label)

    @contextlib.contextmanager
    def instrumented(self):
        """Wrap the cohort step (``repro.kernels.ops``, looked up by the
        engine at call time) and the instance's ``receive``; undo on
        exit."""
        from repro.kernels import ops
        step0 = ops.cohort_train_encode_step
        run = self

        def step(loss_fn, qcfg, spec, layout, hidden_flat, batches, k_train,
                 k_enc, flag, *, b, **kw):
            if run.phase == "setup":
                run._note_keys(spec, batches, k_train, k_enc, b)
            with run._span("client_step"):
                t0 = time.perf_counter()
                out = step0(loss_fn, qcfg, spec, layout, hidden_flat, batches,
                            k_train, k_enc, flag, b=b, **kw)
                t1 = time.perf_counter()
            if run.phase == "window":
                run.window.step_s.append(t1 - t0)
            return out

        receive0 = self.algo.receive
        ops.cohort_train_encode_step = step
        self.algo.receive = self._receive(receive0)
        undo = [patch(self) for patch in self.patches]
        try:
            yield
        finally:
            for u in reversed(undo):
                u()
            ops.cohort_train_encode_step = step0
            self.algo.receive = receive0

    def _note_keys(self, spec, batches, k_train, k_enc, b):
        cids = np.asarray(batches["cid"]).reshape(b, -1)[:, 0]
        kt = np.asarray(k_train).reshape(b, -1)[:, :2].astype(np.uint32)
        ke = np.asarray(k_enc).reshape(b, -1)[:, :2].astype(np.uint32)
        for i, cid in enumerate(cids.tolist()):
            self._keys.setdefault(int(cid), (kt[i], ke[i], spec.bits))

    def _receive(self, receive0):
        import jax
        run, algo = self, self.algo

        def receive(msg, key, n_receivers: int = 1):
            if run.phase == "closed":
                # the engine has applied the last broadcast to its
                # replicas since the window closed: end run() here
                raise _WindowClosed
            flushing = algo.buffer.count + 1 >= algo.buffer.capacity
            if run.block is not None:
                run._note_upload(msg)
            with run._span("flush" if flushing else "receive"):
                t0 = time.perf_counter()
                bmsg = receive0(msg, key, n_receivers)
                if bmsg is not None:
                    jax.block_until_ready((bmsg.payload["packed"],
                                           bmsg.payload["norms"]))
                t1 = time.perf_counter()
            run.received += 1
            if run.phase == "window":
                run.window.uploads += 1
                (run.window.flush_s if bmsg is not None
                 else run.window.receive_s).append(t1 - t0)
            if bmsg is not None:
                run.flushes += 1
                if run.phase == "setup":
                    run._note_flush(key, bmsg)
                run._advance()
            return bmsg

        return receive

    def _note_upload(self, msg):
        from bench.reference import Upload
        p, blk = msg.payload, self.block
        cid = int(msg.meta["client"])
        kt, ke, _ = self._keys[cid]
        i = self.flushes - (blk.first - 1)
        while len(blk.uploads) <= i:
            blk.uploads.append([])
        blk.uploads[i].append(Upload(
            cid=cid, version=int(msg.meta["version"]), bits=int(p["bits"]),
            packed=np.array(p["packed"]), norms=np.array(p["norms"],
                                                         np.float32),
            k_train=kt, k_enc=ke))

    def _host(self, v) -> np.ndarray:
        return np.asarray(v)[:self.algo.state.n]

    def _open_block(self, start):
        from bench.reference import Block
        self.block = Block(first=self.flushes + 1, start=start)
        self.record.blocks.append(self.block)

    def _note_flush(self, key, bmsg):
        """Keep every broadcast of set-up; in a block, the flush's key, the
        momentum after its first flush and x and x-hat after its last."""
        st, blk = self.algo.state, self.block
        self.record.broadcasts.append((
            np.asarray(bmsg.payload["packed"]),
            np.asarray(bmsg.payload["norms"], np.float32)))
        if blk is None:
            return
        blk.flush_keys.append(np.asarray(key).reshape(-1)[:2].astype(
            np.uint32))
        if len(blk.flush_keys) == 1:
            blk.m1 = self._host(st.momentum_flat)
        if len(blk.flush_keys) == CHECK_FLUSHES:
            blk.end = {"x": self._host(st.x_flat),
                       "h": self._host(st.hidden_flat)}
            self.block = None

    def filled(self) -> bool:
        """The clients in flight (admitted, not yet delivered, not
        dropped) reach nine tenths of the concurrency."""
        in_flight = self.admitted - self.received - self.sim.dropped
        return in_flight >= 0.9 * self.cell.traffic["concurrency"]

    def _advance(self):
        """At a flush: open the steady block once a whole concurrency of
        uploads has come back since the pipeline filled, and the window
        right after that block, so the window holds whole buffer cycles
        of K uploads; close the window."""
        import jax
        if self.phase == "setup":
            if self.block is not None:
                return
            if len(self.record.blocks) == 1:
                if self.fill_received is None:
                    if self.filled():
                        self.fill_received = self.received
                elif (self.received - self.fill_received
                      >= self.cell.traffic["concurrency"]):
                    st = self.algo.state
                    self._open_block(start={
                        "x": self._host(st.x_flat),
                        "m": self._host(st.momentum_flat),
                        "h": self._host(st.hidden_flat)})
                return
            self._keys.clear()
            if self.check_only:
                self.phase = "closed"
                return
            if self.trace:
                # the benchmark's own spans are host events of level 1;
                # the Python tracer would record every call of the engine
                opts = jax.profiler.ProfileOptions()
                opts.host_tracer_level = 1
                opts.python_tracer_level = 0
                opts.enable_hlo_proto = False
                jax.profiler.start_trace(str(self.trace_dir),
                                         profiler_options=opts)
            self.window.counters = _counters()
            self.window.compiles = self.compile_watch.compiles
            self.phase = "window"
            self.window.t_open = time.perf_counter()
            self.setup_s = self.window.t_open - self.t0
        elif self.phase == "window":
            elapsed = time.perf_counter() - self.window.t_open
            closing = elapsed >= self.seconds
            if self.trace and self.traced is None and (
                    closing or elapsed >= TRACE_SECONDS):
                self._stop_trace()
            if not closing:
                return
            st = self.algo.state
            jax.block_until_ready((st.x_flat, st.hidden_flat,
                                   st.momentum_flat))
            self.window.t_close = time.perf_counter()
            self.phase = "closed"
            after = _counters()
            self.window.counters = {
                k: after[k] - self.window.counters.get(k, 0) for k in after}
            self.window.compiles = (self.compile_watch.compiles
                                    - self.window.compiles)

    def _stop_trace(self):
        """End the traced part at this flush, once the server state is
        ready, and keep what the window saw until then."""
        import jax
        st = self.algo.state
        jax.block_until_ready((st.x_flat, st.hidden_flat, st.momentum_flat))
        w = self.window
        self.traced = dataclasses.replace(
            w, t_close=time.perf_counter(), receive_s=list(w.receive_s),
            flush_s=list(w.flush_s), step_s=list(w.step_s))
        jax.profiler.stop_trace()

    # -- running ----------------------------------------------------------
    def run(self) -> None:
        import jax
        jax.monitoring.register_event_duration_secs_listener(
            self.compile_watch)
        with self.instrumented():
            try:
                self.sim.run()
            except _WindowClosed:
                pass
        if self.phase != "closed":
            raise RuntimeError(f"the engine stopped in phase {self.phase}")

    def replica_mismatch(self) -> int:
        """Values in which a tracked client replica's x-hat differs, bit
        for bit, from the server's: Algorithm 3's invariant."""
        import jax
        import jax.numpy as jnp
        n = self.algo.state.n
        h = self.algo.state.hidden_flat[:n]
        bad = 0
        for rep in self.sim.replicas:
            a = jax.lax.bitcast_convert_type(rep[:n], jnp.uint32)
            b = jax.lax.bitcast_convert_type(h, jnp.uint32)
            bad += int(jnp.sum(a != b))
        return bad

    def free(self) -> None:
        """Drop the program's state, so the reference runs beside nothing."""
        for name in ("sim", "algo"):
            setattr(self, name, None)
        gc.collect()


def _no_eval(params):
    raise RuntimeError("evaluation is off in the benchmark")


def _layout_bounds(layout) -> tuple:
    sizes = [int(np.prod(s)) for s in layout.shapes]
    out, start = [], 0
    for s in sizes:
        out.append((start, start + s))
        start += s
    return tuple(out)


def reference_inputs(cell: Cell, run: Run):
    """The reference's own view of the model: the initial flat weights of
    any block, made again from the seed by the benchmark's generator, and
    the tree view of a flat vector."""
    import jax
    import jax.numpy as jnp
    model, cfg = cell.model, cell.config
    k_w, _ = jax.random.split(jax.random.PRNGKey(run.seed))
    shapes = jax.eval_shape(lambda k: model.init_params(k, cfg), k_w)
    leaves, treedef = jax.tree.flatten(shapes)

    def unflatten(flat):
        out, start = [], 0
        for leaf in leaves:
            size = int(np.prod(leaf.shape))
            out.append(flat[start:start + size].reshape(leaf.shape))
            start += size
        return jax.tree.unflatten(treedef, out)

    if model.SEPARABLE:
        def init_flat(e0, e1):
            return model.init_block(k_w, e0, e1)
    else:
        full = jnp.concatenate([v.reshape(-1) for v in jax.tree.leaves(
            model.init_params(k_w, cfg))])

        def init_flat(e0, e1):
            return full[e0:e1]
    return init_flat, unflatten


def check(cell: Cell, run: Run, control=None) -> Dict[str, Any]:
    """The reference replay of the recorded flushes (``bench.reference``)
    against the program, or against the control in ``control``'s
    precision."""
    from bench.reference import replay
    init_flat, unflatten = reference_inputs(cell, run)
    return replay(run.record, cell.model, cell.config, run.qcfg, run.data,
                  init_flat, unflatten, run.bounds,
                  cell.config.get("reference_block_rows", 1 << 30),
                  control=control)


def judge(numbers: Dict[str, float], limits: Dict[str, float]) -> tuple:
    """Each number that has a limit in the cell's limits file against it.
    A limit without its number is a fault of the benchmark; a number
    without a limit is printed and not compared (``PERF.md`` says why for
    each)."""
    missing = sorted(set(limits) - set(numbers))
    if missing:
        raise KeyError(f"limits without numbers: {missing}")
    checks = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
    ok = all(math.isfinite(v["value"]) and v["value"] <= v["limit"]
             for v in checks.values())
    return ok, checks
