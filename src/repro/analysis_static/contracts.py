"""The compiled-contract pass: lower the fused entries, assert the HLO.

The AST pass reads declarations; this pass checks what XLA actually built.
For every entry in ``repro.kernels.ops.CONTRACTS`` it drives a tiny real
QAFeL run (so the probe shapes/statics are exactly what production passes
— the capture wrapper records each entry's arguments as avals at call
time), then asserts three things per configuration and device count:

* **donation aliasing** — the compiled module's ``input_output_alias``
  header must alias exactly the declared donated parameters (in-place
  server state update), shifted for jit's keep_unused pruning when
  ``beta is None`` drops the momentum buffer from the module;
* **hard boundaries survived** — at least ``min_hard_boundaries`` HLO
  ``conditional`` ops remain (each ``hard_boundary`` is one lax.cond; if
  XLA elided one it is free to FMA-contract across what used to be an
  eager dispatch boundary and bit-exactness with the reference dies);
* **single dispatch** — under ``trace_guard`` the drive makes no python
  call into any base kernel entry inside the guarded window, and a second
  engine instance with the same statics triggers ZERO retraces.

Device counts above ``jax.device_count()`` re-exec this module in a
subprocess with ``XLA_FLAGS=--xla_force_host_platform_device_count`` (the
same trick the 8-virtual-device CI job uses) and merge its JSON findings.
"""
from __future__ import annotations

import dataclasses
import inspect
import json
import os
import re
import subprocess
import sys
from typing import Dict, List, Optional, Sequence, Tuple

from repro.analysis_static.findings import Finding
from repro.analysis_static.trace_guard import TraceGuardError, trace_guard

_PROBE_D = 512
_XLA_FORCE = "--xla_force_host_platform_device_count"


# ---------------------------------------------------------------------------
# HLO text parsing (reuses launch.hlo_analyzer for the op stream)
# ---------------------------------------------------------------------------

_ALIAS_PAIR_RE = re.compile(r"\{([\d\s,]*)\}:\s*\((\d+)")


def parse_io_aliases(hlo_text: str) -> List[Tuple[str, int]]:
    """``input_output_alias={ {0}: (0, {}, may-alias), ... }`` ->
    [(output_index, param_index), ...]. Brace-depth scan because the block
    nests the per-pair shape index ``{}``."""
    start = hlo_text.find("input_output_alias=")
    if start < 0:
        return []
    i = hlo_text.index("{", start)
    depth = 0
    for j in range(i, len(hlo_text)):
        if hlo_text[j] == "{":
            depth += 1
        elif hlo_text[j] == "}":
            depth -= 1
            if depth == 0:
                break
    block = hlo_text[i:j + 1]
    return [(m.group(1).strip(), int(m.group(2)))
            for m in _ALIAS_PAIR_RE.finditer(block)]


def count_conditionals(hlo_text: str) -> int:
    from repro.launch.hlo_analyzer import HLOModule
    mod = HLOModule(hlo_text)
    return sum(1 for comp in mod.computations.values()
               for op in comp.ops if op.opcode == "conditional")


# ---------------------------------------------------------------------------
# Probe drive: a tiny REAL run so captured shapes match production
# ---------------------------------------------------------------------------


def _probe_loss(params, batch, key):
    # module-level (hashable, stable identity): the lru-cached jit factories
    # key on loss_fn, and a fresh lambda per check would itself be the
    # retrace hazard the unhashable-static-arg rule flags.
    import jax.numpy as jnp
    del key
    return jnp.mean((params["w"] - batch["target"]) ** 2)


def _make_algo(server_quantizer: str, server_momentum: float, mesh,
               taps: bool = False, client_quantizer: str = "qsgd4"):
    import jax.numpy as jnp

    from repro.core.qafel import QAFeL, QAFeLConfig
    qcfg = QAFeLConfig(client_lr=0.1, server_lr=1.0,
                       server_momentum=server_momentum,
                       buffer_size=2, local_steps=1,
                       client_quantizer=client_quantizer,
                       server_quantizer=server_quantizer)
    params0 = {"w": jnp.zeros((_PROBE_D,), jnp.float32)}
    telemetry = None
    if taps:
        from repro.obs import RunTracer
        telemetry = RunTracer(taps=True)
    return QAFeL(qcfg, _probe_loss, params0, mesh=mesh, telemetry=telemetry)


def _drive(algo, n_flushes: int, guard=None, guard_client=None, seed: int = 0):
    """Run clients until ``n_flushes`` buffer flushes happened. ``guard``
    wraps ``receive`` (the flush window), ``guard_client`` wraps
    ``run_client`` (the cohort window)."""
    import contextlib

    import jax
    import jax.numpy as jnp
    key = jax.random.PRNGKey(seed)
    flushes = 0
    while flushes < n_flushes:
        key, k1, k2, k3 = jax.random.split(key, 4)
        batches = {"target": jnp.ones((algo.qcfg.local_steps, _PROBE_D))
                   + 0.1 * jax.random.normal(k1, (algo.qcfg.local_steps,
                                                  _PROBE_D))}
        cwin = guard_client.exclusive() if guard_client is not None \
            else contextlib.nullcontext()
        with cwin:
            msg, _ = algo.run_client(batches, k2)
        swin = guard.exclusive() if guard is not None \
            else contextlib.nullcontext()
        with swin:
            bmsg = algo.receive(msg, k3)
        if bmsg is not None:
            flushes += 1


class _Capture:
    """Record each fused entry's call arguments as avals (the arrays are
    donated by the call itself, so shapes are snapshotted pre-dispatch)."""

    def __init__(self, names: Sequence[str]):
        self.names = tuple(names)
        self.calls: Dict[str, Tuple[tuple, dict]] = {}
        self._saved: Dict[str, object] = {}

    @staticmethod
    def _aval(x):
        import jax
        if isinstance(x, jax.Array):
            # keep only real (multi-device) shardings: an uncommitted
            # host-made array's default single-device sharding would clash
            # with the mesh-sharded state at lowering time
            sh = x.sharding if len(x.sharding.device_set) > 1 else None
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh)
        return x

    def __enter__(self):
        import jax

        from repro.kernels import ops as kops

        def capturing(name, real):
            def wrapper(*a, **kw):
                self.calls[name] = (
                    jax.tree.map(self._aval, a,
                                 is_leaf=lambda l: l is None),
                    jax.tree.map(self._aval, kw,
                                 is_leaf=lambda l: l is None))
                return real(*a, **kw)
            return wrapper

        for name in self.names:
            self._saved[name] = getattr(kops, name)
            setattr(kops, name, capturing(name, self._saved[name]))
        return self

    def __exit__(self, *exc):
        from repro.kernels import ops as kops
        for name, real in self._saved.items():
            setattr(kops, name, real)
        self._saved.clear()


# ---------------------------------------------------------------------------
# Per-entry checks
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class CompiledResult:
    findings: List[Finding]
    checks: int  # contract assertions evaluated (passed + failed)


def _loc(entry: str, label: str, ndev: int) -> str:
    return f"hlo://{entry}?cfg={label}&ndev={ndev}"


def _expected_alias_params(donate: Sequence[int],
                           pruned: Sequence[int]) -> List[int]:
    out = []
    for i in donate:
        if i in pruned:
            continue
        out.append(i - sum(1 for p in pruned if p < i))
    return sorted(out)


def _lower_entry(entry: str, args: tuple, kwargs: dict) -> str:
    """Compiled HLO text for a captured call of ``entry``."""
    from repro.kernels import ops as kops
    if entry == "cohort_train_encode_step":
        # the jit lives in the lru-cached factory; rebind the capture
        bound = inspect.signature(kops.cohort_train_encode_step).bind(
            *args, **kwargs)
        bound.apply_defaults()
        p = bound.arguments
        jitted = kops._cohort_step_fn(p["loss_fn"], p["qcfg"], p["spec"],
                                      p["layout"], p["b"], p["mesh"],
                                      p["taps"], p["member_chunk"],
                                      p["chunk_rows"])
        rest = ((p["residual"], p["basis_seed"])
                if p["spec"].kind == "lowrank" else ())
        return jitted.lower(p["hidden_flat"], p["batches"], p["k_train"],
                            p["k_enc"], p["flag"], *rest).compile().as_text()
    return getattr(kops, entry).lower(*args, **kwargs).compile().as_text()


def _check_hlo(entry: str, label: str, ndev: int, args: tuple, kwargs: dict,
               findings: List[Finding]) -> int:
    from repro.kernels import ops as kops
    contract = kops.CONTRACTS[entry]
    beta = kwargs.get("beta")
    sbits = kwargs.get("sbits")
    taps = bool(kwargs.get("taps", False))
    checks = 0

    hlo = _lower_entry(entry, args, kwargs)

    # 1. donation aliasing (with keep_unused pruning under beta=None)
    pruned = contract["unused_without_momentum"] if beta is None else ()
    expected = _expected_alias_params(contract["donate"], pruned)
    got = sorted(p for _, p in parse_io_aliases(hlo))
    checks += 1
    if got != expected:
        names = contract["donated_args"]
        findings.append(Finding(
            "hlo-donation", _loc(entry, label, ndev), 0, 0,
            f"input_output_alias params {got} != expected {expected} "
            f"(donated: {', '.join(names) or 'none'}; "
            f"beta={beta!r} prunes {list(pruned)}): the in-place state "
            f"update contract is not established in the compiled module"))

    # 2. hard_boundary conditionals survived compilation (the telemetry
    # tap squares declare one extra cond when taps=True; a lowrank window /
    # cohort declares its per-upload expansion / shared projection conds)
    bkw = dict(sbits=sbits, beta=beta, taps=taps)
    if entry.startswith("server_flush_step"):
        group = kwargs.get("group")
        if group is not None:
            bkw.update(group=group, lowrank_k=int(args[3].shape[0]))
    elif entry == "cohort_train_encode_step":
        spec = args[2] if len(args) > 2 else kwargs.get("spec")
        bkw["lowrank"] = getattr(spec, "kind", None) == "lowrank"
    want = contract["min_hard_boundaries"](**bkw)
    n_cond = count_conditionals(hlo)
    checks += 1
    if n_cond < want:
        findings.append(Finding(
            "hlo-hard-boundary", _loc(entry, label, ndev), 0, 0,
            f"{n_cond} HLO conditional(s) < required {want} "
            f"(sbits={sbits!r}, beta={beta!r}, taps={taps!r}): a "
            f"hard_boundary was compiled away and XLA may now contract "
            f"across it"))
    return checks


def _check_flush(mesh, ndev: int, findings: List[Finding]) -> int:
    from repro.kernels import ops as kops
    entry = "server_flush_step" if mesh is None else "server_flush_step_sharded"
    checks = 0
    for label, squant, momentum, taps, cquant in (
            ("qsgd4+momentum", "qsgd4", 0.3, False, "qsgd4"),
            ("identity+nomomentum", "identity", 0.0, False, "qsgd4"),
            # telemetry taps ride the SAME dispatch: all contracts (donation,
            # boundary floor incl. the tap cond, single dispatch, no retrace)
            # must hold with the tap vector threaded through
            ("qsgd4+momentum+taps", "qsgd4", 0.3, True, "qsgd4"),
            # lowrank fill window: the flush dequantize-accumulates in d_r
            # space and expands per upload inside the SAME single dispatch
            ("qsgd4+lowrank", "qsgd4", 0.3, False, "lowrank4g32")):
        cap = _Capture((entry,))
        algo = _make_algo(squant, momentum, mesh, taps=taps,
                          client_quantizer=cquant)
        with cap, trace_guard("server_flush", retraces=None) as g:
            _drive(algo, 2, guard=g)
        checks += 2
        if g.calls < 2 or entry not in cap.calls:
            findings.append(Finding(
                "single-dispatch", _loc(entry, label, ndev), 0, 0,
                f"flush path made {g.calls} call(s) into the fused flush "
                f"entries but {entry} itself saw "
                f"{int(entry in cap.calls)}; expected one {entry} dispatch "
                f"per flush (2): the entry is bypassed or mis-routed"))
            continue
        if g.other_calls:
            findings.append(Finding(
                "single-dispatch", _loc(entry, label, ndev), 0, 0,
                f"{g.other_calls} base kernel dispatch(es) inside the flush "
                f"window: the flush is not ONE compiled dispatch"))

        # warm path: a fresh engine with identical statics must not retrace
        checks += 1
        try:
            with trace_guard("server_flush", retraces=0) as g2:
                _drive(_make_algo(squant, momentum, mesh, taps=taps,
                                  client_quantizer=cquant), 1,
                       guard=g2, seed=1)
        except TraceGuardError as exc:
            findings.append(Finding(
                "retrace", _loc(entry, label, ndev), 0, 0, str(exc)))

        checks += _check_hlo(entry, label, ndev, *cap.calls[entry],
                             findings=findings)
    return checks


def _check_cohort(mesh, ndev: int, findings: List[Finding]) -> int:
    entry = "cohort_train_encode_step"
    checks = 0
    for label, taps, cquant in (
            ("qsgd4", False, "qsgd4"), ("qsgd4+taps", True, "qsgd4"),
            # lowrank cohort: project + quantize-pack + in-graph decode +
            # residual update, still ONE fused dispatch
            ("lowrank4g32", False, "lowrank4g32")):
        cap = _Capture((entry,))
        algo = _make_algo("qsgd4", 0.3, mesh, taps=taps,
                          client_quantizer=cquant)
        with cap, trace_guard("cohort_step", retraces=None) as g:
            _drive(algo, 1, guard_client=g)
        checks += 2
        if g.calls < 1 or entry not in cap.calls:
            findings.append(Finding(
                "single-dispatch", _loc(entry, label, ndev), 0, 0,
                f"client path made {g.calls} call(s) into {entry}: the "
                f"fused cohort entry is being bypassed"))
            continue
        if g.other_calls:
            findings.append(Finding(
                "single-dispatch", _loc(entry, label, ndev), 0, 0,
                f"{g.other_calls} base kernel dispatch(es) inside the client "
                f"window: the client pipeline is not ONE compiled dispatch"))

        checks += 1
        try:
            with trace_guard("cohort_step", retraces=0) as g2:
                _drive(_make_algo("qsgd4", 0.3, mesh, taps=taps,
                                  client_quantizer=cquant), 1,
                       guard_client=g2, seed=1)
        except TraceGuardError as exc:
            findings.append(Finding(
                "retrace", _loc(entry, label, ndev), 0, 0, str(exc)))

        checks += _check_hlo(entry, label, ndev, *cap.calls[entry],
                             findings=findings)
    return checks


def _check_encode_chunk(ndev: int, findings: List[Finding]) -> int:
    """The streaming chunk encode (``qsgd_quantize_chunk``): deliberately
    one dispatch per chunk, so its contracts are (a) row_start is TRACED —
    one compilation serves every chunk of a shape, the host loop never
    retraces per chunk — and (b) the declared (empty) donation set and
    boundary floor hold in the compiled module."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops as kops
    checks = 0
    rows_c = 4
    flat = jnp.ones((rows_c * kops.BUCKET,), jnp.float32)
    key = jax.random.PRNGKey(0)
    for label, threefry in (("threefry", True), ("hash", False)):
        kwargs = {"bits": 4, "threefry": threefry}
        t0 = kops.ENCODE_CHUNK_TRACES
        for start in (0, rows_c, 2 * rows_c):
            kops.qsgd_quantize_chunk(flat, key, start, **kwargs)
        checks += 1
        traces = kops.ENCODE_CHUNK_TRACES - t0
        if traces > 1:
            findings.append(Finding(
                "retrace", _loc("qsgd_quantize_chunk", label, ndev), 0, 0,
                f"{traces} trace(s) for 3 same-shape chunks: row_start is "
                f"being treated as static and the host streaming loop "
                f"recompiles per chunk"))
        checks += _check_hlo("qsgd_quantize_chunk", label, ndev,
                             (flat, key, 0), kwargs, findings=findings)
    return checks


def _check_population(ndev: int, findings: List[Finding]) -> int:
    """The population macro step (``population_advance``): its donated
    argument is a whole PYTREE (arg 0 = the lifecycle state dict), so the
    generic positional-donation check doesn't apply — every leaf of the
    state dict must establish input->output aliasing in the compiled
    module. Dispatch-wise the engine loop must be ONE fused call per macro
    step with no base kernel dispatches in the window, and a fresh
    population with identical statics must trigger ZERO retraces (the
    admission/delivery alternation is a lax.cond, not a recompile)."""
    import jax

    from repro.kernels import ops as kops
    from repro.kernels import population as popk
    from repro.sim.population import compile_scenario
    from repro.sim.scenarios import get_scenario

    entry = "population_advance"
    label = "lognormal_dropout+device"
    checks = 0
    capacity, admit, deliver, queue_cap = 64, 4, 4, 256
    buckets, width = popk.wheel_shape(capacity)
    scn = compile_scenario(get_scenario("lognormal_dropout"), 32)
    statics = dict(scenario=scn, capacity=capacity, buckets=buckets,
                   bucket_width=width, admit=admit, deliver=deliver,
                   queue_cap=queue_cap)
    seeds = popk.run_seeds(0)

    def drive(n_steps):
        pop = popk.init_population(capacity, buckets, width, queue_cap)
        for step in range(n_steps):
            pop, _ = kops.population_advance(pop, seeds, step, **statics)

    n_steps = 6
    with trace_guard(entry, retraces=None) as g:
        with g.exclusive():
            drive(n_steps)
    checks += 2
    if g.calls != n_steps:
        findings.append(Finding(
            "single-dispatch", _loc(entry, label, ndev), 0, 0,
            f"engine loop made {g.calls} call(s) into {entry} for "
            f"{n_steps} macro steps; expected exactly one fused dispatch "
            f"per step"))
    if g.other_calls:
        findings.append(Finding(
            "single-dispatch", _loc(entry, label, ndev), 0, 0,
            f"{g.other_calls} base kernel dispatch(es) inside the macro-step "
            f"window: the lifecycle step is not ONE compiled dispatch"))

    # warm path: a fresh population with the same statics must not retrace
    # across macro steps — neither the admit/deliver alternation nor the
    # advancing version counter may churn the jit cache key
    checks += 1
    try:
        with trace_guard(entry, retraces=0):
            drive(2)
    except TraceGuardError as exc:
        findings.append(Finding(
            "retrace", _loc(entry, label, ndev), 0, 0, str(exc)))

    # pytree donation: every leaf of the state dict aliases its output
    pop0 = popk.init_population(capacity, buckets, width, queue_cap)
    fn = kops._population_advance_fn(scn, capacity, buckets, width, admit,
                                     deliver, queue_cap, False)
    hlo = fn.lower(pop0, seeds, 0).compile().as_text()
    expected = list(range(len(jax.tree_util.tree_leaves(pop0))))
    got = sorted(p for _, p in parse_io_aliases(hlo))
    checks += 1
    if got != expected:
        findings.append(Finding(
            "hlo-donation", _loc(entry, label, ndev), 0, 0,
            f"input_output_alias params {got} != expected {expected} "
            f"(donated: pop — all {len(expected)} state-dict leaves): the "
            f"in-place lifecycle update contract is not established in the "
            f"compiled module"))
    return checks


# ---------------------------------------------------------------------------
# Orchestration
# ---------------------------------------------------------------------------


def _run_in_process(ndev: int) -> CompiledResult:
    from repro.launch.mesh import make_sim_mesh, make_sim_mesh2d
    findings: List[Finding] = []
    checks = 0
    if ndev == 1:
        # the unsharded entries are device-count independent: check once
        checks += _check_flush(None, 1, findings)
        checks += _check_cohort(None, 1, findings)
        checks += _check_encode_chunk(1, findings)
        # pure-jnp entry, no mesh argument: device-count independent too
        checks += _check_population(1, findings)
    mesh = make_sim_mesh(ndev)
    checks += _check_flush(mesh, ndev, findings)
    checks += _check_cohort(mesh, ndev, findings)
    # the 2-D ("data","model") substrate: (1,1) on a single device, a
    # genuinely 2-D (2, ndev/2) split when more are visible — the same
    # entries must hold every contract with the model axis in play
    mesh2 = make_sim_mesh2d((1, 1) if ndev == 1 else (2, ndev // 2))
    checks += _check_flush(mesh2, ndev, findings)
    checks += _check_cohort(mesh2, ndev, findings)
    return CompiledResult(findings, checks)


def _run_subprocess(ndev: int) -> CompiledResult:
    env = dict(os.environ)
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if not f.startswith(_XLA_FORCE)]
    env["XLA_FLAGS"] = " ".join(flags + [f"{_XLA_FORCE}={ndev}"])
    src = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-m", "repro.analysis_static.contracts",
         "--ndev", str(ndev), "--json"],
        env=env, capture_output=True, text=True)
    try:
        doc = json.loads(proc.stdout.strip().splitlines()[-1])
        return CompiledResult([Finding(**f) for f in doc["findings"]],
                              doc["checks"])
    except (json.JSONDecodeError, IndexError, KeyError, TypeError):
        return CompiledResult([Finding(
            "compiled-pass-error", f"hlo://subprocess?ndev={ndev}", 0, 0,
            f"subprocess (rc={proc.returncode}) produced no parseable "
            f"result: {proc.stderr.strip()[-400:]}")], 0)


def run_compiled(ndevs: Sequence[int] = (1,)) -> CompiledResult:
    import jax
    findings: List[Finding] = []
    checks = 0
    for ndev in ndevs:
        if ndev <= jax.device_count():
            res = _run_in_process(ndev)
        elif jax.default_backend() == "tpu":
            # one process owns the chips: a child could not reach them
            res = CompiledResult([Finding(
                "compiled-pass-error", f"hlo://tpu?ndev={ndev}", 0, 0,
                f"ndev={ndev} exceeds the {jax.device_count()} visible TPU "
                "chip(s); virtual devices exist only on the CPU backend")],
                0)
        else:
            res = _run_subprocess(ndev)
        findings.extend(res.findings)
        checks += res.checks
    return CompiledResult(findings, checks)


def main(argv: Optional[Sequence[str]] = None) -> int:
    import argparse
    ap = argparse.ArgumentParser(
        description="compiled-contract pass (subprocess entry)")
    ap.add_argument("--ndev", type=int, default=1)
    ap.add_argument("--json", action="store_true")
    ns = ap.parse_args(argv)
    res = run_compiled((ns.ndev,))
    if ns.json:
        print(json.dumps({"findings": [f.as_dict() for f in res.findings],
                          "checks": res.checks}))
    else:
        for f in res.findings:
            print(f"{f.location()}: [{f.rule}] {f.message}")
        print(f"compiled pass: {len(res.findings)} finding(s), "
              f"{res.checks} check(s)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
