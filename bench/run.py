"""Run one benchmark cell once on the chip.

    python3 -m bench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout. The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
``device``, with ``--trace 1`` ``breakdown``, and last ``checks``: every
number the reference comparison produced beside its limit. The same
numbers are the last lines of standard error.

Exits 2 without a result where the checkout lacks the program or a file
of the cell, and 3 where JAX finds no TPU or fewer chips than the cell
asks for. There is no fallback to the CPU.
"""
from __future__ import annotations

import time

_T0 = time.perf_counter()  # set-up is timed from here, before JAX loads

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Optional  # noqa: E402

import numpy as np  # noqa: E402

from bench import harness  # noqa: E402


@dataclasses.dataclass
class Context:
    """What a per-layer metric's reader (``bench/metrics/<name>.py``) may
    read."""
    cell: Any
    window: Any  # harness.Window: in a traced run, its traced part
    trace: Any  # bench.trace.Reduction, or None
    uploads_per_s: float  # over that window
    work: dict  # required flops and bytes per upload
    peak: dict
    chips: int
    d: int
    k: int
    bits: int
    sbits: int


def _peaks(kind: str) -> dict:
    table = json.loads((harness.BENCH / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def _bits(name: str) -> int:
    return int(name.removeprefix("qsgd"))


def _work(cell) -> dict:
    from bench import work
    t, c = cell.traffic, cell.config
    cw = cell.model.client_work(c)
    return work.step_work(c["d"], _bits(t["client_quantizer"]),
                          _bits(t["server_quantizer"]),
                          t["buffer_size"], cw["data_bytes"], cw["flops"])


def measure(cell, seed: int, seconds: float, trace: bool, dev: dict,
            t0: Optional[float] = None, patches=None, control=None) -> dict:
    """Set-up, the window, the reference check: the result object."""
    trace_dir = Path(tempfile.mkdtemp(prefix="bench-trace-")) if trace else None
    try:
        run = harness.Run(cell, seed, seconds, trace, trace_dir,
                          patches=patches, t0=t0)
        run.build()
        run.run()
        win = run.window
        peak = harness.peak_memory_bytes()
        replica = run.replica_mismatch()
        red = None
        if trace:
            from bench import trace as tr
            t_read = time.perf_counter()
            red = tr.reduce(tr.load(tr.newest_xplane(trace_dir)),
                            run.traced.seconds)
            print(f"trace: {run.traced.seconds:.3f} s traced, "
                  f"{time.perf_counter() - t_read:.1f} s to read",
                  file=sys.stderr, flush=True)
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    rate = win.uploads / win.seconds
    if win.compiles or any(win.counters.values()):
        print(f"compiled inside the window: {win.compiles} backend "
              f"compiles, retrace counters {win.counters}",
              file=sys.stderr, flush=True)
    if not trace:
        values = {
            "uploads_per_s": rate,
            "flush_p95_ms": (float(np.percentile(win.flush_s, 95)) * 1e3
                             if win.flush_s else None),
            "peak_hbm_gb": (peak or 0) / 1e9,
            "setup_s": run.setup_s,
        }
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        metrics = {k: {"value": v, "unit": units[k]} for k, v in values.items()
                   if k in units and v is not None}
    else:
        t, tw = cell.traffic, run.traced
        ctx = Context(cell, tw, red, tw.uploads / tw.seconds, _work(cell),
                      _peaks(dev["kind"]),
                      cell.chips, cell.config["d"], t["buffer_size"],
                      _bits(t["client_quantizer"]),
                      _bits(t["server_quantizer"]))
        metrics = {}
        for m in cell.per_layer:
            reader = harness.load_module(
                harness.BENCH / "metrics" / f"{m['name']}.py",
                f"bench_metric_{m['name'].replace('.', '_')}")
            v = reader.read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = dict(dev, memory_peak_bytes=peak)
    out = {"attempted": win.uploads, "failed": 0, "metrics": metrics,
           "device": device}
    if red is not None:
        device["busy_s"] = red.mean_busy_s
        device["window_s"] = red.window_s
        out["breakdown"] = {"device_ops": red.top_ops(10),
                            "idle_gaps": red.idle_by_host(10)}
    run.free()
    t_ref = time.perf_counter()
    res = harness.check(cell, run, control=control)
    numbers = dict(res["numbers"], replica_mismatch=replica)
    ok, checks = harness.judge(numbers, cell.limits)
    for name in sorted(set(numbers) - set(checks)):
        print(f"not compared {name}: {numbers[name]!r}", file=sys.stderr)
    blocks = run.record.blocks
    print(f"reference replay: {time.perf_counter() - t_ref:.3f} s, "
          f"{sum(len(u) for b in blocks for u in b.uploads)} uploads in "
          f"{sum(len(b.uploads) for b in blocks)} flushes from flushes "
          f"{[b.first for b in blocks]}, staleness (least, most) "
          f"{res['staleness']}, {res['leaves_left_out']} of "
          f"{res['leaves']} leaves left out of the leaf gaps, worst buckets "
          f"(index, row, reference, other) {res['worst']}",
          file=sys.stderr, flush=True)
    return {"correct": ok, **out, "checks": checks}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = harness.load_cell(args.workload)
    except (KeyError, FileNotFoundError, ValueError) as e:
        print(f"bench: cannot load the cell: {e!r}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(harness.ROOT / "src"))
    try:
        import repro  # noqa: F401
    except ImportError as e:
        print(f"bench: the program is not in this checkout: {e}",
              file=sys.stderr)
        return 2
    # libtpu writes its logs under /tmp unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    harness.setup_jax()
    try:
        dev = harness.device_info(cell.chips)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    out = measure(cell, args.seed, args.seconds, bool(args.trace), dev,
                  t0=_T0)
    print(f"correct: {out['correct']}", file=sys.stderr)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
