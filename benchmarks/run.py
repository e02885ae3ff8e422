"""Benchmark harness — one module per paper table/figure + system extras.

Prints ``name,us_per_call,derived`` CSV rows (one per measured cell).

    PYTHONPATH=src python -m benchmarks.run            # everything
    PYTHONPATH=src python -m benchmarks.run --only kernel,roofline
    PYTHONPATH=src python -m benchmarks.run --only kernel --json BENCH_PR4.json
    PYTHONPATH=src python -m benchmarks.run --only kernel --check BENCH_PR4.json

``--json PATH`` additionally writes every row as machine-readable JSON
(with the ``k=v;k=v`` derived string parsed into a dict) so CI can archive
the perf trajectory across PRs — uploads/sec, flush latency, dispatch
counts, compression ratios.

``--check PATH`` is the perf regression gate: the committed baseline JSON
is loaded BEFORE the suites run (so ``--json`` may overwrite the same
path), and every fused-path speedup row present in both runs —
``server/flush_*``, ``sim/cohort_step_*`` and ``shard/*`` — must stay within
``--check-tolerance`` (default 20%; doubled for sub-parity baseline rows,
which document a caveat rather than claim a win) of its baseline speedup,
else the process exits non-zero. Gated baseline rows missing from the run
and crashed suites also fail — a broken benchmark must not pass
vacuously. Only speedup *ratios* are gated (fused vs reference on the
same host, interleaved min-of-N timing), never absolute wall-clock, so
the gate is machine-portable.
"""
from __future__ import annotations

import argparse
import json
import re
import sys
import time
import traceback

_ROWS: list = []


def report(name: str, us_per_call: float, derived: str = "") -> None:
    _ROWS.append({"name": name, "us_per_call": round(us_per_call, 1),
                  "derived": _parse_derived(derived)})
    print(f"{name},{us_per_call:.1f},{derived}", flush=True)


def _parse_derived(derived: str):
    """Best-effort parse of the 'k=v;k=v' derived string (numbers where
    possible); non-conforming fragments are kept verbatim under 'notes'."""
    if not derived:
        return {}
    out, notes = {}, []
    for part in derived.split(";"):
        if "=" in part:
            k, v = part.split("=", 1)
            try:
                out[k] = int(v)
            except ValueError:
                try:
                    out[k] = float(v)
                except ValueError:
                    out[k] = v
        elif part:
            notes.append(part)
    if notes:
        out["notes"] = ";".join(notes)
    return out


SUITES = ["kernel", "roofline", "table1", "fig3", "table2"]

# rows the --check gate covers: the fused-path speedup families plus the
# sharded-substrate overhead rows (shard/*_speedup_ndevN and the 2-D
# shard2d/*_speedup rows — sub-parity on a 2-core CI box, gated so the
# sharding/chunking overhead can't silently balloon), the population
# engine's uploads/sec-vs-event-loop acceptance row, and the lowrank
# upload-bytes reduction rows (wire/lowrank_*_speedup_* — deterministic
# byte ratios, so the gate pins the wire law itself, not a wall clock)
_GATED_PREFIXES = ("server/flush_", "sim/cohort_step_", "shard/", "shard2d/",
                   "sim/population_", "wire/")


def _speedup_value(row) -> float | None:
    """Extract the xN.NN speedup ratio from a row's parsed derived dict
    (under the 'speedup' key, else the free-form 'notes')."""
    derived = row.get("derived", {})
    for key in ("speedup", "notes"):
        v = derived.get(key)
        if isinstance(v, str):
            m = re.match(r"^x([0-9]+(?:\.[0-9]+)?)", v)
            if m:
                return float(m.group(1))
    return None


def run_check(baseline: dict, rows: list, tolerance: float) -> int:
    """Compare this run's gated speedup rows against the baseline; returns
    the number of failures (regressions beyond ``tolerance``, plus gated
    baseline rows this run failed to produce).

    A crashed or partially-run suite must NOT pass vacuously: every gated
    row the baseline carries is expected in the current run, and a check
    that ends up comparing zero rows is itself a failure.
    """
    def is_gated(name: str) -> bool:
        return name.startswith(_GATED_PREFIXES) and "speedup" in name

    base_rows = {r["name"]: r for r in baseline.get("rows", [])
                 if is_gated(r["name"])}
    cur_rows = {r["name"]: r for r in rows if is_gated(r["name"])}
    failures = 0
    checked = 0
    for name, row in cur_rows.items():
        if name not in base_rows:
            print(f"check: {name}: no baseline row (new row, skipped)",
                  file=sys.stderr)
            continue
        cur_v, base_v = _speedup_value(row), _speedup_value(base_rows[name])
        if cur_v is None or base_v is None:
            print(f"check: {name}: unparseable speedup, skipped",
                  file=sys.stderr)
            continue
        checked += 1
        # sub-parity baselines are documented-caveat rows (e.g. the
        # conv-grad-dominated cnn18 cohort step): they claim no win to
        # protect and sit closest to measurement noise, so they gate at
        # twice the tolerance instead of being exempted outright
        tol = tolerance if base_v >= 1.0 else min(2 * tolerance, 0.9)
        floor = (1.0 - tol) * base_v
        verdict = "OK" if cur_v >= floor else "REGRESSION"
        if cur_v < floor:
            failures += 1
        print(f"check: {name}: x{cur_v:.2f} vs baseline x{base_v:.2f} "
              f"(floor x{floor:.2f}) {verdict}", file=sys.stderr)
    for name in base_rows:
        if name not in cur_rows:
            failures += 1
            print(f"check: {name}: MISSING from this run (suite crashed or "
                  "row renamed) — counted as a failure", file=sys.stderr)
    if checked == 0 and base_rows:
        failures += 1
        print("check: no gated rows were compared — counted as a failure "
              "(did the benchmark suite run?)", file=sys.stderr)
    print(f"check: {checked} gated rows, {failures} failure(s)",
          file=sys.stderr)
    return failures


def main() -> None:
    from repro.common.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of: " + ",".join(SUITES))
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write all rows as machine-readable JSON")
    ap.add_argument("--check", default=None, metavar="BASELINE_JSON",
                    help="fail if any gated speedup row (server/flush_*, "
                         "sim/cohort_step_*) regresses vs this baseline")
    ap.add_argument("--check-tolerance", type=float, default=0.2,
                    help="allowed fractional speedup regression (default 0.2)")
    args = ap.parse_args()
    # read the baseline up front: --json may legitimately overwrite it
    baseline = None
    if args.check:
        with open(args.check) as f:
            baseline = json.load(f)
    chosen = args.only.split(",") if args.only else SUITES
    print("name,us_per_call,derived")
    t0 = time.time()
    failures = 0
    for suite in chosen:
        try:
            if suite == "kernel":
                from benchmarks import kernel_bench
                kernel_bench.main(report)
            elif suite == "roofline":
                from benchmarks import roofline
                roofline.main(report)
            elif suite == "table1":
                from benchmarks import table1_qsgd_grid
                table1_qsgd_grid.main(report)
            elif suite == "fig3":
                from benchmarks import fig3_concurrency
                fig3_concurrency.main(report)
            elif suite == "table2":
                from benchmarks import table2_biased_server
                table2_biased_server.main(report)
            else:
                raise ValueError(f"unknown suite {suite}")
        except Exception as e:
            failures += 1
            report(f"{suite}/ERROR", 0.0, f"{type(e).__name__}:{e}")
            traceback.print_exc(file=sys.stderr)
    report("total_wall", (time.time() - t0) * 1e6, f"failures={failures}")
    if args.json:
        import jax

        payload = {
            "suites": chosen,
            "backend": jax.default_backend(),
            "jax_version": jax.__version__,
            "failures": failures,
            "rows": _ROWS,
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=1)
        print(f"wrote {len(_ROWS)} rows to {args.json}", file=sys.stderr)
    regressions = (run_check(baseline, _ROWS, args.check_tolerance)
                   if baseline is not None else 0)
    if failures:  # a crashed suite can't certify anything
        print(f"{failures} suite error(s) — failing the run", file=sys.stderr)
    if regressions or failures:
        sys.exit(2)


if __name__ == "__main__":
    main()
