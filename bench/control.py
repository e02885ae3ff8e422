"""Readings that set the comparison limits, at a cell's own size on the chip.

    python3 -m bench.control --workload <cell> --seeds 1 2 ... \\
        --control-seeds 21 22 23 --fault-seeds 31 32 33

One process, so the programs compile once. For each seed it runs the
cell's set-up up to the end of its steady block of checked flushes (no
window: the comparison reads only the blocks) and prints one JSON line
per reading:

* ``program``: the system as the cell runs it, the lower readings;
* ``control``: the reference in bfloat16 in the program's place (the
  configuration states float32), which must fail;
* one line per fault of ``bench/faults.py`` (``--faults`` picks some).

The last line sums them up: per number the largest program reading, the
smallest control reading, the smallest reading of each fault, and a limit
set between them (see ``limit``). It exits 3 without readings where JAX
finds no TPU or too few chips, like ``bench.run``.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

from bench import faults, harness


def reading(cell, seed: int, patches=(), control=None) -> dict:
    run = harness.Run(cell, seed, 0.0, False, patches=list(patches),
                      check_only=True)
    run.build()
    run.run()
    replica = run.replica_mismatch()
    run.free()
    res = harness.check(cell, run, control=control)
    print(f"seed {seed}: staleness {res['staleness']}, worst buckets "
          f"{res['worst']}", file=sys.stderr, flush=True)
    return dict(res["numbers"], replica_mismatch=replica)


def limit(lower: float, upper) -> float | None:
    """A limit between the lower and the upper reading, in a log scale a
    third of the way down from the upper: more room above the lower
    reading than below the upper one. None where the upper reading is not
    three times the lower."""
    if upper is None or not upper > 3 * lower:
        return None
    if lower <= 0:
        return upper / 10
    return math.exp(math.log(lower) / 3 + 2 * math.log(upper) / 3)


def summarize(rows: list) -> dict:
    names = sorted(rows[0]["numbers"])
    out = {}
    for name in names:
        prog = [r["numbers"][name] for r in rows if r["mode"] == "program"]
        lower = max(prog) if prog else None
        ctl = [r["numbers"][name] for r in rows if r["mode"] == "control"]
        faults_min = {}
        for r in rows:
            if r["mode"] in ("program", "control"):
                continue
            v = r["numbers"][name]
            faults_min[r["mode"]] = min(v, faults_min.get(r["mode"], v))
        uppers = []
        if ctl and lower is not None and min(ctl) >= 3 * lower \
                and min(ctl) > 0:
            uppers.append(min(ctl))
        for f, v in faults_min.items():
            need = 3 if f == "unchanged_state" else 10
            if lower is not None and v >= need * lower and v > 0:
                uppers.append(v)
        upper = min(uppers) if uppers else None
        out[name] = {"lower": lower, "control": min(ctl) if ctl else None,
                     "faults": faults_min, "upper": upper,
                     "limit": None if lower is None else limit(lower, upper)}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--fault-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--faults", nargs="*", default=None,
                    help="names from bench/faults.py (default: all)")
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    sys.path.insert(0, str(harness.ROOT / "src"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    harness.setup_jax()
    try:
        harness.device_info(cell.chips)
    except harness.NoChip as e:
        print(f"bench.control: {e}", file=sys.stderr)
        return 3
    import jax.numpy as jnp
    plans = [("program", s, (), None) for s in args.seeds]
    plans += [("control", s, (), jnp.bfloat16) for s in args.control_seeds]
    chosen = faults.FAULTS
    if args.faults is not None:
        chosen = {k: chosen[k] for k in args.faults}
    plans += [(name, s, (patch,), None) for name, patch in chosen.items()
              for s in args.fault_seeds]
    rows = []
    for mode, seed, patches, control in plans:
        t0 = time.perf_counter()
        numbers = reading(cell, seed, patches, control)
        row = {"cell": cell.name, "mode": mode, "seed": seed,
               "seconds": time.perf_counter() - t0, "numbers": numbers}
        rows.append(row)
        print(json.dumps(row), flush=True)
    if rows:
        print(json.dumps({"cell": cell.name, "summary": summarize(rows)}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
