"""Self-tests of the benchmark, on the CPU at sizes a test run holds.

    JAX_PLATFORMS=cpu python3 -m bench.selftest [name ...]

They are not part of the repository's test suite (its ``testpaths`` is
``tests/``); run them after any change under ``bench/``. Each case prints
``ok`` or raises:

* ``work``: the byte and FLOP counts against hand counts for small shapes;
* ``wire``: the reference encode and decode against the program's on the
  same inputs: codes and decoded values bit for bit, norms to rounding;
* ``trace``: the trace reduction on the small recorded TPU trace beside
  this file, against the same numbers taken by brute force;
* ``reference``: the reference replay on a synthetic record
  (``bench/test_reference.py``): the running x-hat, the flush phase in row
  blocks, and live device bytes that do not grow with the versions in use;
* ``shrink``: every cell has its shrink file, ``bench/shrink/<cell>.json``
  (``{"config": {...}, "traffic": {...}}``: the same files at small sizes
  and short windows), and its files hold what ``bench/harness.py``'s
  docstring asks of a cell;
* ``sound``: a shrunk run of every one-chip cell, skipping the look for a
  chip, comes out correct;
* ``control``: the same runs with the bfloat16 reference in the program's
  place come out not correct;
* ``faults``: each fault of ``bench/faults.py`` planted under the timed
  path makes a run come out not correct;
* ``nochip``: ``bench.run`` exits 3 with no result on the CPU, and 2 in a
  directory holding only ``BENCHMARK.json`` and ``bench/``.
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]

SEED = 2**31 + 977


def _setup():
    sys.path.insert(0, str(ROOT / "src"))
    from bench import harness
    harness.setup_jax()
    return harness


def _shrink(name):
    path = ROOT / "bench" / "shrink" / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"cell {name!r} has no shrink file {path}")
    return json.loads(path.read_text())


def _run(name, patches=(), control=None, seed=SEED):
    harness = _setup()
    from bench import run as brun
    cell = harness.load_cell(name, _shrink(name))
    dev = harness.device_info(cell.chips, require_tpu=False)
    return brun.measure(cell, seed, 1.0, False, dev, patches=list(patches),
                        control=control)


def _cells():
    data = json.loads((ROOT / "BENCHMARK.json").read_text())
    return [w["name"] for w in data["workloads"]]


def test_work():
    from bench import work
    from bench.models import cnn
    # 256 values at 4 bits: 128 bytes of codes, 2 rows of f32 norms
    assert work.message_bytes(256, 4) == 128 + 8
    assert work.quantize_pack_bytes(256, 4) == 1024 + 136
    assert work.buffer_aggregate_bytes(256, 4, 10) == 1360 + 1024
    assert work.flush_bytes(256, 4, 4, 10) == 1360 + 6 * 1024 + 136
    # one 3x3 conv of 1 -> 2 channels on 8x8, padding 1, pooled to 4x4,
    # 2 classes: 2*8*8*2*9 + 2*4*4*2*2 = 2304 + 128
    cfg = {"image_size": 8, "in_channels": 1, "conv_layers": 1,
           "channels": 2, "kernel": 3, "padding": 1, "classes": 2,
           "local_steps": 2, "batch": 3}
    assert cnn.forward_flops(cfg) == 2432
    cw = cnn.client_work(cfg)
    assert cw["flops"] == 3 * 2432 * 6
    assert cw["data_bytes"] == 6 * (64 + 4)
    # padding 2 grows each side by 2 before the pool: 8 -> 10 -> 5, then
    # 5 -> 7 -> 3; 2*10*10*2*9 + 2*7*7*2*18 + 2*3*3*2*2 = 3600 + 3528 + 72
    cfg.update(padding=2, conv_layers=2)
    assert cnn.forward_flops(cfg) == 7200


def test_wire():
    _setup()
    import jax
    import jax.numpy as jnp
    import numpy as np
    from bench import wire
    from repro.kernels import ops
    n = 5000
    x = jax.random.normal(jax.random.PRNGKey(3), (2, n)) * 0.01
    keys = jax.random.split(jax.random.PRNGKey(4), 2)
    for bits in (2, 4, 8):
        packed, norms = ops.qsgd_quantize_batch(x, keys, bits)
        rows = wire.rows_for(n)
        for i in range(2):
            xp = jnp.concatenate([x[i], jnp.zeros(rows * 128 - n)])
            codes, nr = wire.encode_rows(xp.reshape(rows, 128), keys[i], 0,
                                         bits=bits)
            assert np.array_equal(np.asarray(wire.pack(np.asarray(codes),
                                                       bits)),
                                  np.asarray(packed[i])), bits
            # the norms' sums run in another order: equal to rounding
            np.testing.assert_allclose(np.asarray(nr), np.asarray(norms[i]),
                                       rtol=1e-6)
            back = np.asarray(wire.decode_rows(codes, norms[i], bits=bits))
            ref = np.asarray(ops.qsgd_dequantize(packed[i], norms[i], bits,
                                                 n))
            assert np.array_equal(back.reshape(-1)[:n], ref), bits


def test_trace():
    import numpy as np
    from bench import trace as tr
    path = ROOT / "bench" / "testdata" / "small.xplane.pb"
    expect = json.loads((path.parent / "small.expect.json").read_text())
    t = tr.load(path)
    red = tr.reduce(t, expect["window_s"])
    # brute force: mark every microsecond an op of the first chip covers
    lo, hi = red.lo, red.hi
    mask = np.zeros((hi - lo) // 1000 + 1, bool)
    for iv in t.devices[0].ops:
        a, b = max(iv.start, lo), min(iv.end, hi)
        if a < b:
            mask[(a - lo) // 1000:(b - lo + 999) // 1000] = True
    brute = mask.sum() / 1e6
    assert abs(red.busy_s[0] - brute) <= 2e-6 * len(t.devices[0].ops) + 1e-6
    for key, want in expect["numbers"].items():
        got = {"busy_s": red.busy_s[0],
               "client_program_s": red.module_time(r"^jit_step\b")[0],
               "flush_program_s": red.module_time(
                   r"^jit_server_flush_step")[0],
               "idle_total_s": sum(v for _, v in red.idle_by_host())}[key]
        assert abs(got - want) <= 1e-9 + 1e-9 * abs(want), (key, got, want)
    idle = sum(v for _, v in red.idle_by_host())
    assert abs(idle + red.busy_s[0] - red.window_s) < 1e-6
    # every per-layer reader of the cell the trace came from finds its
    # number in it, and no share of a peak passes 100%
    import types
    from bench import harness, run as brun
    cell = harness.load_cell(expect["cell"])
    t = cell.traffic
    win = types.SimpleNamespace(seconds=expect["window_s"],
                                **expect["window"])
    ctx = brun.Context(cell, win, red, win.uploads / win.seconds,
                       brun._work(cell), brun._peaks("TPU v5 lite"),
                       cell.chips, cell.config["d"], t["buffer_size"],
                       brun._bits(t["client_quantizer"]),
                       brun._bits(t["server_quantizer"]))
    for m in cell.per_layer:
        reader = harness.load_module(ROOT / "bench" / "metrics"
                                     / f"{m['name']}.py", m["name"])
        v = reader.read(ctx)
        assert v is not None and v >= 0, (m["name"], v)
        assert m["unit"] != "%" or v <= 100, (m["name"], v)


def test_reference():
    _setup()
    from bench import test_reference as tr
    for name in dir(tr):
        if name.startswith("test_"):
            getattr(tr, name)()


def test_shrink():
    harness = _setup()
    import jax
    import numpy as np
    for name in _cells():
        cell = harness.load_cell(name, _shrink(name))
        model, c, t = cell.model, cell.config, cell.traffic
        need = harness.MODEL_NAMES + (("init_block",) if model.SEPARABLE
                                      else ())
        missing = [k for k in need if not hasattr(model, k)]
        missing += [k for k in harness.CONFIG_KEYS if k not in c]
        missing += [k for k in harness.TRAFFIC_KEYS if k not in t]
        assert not missing, (name, missing)
        k_w, k_d = jax.random.split(jax.random.PRNGKey(SEED))
        if model.SEPARABLE:
            whole = jax.tree.leaves(model.init_params(k_w, c))
            assert np.array_equal(
                np.concatenate([np.asarray(v).reshape(-1) for v in whole]),
                np.asarray(model.init_block(k_w, 0, c["d"]))), name
        batches = model.provider(model.make_data(k_d, c, t), c, t)
        assert getattr(batches, "batched", False), name
        cids = np.array([3, 5])
        out = batches(cids, jax.random.split(k_d, 2))
        got = np.asarray(out["cid"]).reshape(len(cids), -1)
        assert (got == cids[:, None]).all(), (name, got)


def test_sound():
    for name in _cells():
        out = _run(name)
        assert out["correct"], (name, out["checks"])


def test_control():
    import jax.numpy as jnp
    for name in _cells():
        out = _run(name, control=jnp.bfloat16)
        assert not out["correct"], (name, out["checks"])


def test_faults():
    from bench import faults
    for name in _cells():
        for fault, patch in faults.FAULTS.items():
            out = _run(name, patches=[patch])
            assert not out["correct"], (name, fault, out["checks"])


def test_nochip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    args = [sys.executable, "-m", "bench.run", "--workload",
            _cells()[0], "--seed", "1", "--seconds", "1", "--trace", "0"]
    p = subprocess.run(args, cwd=ROOT, env=env, capture_output=True,
                       text=True, timeout=600)
    assert p.returncode == 3 and not p.stdout.strip(), (p.returncode,
                                                        p.stdout)
    with tempfile.TemporaryDirectory() as d:
        shutil.copy(ROOT / "BENCHMARK.json", d)
        shutil.copytree(ROOT / "bench", Path(d) / "bench",
                        ignore=shutil.ignore_patterns("__pycache__"))
        p = subprocess.run(args, cwd=d, env=env, capture_output=True,
                           text=True, timeout=600)
        assert p.returncode not in (0,) and not p.stdout.strip(), (
            p.returncode, p.stdout)


CASES = {"work": test_work, "wire": test_wire, "trace": test_trace,
         "reference": test_reference, "shrink": test_shrink,
         "sound": test_sound, "control": test_control,
         "faults": test_faults, "nochip": test_nochip}


def main(argv=None) -> int:
    names = (argv if argv is not None else sys.argv[1:]) or list(CASES)
    for name in names:
        CASES[name]()
        print(f"{name}: ok", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
