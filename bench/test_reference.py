"""CPU tests of the reference replay (``bench/reference.py``) on a
synthetic record: no engine runs, the wire's codes, norms and recorded
states are drawn from a fixed seed.

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/test_reference.py

``bench.selftest``'s ``reference`` case runs the same tests.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference, wire

LANES = wire.LANES
SBITS = 4


class Coupled:
    """A client model that is not separable: the loss
    ``mean((w - target)**2) + mean(w)**2`` couples every coordinate
    through the mean, so a coordinate's delta depends on all of x-hat."""
    SEPARABLE = False

    @staticmethod
    def client_batch(data, cfg, cid):
        return {"target": data[cid % data.shape[0]]}

    @staticmethod
    def ref_client_delta(h, batch, k_train, unflatten, cfg, qcfg, dtype):
        del k_train, unflatten
        n, lr = h.shape[0], qcfg["client_lr"]
        t = jnp.resize(batch["target"].astype(dtype), (n,))
        y0 = h.astype(dtype)
        y = y0
        for _ in range(cfg["local_steps"]):
            y = y - lr * (2 * (y - t) / n + 2 * jnp.mean(y) / n)
        return (y - y0).astype(jnp.float32)


CFG = {"local_steps": 2}
QCFG = {"client_lr": 50.0, "server_lr": 1.0, "server_momentum": 0.3,
        "buffer_size": 3}


def _message(rng, rows: int, bits: int = SBITS) -> tuple:
    packed = rng.integers(0, 256, (rows, LANES * bits // 8), dtype=np.uint8)
    norms = rng.uniform(0.005, 0.02, rows).astype(np.float32)
    return packed, norms


def _keys(rng) -> np.ndarray:
    return rng.integers(0, 2**32, 2, dtype=np.uint64).astype(np.uint32)


def synthetic(n: int, versions: list, seed: int = 7) -> tuple:
    """A record of two blocks of three flushes: the first block from the
    seed's weights, K uploads a flush at version 0; the steady block, whose
    uploads carry ``versions`` in turn (not sorted), at least K a flush and
    each version at least once, flushed after every broadcast those
    versions need. Returns the record, the seed's weights and the
    clients' targets."""
    rng = np.random.default_rng(seed)
    rows = wire.rows_for(n)
    k = QCFG["buffer_size"]

    def upload(version):
        packed, norms = _message(rng, rows)
        return reference.Upload(
            cid=int(rng.integers(0, 1000)), version=version, bits=SBITS,
            packed=packed, norms=norms, k_train=_keys(rng), k_enc=_keys(rng))

    def state():
        return rng.normal(0, 0.1, n).astype(np.float32)

    steady_first = max(versions) + 1
    rec = reference.Record(n=n, sbits=SBITS)
    rec.broadcasts = [_message(rng, rows) for _ in range(steady_first + 2)]
    first = reference.Block(first=1)
    first.uploads = [[upload(0) for _ in range(k)] for _ in range(3)]
    steady = reference.Block(first=steady_first,
                             start={"x": state(), "m": state(),
                                    "h": state()})
    per = max(k, -(-len(versions) // 3))
    cycle = iter(versions * (3 * per))
    steady.uploads = [[upload(next(cycle)) for _ in range(per)]
                      for _ in range(3)]
    for blk in (first, steady):
        blk.flush_keys = [_keys(rng) for _ in range(3)]
        blk.m1 = state()
        blk.end = {"x": state(), "h": state()}
    rec.blocks = [first, steady]
    x0 = rng.uniform(-1, 1, n).astype(np.float32)
    targets = jnp.asarray(rng.normal(0, 1, (16, LANES)).astype(np.float32))
    return rec, x0, targets


def _replay(rec, x0, targets, block_rows, control=None):
    n = rec.n
    full = jnp.asarray(x0)
    bounds = ((0, 1000), (1000, 1003), (1003, n))
    return reference.replay(
        rec, Coupled, CFG, QCFG, targets, lambda e0, e1: full[e0:e1],
        None, bounds, block_rows, control=control)


def test_running_hat_is_x0_plus_decodes():
    """At each version the running x-hat equals x0 plus the reference's
    decodes of broadcasts 1..v added in order, bit for bit, on the whole
    vector and on a block of rows."""
    n = 37 * LANES - 50
    rec, x0, _ = synthetic(n, [1, 4, 2])
    rows = wire.rows_for(n)
    xp = np.concatenate([x0, np.zeros(rows * LANES - n, np.float32)])
    for r0, r1 in ((0, rows), (8, 16)):
        e0, e1 = r0 * LANES, r1 * LANES
        want = xp[e0:e1].copy()
        hats = reference._hats(rec, jnp.asarray(xp[e0:e1]), r0, r1,
                               {"ref": jnp.float32})
        for v, hat in enumerate(hats):
            if v:
                pk, pn = rec.broadcasts[v - 1]
                dec = wire.decode_rows(
                    reference.unpack_dev(jnp.asarray(pk[r0:r1]), bits=SBITS),
                    jnp.asarray(pn[r0:r1]), bits=SBITS)
                want = want + np.asarray(dec).reshape(-1)
            got = np.asarray(hat["ref"])
            assert np.array_equal(got.view(np.uint32),
                                  want.view(np.uint32)), (r0, v)
        assert v == len(rec.broadcasts)


def test_flush_phase_in_row_blocks_equals_one_block():
    """A non-separable replay whose flush phase runs in several row blocks
    (the last one short) gives the one-block replay's numbers: codes and
    norms exactly, leaf and row gaps to f32 rounding; so does its
    bfloat16 control."""
    n = 37 * LANES - 50
    rec, x0, targets = synthetic(n, [3, 1, 2])
    rows = wire.rows_for(n)
    exact = ("upload_norm_gap", "upload_l2_gap", "upload_code_mismatch",
             "broadcast_norm_gap", "broadcast_code_mismatch")
    for control in (None, jnp.bfloat16):
        one = _replay(rec, x0, targets, rows, control)
        several = _replay(rec, x0, targets, 8, control)
        a, b = one["numbers"], several["numbers"]
        assert set(a) == set(b)
        for name in a:
            if name in exact:
                assert a[name] == b[name], (control, name, a[name], b[name])
            else:
                np.testing.assert_allclose(b[name], a[name], rtol=1e-5,
                                           atol=1e-7, err_msg=name)
        assert one["worst"]["broadcast"] == several["worst"]["broadcast"]
        assert one["staleness"] == several["staleness"]
        assert 0 < a["broadcast_code_mismatch"] < 1


def _peak_live_bytes(rec, x0, targets, block_rows) -> int:
    """The most device bytes alive, summed over ``jax.live_arrays()``
    after each decode, encode and unpack of the replay, above what was
    alive before it."""
    def live():
        return sum(a.nbytes for a in jax.live_arrays())

    base = live()
    peak = [0]

    def sampled(fn):
        @functools.wraps(fn)
        def call(*args, **kw):
            out = fn(*args, **kw)
            peak[0] = max(peak[0], live() - base)
            return out
        return call

    saved = (wire.encode_rows, wire.decode_rows, reference.unpack_dev)
    wire.encode_rows, wire.decode_rows = map(sampled, saved[:2])
    reference.unpack_dev = sampled(saved[2])
    try:
        _replay(rec, x0, targets, block_rows)
    finally:
        wire.encode_rows, wire.decode_rows, reference.unpack_dev = saved
    return peak[0]


def test_live_bytes_do_not_grow_with_versions_in_use():
    """A non-separable replay holds one running x-hat, whether 3 or 30
    versions are in use: its live device bytes stay under a bound of a
    few whole vectors, the same for both."""
    n = 64 * LANES
    vector = 4 * n
    peaks = {}
    for count in (3, 30):
        rec, x0, targets = synthetic(n, list(range(count)))
        used = {up.version for blk in rec.blocks for ups in blk.uploads
                for up in ups}
        assert len(used) == count
        peaks[count] = _peak_live_bytes(rec, x0, targets, 16)
    assert peaks[3] > 0
    assert peaks[30] <= 12 * vector, peaks
    assert peaks[30] <= peaks[3] + vector // 4, peaks
