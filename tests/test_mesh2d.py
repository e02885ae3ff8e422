"""LLM-scale flat substrate: 2-D ("data","model") mesh + chunked streaming
quantize-encode — the bit-exactness battery.

Everything here is EXACT equality against the single-device fused paths.
The chunked/streamed/2-D modes are dispatch *shapes*, never protocol state:
the qsgd dither keys on the global element index (counter-hash) or rebuilds
exact chunks of the whole-message threefry uniform field, so any tiling of
the encode — ``chunk_rows`` scan chunks, model-axis row segments, host-
streamed uplink chunks — emits the same wire bits as one whole-message
encode.

Layers:

* quantizer-level: ``qsgd_quantize_chunk`` / ``qsgd_encode_flat2d``
  chunkings reassemble to the whole-message entries (threefry b=1 AND
  counter-hash b>1, chunk sizes that don't divide the row count),
* cohort-step-level: ``member_chunk`` x ``chunk_rows`` x 2-D mesh all
  bit-identical to the monolithic single-device step,
* protocol-level: the host-streamed uplink (``run_client_stream`` +
  per-chunk ``receive``) matches the fused upload message-for-message,
  byte-for-byte, and the servers stay in lockstep across flush windows,
* engine-level: the batched batch-provider protocol (one stacked call per
  cohort instead of b host calls) changes nothing downstream,
* an 8-virtual-device subprocess re-runs the battery on real (2,4) and
  (8,1) meshes (d=307 -> 3 bucket rows and b=5 members: neither divides
  any axis — both padding edges exercised).
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import QAFeL, QAFeLConfig
from repro.core.quantizers import (flatten_tree, qsgd_encode_flat2d,
                                   qsgd_encode_rows)
from repro.kernels import ops as kops
from repro.launch.mesh import make_sim_mesh2d

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# d = 307 -> 3 bucket rows (divides no ndev_model > 1); b = 5 divides no
# data extent > 1 either: every test runs on both padding edges.
PARAMS0 = {"w": jnp.zeros((300,), jnp.float32),
           "b": jnp.ones((7,), jnp.float32)}
D = 300


def quad_loss(params, batch, key):
    del key
    return jnp.sum((params["w"] - batch["target"]) ** 2)


def make_qcfg(**kw):
    base = dict(client_lr=0.1, server_lr=1.2, server_momentum=0.3,
                buffer_size=3, local_steps=2, client_quantizer="qsgd4",
                server_quantizer="qsgd4")
    base.update(kw)
    return QAFeLConfig(**base)


def assert_equal(a, b, msg=""):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=msg)


# -- quantizer level ------------------------------------------------------

def test_quantize_chunk_threefry_reassembles_whole_message():
    """Host-streamed chunks (threefry dither = exact chunks of the full
    uniform field) concatenate to ``qsgd_quantize``'s message, for chunk
    sizes that do and don't divide the row count."""
    key = jax.random.PRNGKey(7)
    for n in (307, 1024, 1000):
        flat = jax.random.normal(jax.random.PRNGKey(1), (n,))
        rows = kops.rows_for(n)
        ref_p, ref_n = kops.qsgd_quantize(flat, key, 4)
        pad = rows * kops.BUCKET - n
        padded = jnp.concatenate([flat, jnp.zeros((pad,))]) if pad else flat
        for c in (1, 2, 3, rows):
            ps, ns = [], []
            nch = -(-rows // c)
            rpad = nch * c * kops.BUCKET - rows * kops.BUCKET
            full = jnp.concatenate([padded, jnp.zeros((rpad,))]) \
                if rpad else padded
            for i in range(nch):
                p_c, n_c = kops.qsgd_quantize_chunk(
                    full[i * c * kops.BUCKET:(i + 1) * c * kops.BUCKET],
                    key, i * c, bits=4)
                rc = min(c, rows - i * c)
                ps.append(np.asarray(p_c[:rc]))
                ns.append(np.asarray(n_c[:rc]))
            assert_equal(np.concatenate(ps), ref_p, f"packed n={n} c={c}")
            assert_equal(np.concatenate(ns), ref_n, f"norms n={n} c={c}")


def test_quantize_chunk_counter_hash_matches_batched():
    """threefry=False chunks == ``qsgd_quantize_batch``'s counter-hash rows
    (the 2-D sharded encode's convention): global-row-index keying makes the
    chunk offset, not the chunk size, the only thing that matters."""
    n = 307
    rows = kops.rows_for(n)
    key = jax.random.PRNGKey(3)
    flat = jax.random.normal(jax.random.PRNGKey(2), (n,))
    ref_p, ref_n = kops.qsgd_quantize_batch(flat[None], key[None], 4)
    pad = rows * kops.BUCKET - n
    padded = jnp.concatenate([flat, jnp.zeros((pad,))])
    for c in (1, 2):
        nch = -(-rows // c)
        rpad = (nch * c - rows) * kops.BUCKET
        full = jnp.concatenate([padded, jnp.zeros((rpad,))]) if rpad \
            else padded
        ps = [kops.qsgd_quantize_chunk(
            full[i * c * kops.BUCKET:(i + 1) * c * kops.BUCKET], key, i * c,
            bits=4, threefry=False) for i in range(nch)]
        packed = np.concatenate([np.asarray(p) for p, _ in ps])[:rows]
        norms = np.concatenate([np.asarray(nn) for _, nn in ps])[:rows]
        assert_equal(packed, ref_p[0], f"packed c={c}")
        assert_equal(norms, ref_n[0], f"norms c={c}")


def test_encode_flat2d_chunk_rows_bit_invisible():
    """``qsgd_encode_flat2d(chunk_rows=...)`` == unchunked, for the threefry
    (b=1) and counter-hash (b>1) conventions and chunk sizes that don't
    divide the row count."""
    for b, threefry in ((1, True), (1, False), (4, False)):
        flat2d = jax.random.normal(jax.random.PRNGKey(5), (b, 307))
        keys = (jax.random.PRNGKey(6) if threefry
                else jax.random.split(jax.random.PRNGKey(6), b))
        ref_p, ref_n = qsgd_encode_flat2d(flat2d, keys, 4, threefry=threefry)
        for c in (1, 2, 5):
            p, nn = qsgd_encode_flat2d(flat2d, keys, 4, threefry=threefry,
                                       chunk_rows=c)
            assert_equal(p, ref_p, f"packed b={b} threefry={threefry} c={c}")
            assert_equal(nn, ref_n, f"norms b={b} threefry={threefry} c={c}")


def test_encode_rows_row_offset_is_global():
    """``qsgd_encode_rows`` at row_off k == rows [k:] of the encode at
    row_off 0 over a longer block — the global-element-index dither law that
    makes model-axis segments and streamed chunks the same computation."""
    b, rows = 2, 6
    x3d = jax.random.normal(jax.random.PRNGKey(8), (b, rows, kops.BUCKET))
    seeds = jnp.arange(2 * b, dtype=jnp.uint32).reshape(b, 2)
    ref_p, ref_n = qsgd_encode_rows(x3d, seeds, 4, 0)
    off_p, off_n = qsgd_encode_rows(x3d[:, 2:], seeds, 4, 2)
    assert_equal(off_p, ref_p[:, 2:])
    assert_equal(off_n, ref_n[:, 2:])


# -- cohort-step level ----------------------------------------------------

def test_cohort_step_chunked_modes_bit_identical():
    """member_chunk x chunk_rows x 2-D mesh: every chunked/sharded dispatch
    shape of the fused cohort step emits the monolithic step's exact bits."""
    qcfg = make_qcfg()
    flat0, layout = flatten_tree(PARAMS0)
    b = 5
    keys = jax.random.split(jax.random.PRNGKey(4), 2 * b)
    tk, ek = keys[:b], keys[b:]
    batches = {"target": jax.random.normal(jax.random.PRNGKey(3),
                                           (b, qcfg.local_steps, D))}
    ref = kops.cohort_train_encode_step(
        quad_loss, qcfg, qcfg.cq().spec, layout, flat0, batches, tk, ek,
        jnp.asarray(True), b=b)
    variants = [dict(member_chunk=2), dict(chunk_rows=2),
                dict(member_chunk=1, chunk_rows=1),
                dict(mesh=make_sim_mesh2d((1, 1)), chunk_rows=2),
                dict(mesh=make_sim_mesh2d((1, 1)), member_chunk=3,
                     chunk_rows=1)]
    for kw in variants:
        out = kops.cohort_train_encode_step(
            quad_loss, qcfg, qcfg.cq().spec, layout, flat0, batches, tk, ek,
            jnp.asarray(True), b=b, **kw)
        label = str({k: v for k, v in kw.items() if k != "mesh"})
        assert_equal(out["packed"], ref["packed"], f"packed {label}")
        assert_equal(out["norms"], ref["norms"], f"norms {label}")


# -- protocol level -------------------------------------------------------

def drive_pair(single, other, n_uploads, seed=0):
    """Identical seeded upload stream into both servers; every broadcast's
    wire bits must match."""
    key = jax.random.PRNGKey(seed)
    for _ in range(n_uploads):
        key, k1, k2, k3 = jax.random.split(key, 4)
        batches = {"target": jnp.broadcast_to(
            jax.random.normal(k1, (D,)) + 3.0, (2, D))}
        ma, _ = single.run_client(batches, k2)
        mb, _ = other.run_client(batches, k2)
        ra, rb = single.receive(ma, k3), other.receive(mb, k3)
        assert (ra is None) == (rb is None)
        if ra is not None:
            assert ra.wire_bytes == rb.wire_bytes
            assert_equal(ra.payload["packed"], rb.payload["packed"])
            assert_equal(ra.payload["norms"], rb.payload["norms"])
    return single, other


def assert_states_match(single, other):
    n = single.state.layout.total_size
    for name in ("x_flat", "hidden_flat", "momentum_flat"):
        a = np.asarray(getattr(single.state, name))
        b = np.asarray(getattr(other.state, name))
        np.testing.assert_array_equal(a[:n], b[:n], err_msg=name)
    assert single.state.t == other.state.t
    assert single.meter.summary() == other.meter.summary()


def test_streamed_upload_matches_fused():
    """``run_client_stream`` + per-chunk ``receive`` == the fused
    ``run_client`` upload: reassembled wire bits, metered bytes, broadcast
    bits and server state all identical across flush windows — with a
    chunk size that doesn't divide the 3-row message."""
    qcfg = make_qcfg()
    fused = QAFeL(qcfg, quad_loss, PARAMS0)
    streamed = QAFeL(qcfg, quad_loss, PARAMS0, chunk_rows=2)
    key = jax.random.PRNGKey(11)
    for u in range(7):
        key, k1, k2, k3 = jax.random.split(key, 4)
        batches = {"target": jnp.broadcast_to(
            jax.random.normal(k1, (D,)) + 3.0, (2, D))}
        ma, _ = fused.run_client(batches, k2)
        msgs, _ = streamed.run_client_stream(batches, k2)
        assert len(msgs) == 2  # ceil(3 rows / 2)
        cat_p = np.concatenate([m.payload["packed"] for m in msgs])
        cat_n = np.concatenate([m.payload["norms"] for m in msgs])
        assert_equal(cat_p, ma.payload["packed"], f"upload {u}")
        assert_equal(cat_n, ma.payload["norms"], f"upload {u}")
        assert sum(m.wire_bytes for m in msgs) == ma.wire_bytes
        ra = fused.receive(ma, k3)
        rbs = [streamed.receive(m, k3) for m in msgs]
        rb = next((r for r in rbs if r is not None), None)
        assert (ra is None) == (rb is None)
        if ra is not None:
            assert ra.wire_bytes == rb.wire_bytes
            assert_equal(ra.payload["packed"], rb.payload["packed"])
            assert_equal(ra.payload["norms"], rb.payload["norms"])
    assert fused.state.t >= 2
    assert_states_match(fused, streamed)


def test_mesh2d_flush_chunked_bit_identical():
    """QAFeL on a (1,1) 2-D mesh with chunked encode+flush stays in lockstep
    with the meshless unchunked server (the sharded path runs as a
    one-segment-per-axis shard_map on 1 device)."""
    single = QAFeL(make_qcfg(), quad_loss, PARAMS0)
    mesh2d = QAFeL(make_qcfg(), quad_loss, PARAMS0,
                   mesh=make_sim_mesh2d((1, 1)), chunk_rows=1)
    drive_pair(single, mesh2d, 9)
    assert single.state.t >= 3
    assert_states_match(single, mesh2d)


# -- engine level ---------------------------------------------------------

def _run_cohort_sim(mesh=None, chunk_rows=None, batched=False):
    from repro.sim import CohortAsyncFLSimulator, SimConfig

    qcfg = make_qcfg(buffer_size=3, local_steps=1)
    algo = QAFeL(qcfg, quad_loss, {"w": jnp.zeros((256,), jnp.float32)},
                 mesh=mesh, chunk_rows=chunk_rows)

    def member(key):
        return jax.random.normal(key, (1, 256)) + 1.0

    if batched:
        def client_batches(cids, keys):
            return {"target": jnp.stack([member(k) for k in keys])}
        client_batches.batched = True
    else:
        def client_batches(cid, key):
            return {"target": member(key)}

    def eval_fn(params):
        return float(-jnp.mean((params["w"] - 1.0) ** 2))

    sim = CohortAsyncFLSimulator(
        algo, SimConfig(concurrency=4, max_uploads=14, eval_every_steps=2,
                        track_hidden_replicas=2, seed=5),
        client_batches, eval_fn, scenario="identity", cohort_size=3)
    return sim.run()


def test_batched_provider_engine_equivalent():
    """The batched batch-provider protocol (one stacked host call per
    cohort) produces the exact run of the per-member provider."""
    a = _run_cohort_sim()
    b = _run_cohort_sim(batched=True)
    assert a.accuracy_trace == b.accuracy_trace
    assert a.metrics == b.metrics
    assert a.sim_time == b.sim_time


def test_mesh2d_chunked_cohort_sim_bit_identical():
    """End-to-end cohort-engine sim on a (1,1) 2-D mesh with chunk_rows=1
    (+ batched provider) == the plain single-device sim."""
    a = _run_cohort_sim()
    b = _run_cohort_sim(mesh=make_sim_mesh2d((1, 1)), chunk_rows=1,
                        batched=True)
    assert a.accuracy_trace == b.accuracy_trace
    assert a.final_accuracy == b.final_accuracy
    assert a.metrics == b.metrics


# -- 8 virtual devices ----------------------------------------------------

def test_eight_virtual_devices_mesh2d():
    """Force 8 host-platform devices in a subprocess and re-run the battery
    on REAL 2-D meshes: (2,4) and (8,1) — b=5 members vs data extents 2/8,
    3 wire rows vs model extents 4/1 (neither divides; both padding edges),
    plus the streamed uplink under a (2,4)-sharded server and a full
    cohort-engine sim on (8,1)."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        import tests.test_mesh2d as T
        from repro.core import QAFeL
        from repro.core.quantizers import flatten_tree
        from repro.kernels import ops as kops
        from repro.launch.mesh import make_sim_mesh2d
        assert jax.device_count() == 8

        qcfg = T.make_qcfg()
        flat0, layout = flatten_tree(T.PARAMS0)
        b = 5
        keys = jax.random.split(jax.random.PRNGKey(4), 2 * b)
        tk, ek = keys[:b], keys[b:]
        batches = {"target": jax.random.normal(jax.random.PRNGKey(3),
                                               (b, qcfg.local_steps, T.D))}
        ref = kops.cohort_train_encode_step(
            T.quad_loss, qcfg, qcfg.cq().spec, layout, flat0, batches,
            tk, ek, jnp.asarray(True), b=b)
        for shape in ((2, 4), (8, 1), (4, 2)):
            for cr in (None, 1, 2):
                out = kops.cohort_train_encode_step(
                    T.quad_loss, qcfg, qcfg.cq().spec, layout, flat0,
                    batches, tk, ek, jnp.asarray(True), b=b,
                    mesh=make_sim_mesh2d(shape), chunk_rows=cr)
                T.assert_equal(out["packed"], ref["packed"],
                               f"packed {shape} cr={cr}")
                T.assert_equal(out["norms"], ref["norms"],
                               f"norms {shape} cr={cr}")

        # flush windows in lockstep on both 2-D layouts
        for shape, cr in (((2, 4), 2), ((8, 1), 1)):
            single = QAFeL(T.make_qcfg(), T.quad_loss, T.PARAMS0)
            sharded = QAFeL(T.make_qcfg(), T.quad_loss, T.PARAMS0,
                            mesh=make_sim_mesh2d(shape), chunk_rows=cr)
            T.drive_pair(single, sharded, 9)
            assert single.state.t >= 3
            T.assert_states_match(single, sharded)

        # streamed uplink INTO a (2,4)-sharded chunked server == fused
        # uplink into the meshless server
        fused = QAFeL(T.make_qcfg(), T.quad_loss, T.PARAMS0)
        streamed = QAFeL(T.make_qcfg(), T.quad_loss, T.PARAMS0,
                         mesh=make_sim_mesh2d((2, 4)), chunk_rows=2)
        key = jax.random.PRNGKey(11)
        for _ in range(7):
            key, k1, k2, k3 = jax.random.split(key, 4)
            bt = {"target": jnp.broadcast_to(
                jax.random.normal(k1, (T.D,)) + 3.0, (2, T.D))}
            ma, _ = fused.run_client(bt, k2)
            msgs, _ = streamed.run_client_stream(bt, k2)
            T.assert_equal(np.concatenate([m.payload["packed"] for m in msgs]),
                           ma.payload["packed"])
            ra = fused.receive(ma, k3)
            rbs = [streamed.receive(m, k3) for m in msgs]
            rb = next((r for r in rbs if r is not None), None)
            assert (ra is None) == (rb is None)
            if ra is not None:
                T.assert_equal(ra.payload["packed"], rb.payload["packed"])
        T.assert_states_match(fused, streamed)

        # end-to-end cohort-engine sim on (8,1) with chunked encode
        a = T._run_cohort_sim()
        c = T._run_cohort_sim(mesh=make_sim_mesh2d((8, 1)), chunk_rows=1,
                              batched=True)
        assert a.accuracy_trace == c.accuracy_trace
        assert a.metrics == c.metrics
        print("MESH2D_8DEV_OK")
    """)
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=560,
        env={**os.environ,
             "PYTHONPATH": os.path.join(REPO, "src") + os.pathsep + REPO},
        cwd=REPO)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-4000:]
    assert "MESH2D_8DEV_OK" in out.stdout
