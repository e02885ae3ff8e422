"""Sharded flat substrate: seeded bit-exactness of mesh runs vs single-device.

Two layers of coverage:

* In-process tests build a ``make_sim_mesh()`` over every VISIBLE device —
  1 on a plain CPU run (the sharded code path still executes, as a
  one-segment shard_map over a padded state) and 8 under the CI job's
  ``XLA_FLAGS=--xla_force_host_platform_device_count=8``. Every assertion
  is exact equality with the meshless path, so the same tests pin genuine
  multi-device bit-exactness when devices are available.
* One subprocess test forces 8 virtual devices regardless of the parent's
  platform and drives the full stack — cohort step (including a cohort
  that doesn't divide the device count and a d whose bucket rows don't
  divide it either), the sharded flush across windows, an end-to-end
  cohort-engine sim, and a cross-device-count checkpoint round-trip.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import QAFeL, QAFeLConfig
from repro.core.quantizers import flatten_tree
from repro.kernels import ops as kops
from repro.launch.mesh import make_sim_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# d = 307 -> 3 bucket rows: doesn't divide any ndev > 1 (padding edge baked
# into every test); b = 5 below doesn't divide 8 either.
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


def test_sharded_cohort_step_bit_identical():
    """Member-sharded cohort train+encode == single-device dispatch, for a
    cohort that divides the device count and one that doesn't."""
    qcfg = make_qcfg()
    mesh = make_sim_mesh()
    flat0, layout = flatten_tree(PARAMS0)
    for b in (4, 5):
        keys = jax.random.split(jax.random.PRNGKey(4), 2 * b)
        tk, ek = keys[:b], keys[b:]
        batches = {"target": jax.random.normal(jax.random.PRNGKey(3),
                                               (b, qcfg.local_steps, D))}
        single = kops.cohort_train_encode_step(
            quad_loss, qcfg, qcfg.cq().spec, layout, flat0, batches, tk, ek,
            jnp.asarray(True), b=b)
        sharded = kops.cohort_train_encode_step(
            quad_loss, qcfg, qcfg.cq().spec, layout, flat0, batches, tk, ek,
            jnp.asarray(True), b=b, mesh=mesh)
        assert_equal(single["packed"], sharded["packed"], f"packed b={b}")
        assert_equal(single["norms"], sharded["norms"], f"norms b={b}")


def drive_pair(single, sharded, n_uploads, seed=0):
    """Feed both servers the identical seeded upload stream; assert every
    broadcast's wire bits match; return the pair."""
    key = jax.random.PRNGKey(seed)
    for _ in range(n_uploads):
        key, k1, k2, k3 = jax.random.split(key, 4)
        batches = {"target": jnp.broadcast_to(
            jax.random.normal(k1, (D,)) + 3.0, (2, D))}
        ma, _ = single.run_client(batches, k2)
        mb, _ = sharded.run_client(batches, k2)
        ra, rb = single.receive(ma, k3), sharded.receive(mb, k3)
        assert (ra is None) == (rb is None)
        if ra is not None:
            pa, pb = ra.payload, rb.payload
            assert ra.wire_bytes == rb.wire_bytes
            if pa["kind"] == "qsgd":
                assert_equal(pa["packed"], pb["packed"])
                assert_equal(pa["norms"], pb["norms"])
            elif pa["kind"] == "identity":
                assert_equal(pa["payload"], pb["payload"])
            else:  # top_k / rand_k sparse pairs
                assert_equal(pa["idx"], pb["idx"])
                assert_equal(pa["vals"], pb["vals"])
    return single, sharded


def assert_states_match(single, sharded):
    n = single.state.layout.total_size
    for name in ("x_flat", "hidden_flat", "momentum_flat"):
        a = np.asarray(getattr(single.state, name))
        b = np.asarray(getattr(sharded.state, name))
        np.testing.assert_array_equal(a, b[:n], err_msg=name)
        assert np.all(b[n:] == 0), f"{name}: non-zero segment padding"
    assert single.state.t == sharded.state.t
    assert single.meter.summary() == sharded.meter.summary()


def test_sharded_flush_bit_identical():
    """x / x-hat / momentum and every broadcast's wire bits are identical to
    the single-device server across several flush windows; the mesh state
    really is NamedSharding-placed and segment-aligned."""
    from jax.sharding import NamedSharding

    mesh = make_sim_mesh()
    qcfg = make_qcfg()
    single = QAFeL(qcfg, quad_loss, PARAMS0)
    sharded = QAFeL(qcfg, quad_loss, PARAMS0, mesh=mesh)
    ndev = jax.device_count()
    assert isinstance(sharded.state.x_flat.sharding, NamedSharding)
    assert sharded.state.x_flat.shape[0] % (ndev * kops.BUCKET) == 0
    drive_pair(single, sharded, 9)
    assert single.state.t >= 3
    assert_states_match(single, sharded)


def test_sharded_flush_identity_and_no_momentum_branches():
    """FedBuff identity uploads (flat-accumulator window, identity
    broadcast) and the no-momentum branch stay bit-identical too."""
    mesh = make_sim_mesh()
    qcfg = make_qcfg(client_quantizer="identity", server_quantizer="identity",
                     server_momentum=0.0)
    single = QAFeL(qcfg, quad_loss, PARAMS0)
    sharded = QAFeL(qcfg, quad_loss, PARAMS0, mesh=mesh)
    drive_pair(single, sharded, 7, seed=2)
    assert_states_match(single, sharded)


def test_sharded_sparse_server_quantizer_branch():
    """top_k server broadcasts (the non-fused flat chain) under a mesh:
    sliced to true-n, re-placed as segments, bit-identical."""
    mesh = make_sim_mesh()
    qcfg = make_qcfg(server_quantizer="top_k0.2")
    single = QAFeL(qcfg, quad_loss, PARAMS0)
    sharded = QAFeL(qcfg, quad_loss, PARAMS0, mesh=mesh)
    drive_pair(single, sharded, 6, seed=3)
    assert_states_match(single, sharded)


def test_sharded_full_sim_bit_identical():
    """End-to-end cohort-engine sim on the mesh == the single-device sim:
    same accuracy trace, meters, staleness summary, replicas in sync."""
    from repro.sim import CohortAsyncFLSimulator, SimConfig

    def run(mesh):
        qcfg = make_qcfg(buffer_size=3, local_steps=1)
        algo = QAFeL(qcfg, quad_loss,
                     {"w": jnp.zeros((256,), jnp.float32)}, mesh=mesh)

        def client_batches(cid, key):
            return {"target": jax.random.normal(key, (1, 256)) + 1.0}

        def eval_fn(params):
            return float(-jnp.mean((params["w"] - 1.0) ** 2))

        sim = CohortAsyncFLSimulator(
            algo, SimConfig(concurrency=4, max_uploads=14, eval_every_steps=2,
                            track_hidden_replicas=2, seed=5),
            client_batches, eval_fn, scenario="identity", cohort_size=3)
        return sim.run()

    res_single = run(None)
    res_sharded = run(make_sim_mesh())
    assert res_single.accuracy_trace == res_sharded.accuracy_trace
    assert res_single.final_accuracy == res_sharded.final_accuracy
    assert res_single.sim_time == res_sharded.sim_time
    assert res_single.metrics == res_sharded.metrics
    assert res_sharded.metrics["replicas_in_sync"]


def test_checkpoint_reshards_across_device_counts(tmp_path):
    """A single-device checkpoint loads into a sharded run (and back) and
    both continue bit-identically — the canonical-array interop contract."""
    mesh = make_sim_mesh()
    path1 = str(tmp_path / "single.npz")
    path2 = str(tmp_path / "sharded.npz")
    single = QAFeL(make_qcfg(), quad_loss, PARAMS0)
    sharded = QAFeL(make_qcfg(), quad_loss, PARAMS0, mesh=mesh)
    drive_pair(single, sharded, 7)  # mid-window occupancy (7 % 3 == 1)
    assert single.buffer.count == 1
    single.save_checkpoint(path1)
    sharded.save_checkpoint(path2)

    # cross-load: single-device archive -> sharded run, and vice versa
    into_sharded = QAFeL(make_qcfg(), quad_loss, PARAMS0,
                         mesh=mesh).load_checkpoint(path1)
    into_single = QAFeL(make_qcfg(), quad_loss, PARAMS0).load_checkpoint(path2)
    assert into_sharded.buffer.count == into_single.buffer.count == 1
    n = single.state.layout.total_size
    assert into_sharded.state.x_flat.shape == sharded.state.x_flat.shape
    assert into_single.state.x_flat.shape[0] == n
    drive_pair(into_single, into_sharded, 8, seed=9)
    assert_states_match(into_single, into_sharded)


def test_target_accuracy_zero_fires():
    """Satellite regression: target_accuracy=0.0 must stop the run on the
    first eval at/above zero (the old truthy check never fired)."""
    from repro.sim import AsyncFLSimulator, SimConfig

    algo = QAFeL(make_qcfg(buffer_size=2, local_steps=1), quad_loss,
                 {"w": jnp.zeros((64,), jnp.float32)})

    def client_batches(cid, key):
        return {"target": jax.random.normal(key, (1, 64))}

    sim = AsyncFLSimulator(
        algo, SimConfig(concurrency=2, max_uploads=50, eval_every_steps=1,
                        target_accuracy=0.0, track_hidden_replicas=0, seed=0),
        client_batches, eval_fn=lambda params: 0.0)
    res = sim.run()
    assert res.reached_target
    assert res.uploads < 50  # stopped early, not by the upload budget


def test_mesh_decode_matches_single_device():
    """A payload spread over the mesh (a sharded flush's broadcast, as the
    replicas receive it) decodes row-locally under shard_map to the same
    bits as the one-device decode, for row counts that don't divide the
    device count; the result stays spread over the mesh."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    mesh = make_sim_mesh()
    for n, bits in ((307, 4), (1000, 2), (128, 8)):
        flat = jax.random.normal(jax.random.PRNGKey(n), (n,)) * 3.0
        packed, norms = kops.qsgd_quantize(flat, jax.random.PRNGKey(1), bits)
        rows = packed.shape[0]
        if rows % jax.device_count() == 0:
            packed = jax.device_put(packed, NamedSharding(mesh, P("data")))
            norms = jax.device_put(norms, NamedSharding(mesh, P("data")))
        got = kops.qsgd_dequantize_on_mesh(packed, norms, bits, n, mesh)
        assert_equal(got, kops.qsgd_dequantize(np.asarray(packed),
                                               np.asarray(norms), bits, n),
                     f"n={n} bits={bits}")
        assert got.sharding.device_set == set(mesh.devices.flat)


def test_eight_virtual_devices_end_to_end():
    """Force 8 host-platform devices in a subprocess and re-run the whole
    equivalence battery there: cohort step (b=5 vs ndev=8, rows=3 vs
    ndev=8 — both padding edges), flush windows, an end-to-end sim, and a
    sharded-save -> single-device-load checkpoint continuation."""
    code = textwrap.dedent("""
        import os, tempfile
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        import tests.test_sharded as T
        from repro.core import QAFeL
        from repro.launch.mesh import make_sim_mesh
        assert jax.device_count() == 8

        T.test_sharded_cohort_step_bit_identical()
        T.test_sharded_flush_bit_identical()
        T.test_sharded_full_sim_bit_identical()
        T.test_mesh_decode_matches_single_device()

        # sharded(8) save -> single-device load, continue in lockstep
        # (unique temp path: concurrent suite runs must not race on it)
        ckpt = os.path.join(tempfile.mkdtemp(), "sharded8.npz")
        mesh = make_sim_mesh(8)
        single = QAFeL(T.make_qcfg(), T.quad_loss, T.PARAMS0)
        sharded = QAFeL(T.make_qcfg(), T.quad_loss, T.PARAMS0, mesh=mesh)
        T.drive_pair(single, sharded, 7)
        sharded.save_checkpoint(ckpt)
        resumed = QAFeL(T.make_qcfg(), T.quad_loss,
                        T.PARAMS0).load_checkpoint(ckpt)
        T.drive_pair(resumed, sharded, 8, seed=9)
        T.assert_states_match(resumed, sharded)
        print("SHARDED_8DEV_OK")
    """)
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=560,
        env={**os.environ,
             "PYTHONPATH": os.path.join(REPO, "src") + os.pathsep + REPO},
        cwd=REPO)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-4000:]
    assert "SHARDED_8DEV_OK" in out.stdout
