"""The Pallas kernels of the wire path compile under Mosaic for a TPU v5e.

Each case lowers one kernel entry with ``interpret=False`` for one chip of
a described ``v5e:2x2`` topology (no chip attached: the TPU compiler runs
ahead of time) at the row count of a d=1e8 message, and checks that the
kernel reached the compiled module as a ``tpu_custom_call``. This catches
what interpret mode cannot: casts, reshapes and reductions Mosaic refuses,
and blocks that overflow scoped VMEM (the bits=8 unpack/aggregate cases).

Two more cases compile over all four chips of the topology: a payload
spread over a mesh decodes under ``shard_map`` with the kernel on every
chip, and a graph GSPMD partitions decodes with the block math, since
Mosaic refuses a kernel there.

The topology is described inside a fixture, never at import, so every test
worker collects the same cases and only the one that runs this file loads
the TPU compiler.
"""
import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import buffer_agg as _agg
from repro.kernels import ops
from repro.kernels import qsgd as _qsgd

D = 100_000_000
ROWS = ops.rows_for(D)  # 781,250 wire rows
TILE_ROWS = ops.tile_rows_for(D)  # padded to a BLOCK_ROWS multiple
K = 10  # the paper's buffer size


@contextlib.contextmanager
def _no_persistent_cache():
    """Ahead-of-time TPU executables cannot be read back without a chip, so
    keep them out of the persistent compilation cache."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


@pytest.fixture(scope="module")
def v5e_2x2():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp, _no_persistent_cache():
        mp.setenv("TPU_LOG_DIR", "disabled")  # else libtpu logs under /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler here: nothing to check
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield topo


def _four_chip_mesh(topo):
    from jax.sharding import AxisType, Mesh
    return Mesh(np.array(topo.devices[:4]), ("data",),
                axis_types=(AxisType.Auto,))


def _entry(name, bits):
    """(kernel call, argument shapes) for one Pallas entry at d=1e8."""
    in_lanes = _qsgd.LANES * bits // 8
    f32, u8 = jnp.float32, jnp.uint8
    if name == "quantize_pack":
        return (lambda x, u: _qsgd.qsgd_quantize_pack(
            x, u, bits, interpret=False),
            [((TILE_ROWS, _qsgd.LANES), f32)] * 2)
    if name == "quantize_pack_batch":
        return (lambda x, s: _qsgd.qsgd_quantize_pack_batch(
            x, s, bits, interpret=False),
            [((1, ROWS, _qsgd.LANES), f32), ((1, 2), jnp.uint32)])
    if name == "unpack_dequantize":
        return (lambda p, n: _qsgd.qsgd_unpack_dequantize(
            p, n, bits, interpret=False),
            [((ROWS, in_lanes), u8), ((ROWS,), f32)])
    assert name == "buffer_aggregate", name
    return (lambda p, n, w: _agg.buffer_aggregate(
        p, n, w, bits, interpret=False),
        [((K, ROWS, in_lanes), u8), ((K, ROWS), f32), ((K,), f32)])


@pytest.mark.parametrize("bits", [2, 4, 8])
@pytest.mark.parametrize("name", ["quantize_pack", "quantize_pack_batch",
                                  "unpack_dequantize", "buffer_aggregate"])
def test_kernel_compiles_for_v5e(v5e_2x2, name, bits):
    from jax.sharding import SingleDeviceSharding
    one_chip = SingleDeviceSharding(v5e_2x2.devices[0])
    fn, shapes = _entry(name, bits)
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    with _no_persistent_cache():
        compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_mesh_decode_compiles_for_four_chips(v5e_2x2, monkeypatch):
    """The replicas' decode of a d=1e8 broadcast spread over four chips:
    row-local under shard_map, the Pallas kernel on every chip."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P
    mesh = _four_chip_mesh(v5e_2x2)
    rep = NamedSharding(mesh, P())
    args = (jax.ShapeDtypeStruct((ROWS, 64), jnp.uint8, sharding=rep),
            jax.ShapeDtypeStruct((ROWS,), jnp.float32, sharding=rep))
    monkeypatch.setattr(ops, "_interpret", lambda: False)  # the TPU route
    with _no_persistent_cache():
        compiled = ops.qsgd_dequantize_on_mesh.lower(
            *args, bits=4, n=D, mesh=mesh).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_gspmd_decode_compiles_for_four_chips(v5e_2x2):
    """Inside one jit over four chips (the distributed round's shape)
    Mosaic refuses the kernel, and the block-math decode compiles."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.core.quantizers import qsgd_decode_flat
    mesh = _four_chip_mesh(v5e_2x2)
    rows = ROWS - ROWS % 4
    spread = NamedSharding(mesh, P("data"))
    args = (jax.ShapeDtypeStruct((rows, 64), jnp.uint8, sharding=spread),
            jax.ShapeDtypeStruct((rows,), jnp.float32, sharding=spread))
    with _no_persistent_cache():
        with pytest.raises(NotImplementedError, match="Mosaic"):
            jax.jit(lambda p, n: _qsgd.qsgd_unpack_dequantize(
                p, n, 4, interpret=False)).lower(*args)
        compiled = jax.jit(lambda p, n: qsgd_decode_flat(
            p, n, 4, rows * _qsgd.LANES)).lower(*args).compile()
    assert "tpu_custom_call" not in compiled.as_text()
