"""The flat substrate's quadratic client model: the benchmark's inputs and
its plain reference.

A client model is one flat vector ``w`` of d values and its loss is
``mean((w - target)**2)``, as ``chip_smoke.py`` trains it at d=1e8. A
client's target is a row of 128 values tiled over the d coordinates, made
from the seed; its local step reads x-hat once and writes its delta, so
the protocol's wire path (encode, framing, buffer, flush) is the whole of
the work. The loss is separable: coordinate i's delta depends on
coordinate i alone, so the reference can run over blocks of rows.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

SEPARABLE = True
LANES = 128
_W_SALT = 0x5BD1E995


def _uniform(seed, idx):
    """Counter-hash uniforms in [-1, 1) of global indices: the initial
    weights can be made for any block of rows without the rest."""
    from bench.wire import dither
    u = dither(seed ^ jnp.uint32(_W_SALT), seed, idx)
    return 2.0 * u - 1.0


def init_block(key, e0: int, e1: int):
    """The initial weights at global indices [e0, e1)."""
    idx = jnp.arange(e0, e1, dtype=jnp.uint32)
    return _uniform(jnp.asarray(key, jnp.uint32).reshape(-1)[-1], idx)


def init_params(key, cfg):
    d = cfg["d"]
    if d % LANES:
        raise ValueError(f"d={d} must be a multiple of {LANES}")
    return {"w": jax.jit(init_block, static_argnums=(1, 2))(key, 0, d)}


def make_data(key, cfg, traffic):
    """Each client's target row: (clients, 128) standard normals."""
    k = jax.random.fold_in(key, 1)
    return {"target": jax.jit(lambda k: jax.random.normal(
        k, (cfg["clients"], LANES)))(k)}


def provider(data, cfg, traffic):
    """Batched provider: the members' target rows, one per local step, and
    their client ids (``cid``, which the model never reads)."""
    nc, p = cfg["clients"], cfg["local_steps"]

    @jax.jit
    def gather(target, cids):
        t = target[cids % nc]
        b = cids.shape[0]
        return {"target": jnp.broadcast_to(t[:, None], (b, p, LANES)),
                "cid": jnp.broadcast_to(cids[:, None], (b, p))}

    def batches(cids, keys):
        del keys
        return gather(data["target"], jnp.asarray(cids, jnp.int32))

    batches.batched = True
    return batches


def client_batch(data, cfg, cid):
    t = data["target"][cid % cfg["clients"]]
    return {"target": jnp.broadcast_to(t, (cfg["local_steps"], LANES))}


def program_loss(params, batch, key):
    del key
    w = params["w"]
    return jnp.mean((w.reshape(-1, LANES) - batch["target"]) ** 2)


def ref_client_delta(h_block, batch, k_train, unflatten, cfg, qcfg, dtype):
    """Algorithm 2 on one block of whole rows: ``local_steps`` SGD steps of
    the gradient ``2 (w - target) / d``; returns the f32 delta block. The
    loss draws no randomness, so ``k_train`` is unused, and the block needs
    no tree view."""
    del k_train, unflatten
    d, lr = cfg["d"], qcfg["client_lr"]
    y0 = h_block.astype(dtype).reshape(-1, LANES)
    y = y0
    for p in range(cfg["local_steps"]):
        t = batch["target"][p].astype(dtype)
        y = y - lr * (2 * (y - t) / d)
    return (y - y0).astype(jnp.float32).reshape(-1)


def client_work(cfg) -> dict:
    """Per upload: the loss's forward pass is a subtract, a square and a
    sum per value (three FLOPs), counted three times for training; the
    client's data is one 128-value row per step."""
    return {"flops": 3.0 * 3.0 * cfg["d"] * cfg["local_steps"],
            "data_bytes": cfg["local_steps"] * LANES * 4}
