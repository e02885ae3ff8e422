"""The paper's CelebA CNN: the benchmark's inputs and its plain reference.

The system under test runs ``repro.models.cnn``'s forward pass: four conv
layers of 32 channels (stride 1, padding 2), each followed by GroupNorm (8
groups), ReLU and 2x2 max-pooling, dropout 0.1 on the flattened features,
and a 2-class linear head. The kernel size and the head's width come from
the weights, which this module makes from the seed at the configuration's
sizes (3x3 kernels on LEAF's 84x84 images), together with every client's
data; it hands the program a batched data provider, and holds the same
network written out in plain ``jax.numpy`` as the reference. The reference
runs its convolutions and products at ``Precision.HIGHEST``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

SEPARABLE = False  # a client's delta depends on every coordinate of x-hat
HIGHEST = jax.lax.Precision.HIGHEST


def _layers(cfg):
    """Per conv layer: its index, kernel shape and output side (before the
    pool); the pool halves the side, rounding down."""
    ch, k, cin = cfg["channels"], cfg["kernel"], cfg["in_channels"]
    side = cfg["image_size"]
    for i in range(cfg["conv_layers"]):
        out = side + 2 * cfg["padding"] - k + 1
        yield i, (k, k, cin if i == 0 else ch, ch), out
        side = out // 2


def _head_side(cfg):
    return [out for *_, out in _layers(cfg)][-1] // 2


def init_params(key, cfg):
    """Fan-in truncated-normal conv and head weights, zero biases, unit
    GroupNorm scales: the initialisation of ``repro.models.cnn``, drawn
    from the benchmark's own key."""
    keys = jax.random.split(key, cfg["conv_layers"] + 1)
    params = {}
    for i, shape, _ in _layers(cfg):
        fan_in = shape[0] * shape[1] * shape[2]
        ch = shape[3]
        params[f"conv{i}"] = {
            "w": jax.random.truncated_normal(keys[i], -2.0, 2.0, shape)
            / math.sqrt(fan_in),
            "b": jnp.zeros((ch,)), "gn_scale": jnp.ones((ch,)),
            "gn_bias": jnp.zeros((ch,))}
    side = _head_side(cfg)
    feat = side * side * cfg["channels"]
    params["head"] = {
        "w": jax.random.truncated_normal(keys[-1], -2.0, 2.0,
                                         (feat, cfg["classes"]))
        / math.sqrt(feat),
        "b": jnp.zeros((cfg["classes"],))}
    return params


def _chunk(n: int, most: int = 8192) -> int:
    """The largest divisor of n that is at most ``most``: images are made
    in equal chunks, so the pool is never copied to trim it."""
    return max(c for c in range(1, min(n, most) + 1) if n % c == 0)


def _images(key, labels, s):
    """CelebA-like uint8 RGB images for the given labels: noise at a
    quarter of the side, upsampled and smoothed, with a bright arc in the
    mouth region that curves up for label 1 ("smiling") and down for 0.
    The pattern of ``repro.data.SyntheticCelebA``, made on the device."""
    n = labels.shape[0]
    k_img, k_amp = jax.random.split(key)
    low = jax.random.normal(k_img, (n, s // 4, s // 4, 3))
    base = jnp.repeat(jnp.repeat(low, 4, axis=1), 4, axis=2)
    base = 0.25 * (base + jnp.roll(base, 1, 1) + jnp.roll(base, 1, 2)
                   + jnp.roll(base, -1, 1))
    yy, xx = np.mgrid[0:s, 0:s].astype(np.float32)
    cx, cy, w = s / 2.0, s * 0.72, s / 32.0
    bend = ((xx - cx) / (4.0 * w)) ** 2
    arc_up = np.exp(-((xx - cx) ** 2 / (18.0 * w * w)
                      + (yy - (cy - 2 * w + bend * w)) ** 2 / (2 * w * w)))
    arc_dn = np.exp(-((xx - cx) ** 2 / (18.0 * w * w)
                      + (yy - (cy + 2 * w - bend * w)) ** 2 / (2 * w * w)))
    amp = jax.random.uniform(k_amp, (n, 1, 1), minval=0.8, maxval=1.6)
    pattern = amp * jnp.where(labels[:, None, None] == 1, arc_up, arc_dn)
    base = base.at[..., 0].add(pattern).at[..., 1].add(0.5 * pattern)
    return jnp.clip(jnp.round(127.5 + 60.0 * base), 0, 255).astype(jnp.uint8)


def make_data(key, cfg, traffic):
    """The image pool and every client's local batches, on the device.

    LEAF's CelebA split: ``pool_images`` images over ``clients`` clients,
    client c owning a contiguous share of about pool/clients images. Each
    client draws a label share from Dirichlet(alpha) over the two classes
    (non-IID, as a celebrity's photos are), and its images take labels
    from it. An upload's ``local_steps`` x ``batch`` images are drawn from
    the client's own share. Returns ``images`` (pool, s, s, 3) uint8,
    ``labels`` (pool,) int32 and ``index`` (clients, steps, batch) int32
    into the pool."""
    n, s = cfg["pool_images"], cfg["image_size"]
    nc, p, b = cfg["clients"], cfg["local_steps"], cfg["batch"]
    chunk = _chunk(n)

    @jax.jit
    def make(key):
        k_share, k_lab, k_img, k_pick = jax.random.split(key, 4)
        share = jax.random.beta(k_share, cfg["dirichlet_alpha"],
                                cfg["dirichlet_alpha"], (nc,))
        owner = (jnp.arange(n, dtype=jnp.int32) * nc) // n
        labels = jax.random.bernoulli(k_lab, share[owner]).astype(jnp.int32)
        keys = jax.random.split(k_img, n // chunk)
        images = jax.lax.map(lambda a: _images(a[0], a[1], s),
                             (keys, labels.reshape(-1, chunk)))
        first = (jnp.arange(nc, dtype=jnp.int32) * n + nc - 1) // nc
        count = (jnp.arange(1, nc + 1, dtype=jnp.int32) * n + nc - 1) // nc \
            - first
        u = jax.random.uniform(k_pick, (nc, p, b))
        index = first[:, None, None] + jnp.floor(
            u * count[:, None, None]).astype(jnp.int32)
        return {"images": images.reshape(n, s, s, 3), "labels": labels,
                "index": index}

    return make(key)


def _pixels(images):
    """uint8 pixels to the network's inputs: mean 0.5, std 0.5 scaling."""
    return images.astype(jnp.float32) / 127.5 - 1.0


def provider(data, cfg, traffic):
    """The engine's batched data provider: one call per admitted cohort,
    a device gather of the members' batches. ``cid`` rides along so the
    benchmark can tell members apart; the model never reads it."""
    nc, p = cfg["clients"], cfg["local_steps"]

    @jax.jit
    def gather(images, labels, index, cids):
        idx = index[cids % nc]
        return {"images": _pixels(images[idx]), "labels": labels[idx],
                "cid": jnp.broadcast_to(cids[:, None], (cids.shape[0], p))}

    def batches(cids, keys):
        del keys
        return gather(data["images"], data["labels"], data["index"],
                      jnp.asarray(cids, jnp.int32))

    batches.batched = True
    return batches


def client_batch(data, cfg, cid):
    """One client's batches, for the reference."""
    idx = data["index"][cid % cfg["clients"]]
    return {"images": _pixels(data["images"][idx]),
            "labels": data["labels"][idx]}


def program_loss(params, batch, key):
    """The system under test's model: ``repro.models.cnn`` in training
    mode (dropout on)."""
    from repro.models.cnn import cnn_loss
    return cnn_loss(params, batch, train=True, key=key)[0]


def _group_norm(h, scale, bias, groups, eps=1e-5):
    b, hh, ww, c = h.shape
    g = h.reshape(b, hh, ww, groups, c // groups)
    mean = g.mean(axis=(1, 2, 4), keepdims=True)
    var = ((g - mean) ** 2).mean(axis=(1, 2, 4), keepdims=True)
    g = (g - mean) / jnp.sqrt(var + eps)
    return g.reshape(b, hh, ww, c) * scale + bias


def ref_loss(params, batch, key, cfg, dtype):
    """Plain forward pass and mean cross-entropy, in ``dtype``."""
    pad = cfg["padding"]
    h = batch["images"].astype(dtype)
    for i in range(cfg["conv_layers"]):
        p = params[f"conv{i}"]
        h = jax.lax.conv_general_dilated(
            h, p["w"], (1, 1), [(pad, pad), (pad, pad)],
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST)
        h = _group_norm(h + p["b"], p["gn_scale"], p["gn_bias"],
                        cfg["groups"])
        h = jnp.maximum(h, 0)
        b, hh, ww, c = h.shape
        h = h[:, :hh // 2 * 2, :ww // 2 * 2]
        h = h.reshape(b, hh // 2, 2, ww // 2, 2, c).max(axis=(2, 4))
    h = h.reshape(h.shape[0], -1)
    rate = cfg["dropout"]
    keep = jax.random.bernoulli(key, 1.0 - rate, h.shape)
    h = jnp.where(keep, h / (1.0 - rate), 0)
    logits = jnp.dot(h, params["head"]["w"], precision=HIGHEST)
    logits = logits + params["head"]["b"]
    logp = logits - jax.nn.logsumexp(logits, axis=1, keepdims=True)
    nll = -jnp.take_along_axis(logp, batch["labels"][:, None], axis=1)
    return nll.mean()


def ref_client_delta(h_flat, batch, k_train, unflatten, cfg, qcfg, dtype):
    """Algorithm 2 in plain form: ``local_steps`` SGD steps from x-hat on
    the client's batches, dropout keyed by the split of ``k_train``;
    returns the flat f32 delta y_P - y_0."""
    y0 = jax.tree.map(lambda v: v.astype(dtype), unflatten(h_flat))
    keys = jax.random.split(k_train, cfg["local_steps"])
    grad = jax.grad(ref_loss)
    y = y0
    for p in range(cfg["local_steps"]):
        b = {"images": batch["images"][p], "labels": batch["labels"][p]}
        g = grad(y, b, keys[p], cfg, dtype)
        y = jax.tree.map(lambda yi, gi: yi - qcfg["client_lr"] * gi, y, g)
    delta = jax.tree.map(lambda a, b: (a - b).astype(jnp.float32), y, y0)
    return jnp.concatenate([v.reshape(-1) for v in jax.tree.leaves(delta)])


def forward_flops(cfg) -> float:
    """Multiply-adds of one image's forward pass, times two: the convs at
    each layer's output resolution and the head. Normalisation,
    activations and pooling are elementwise and not counted."""
    flops = 0.0
    for _, (kh, kw, cin, cout), out in _layers(cfg):
        flops += 2.0 * out * out * cout * kh * kw * cin
    side = _head_side(cfg)
    flops += 2.0 * side * side * cfg["channels"] * cfg["classes"]
    return flops


def client_work(cfg) -> dict:
    """Per upload: training FLOPs at three forward passes per image and
    step, and the bytes of the client's data read once: its uint8 pixels
    and int32 labels."""
    images = cfg["local_steps"] * cfg["batch"]
    s = cfg["image_size"]
    return {"flops": 3.0 * forward_flops(cfg) * images,
            "data_bytes": images * (s * s * cfg["in_channels"] + 4)}
