"""Serve a (QAFeL-trained) model with batched prefill + decode.

Demonstrates the inference side across architecture families, including the
ring-buffer sliding-window cache used by the long_500k serving shape and
Mamba2's constant-size recurrent state.

    PYTHONPATH=src python examples/serve_model.py --arch mamba2-1.3b
    PYTHONPATH=src python examples/serve_model.py --arch gemma2-2b --window 32
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs as config_registry
from repro.common.compile_cache import enable_compile_cache
from repro.data.synthetic import synthetic_batch_for_config
from repro.models import transformer as T


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mamba2-1.3b")
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=48)
    ap.add_argument("--decode-steps", type=int, default=24)
    ap.add_argument("--window", type=int, default=None)
    args = ap.parse_args()

    cfg = config_registry.get_reduced(args.arch)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    batch = synthetic_batch_for_config(cfg, rng, args.batch, args.prompt_len)
    inputs = {k: jnp.asarray(v) for k, v in batch.items() if k != "labels"}
    max_len = args.prompt_len + args.decode_steps

    prefill = jax.jit(lambda p, i: T.prefill(
        cfg, p, i, max_len=max_len, window_override=args.window))
    decode = jax.jit(lambda p, c, i, pos: T.decode_step(
        cfg, p, c, i, pos, window_override=args.window))

    t0 = time.time()
    logits, cache = prefill(params, inputs)
    print(f"{cfg.arch_id}: prefill {args.batch}x{args.prompt_len} -> "
          f"logits {logits.shape}  ({time.time() - t0:.2f}s)")

    tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
    generated = [np.asarray(tok)]
    t0 = time.time()
    for t in range(args.decode_steps):
        pos = jnp.asarray(args.prompt_len + t, jnp.int32)
        step_in = {"tokens": tok[:, None, :] if cfg.modality == "audio"
                   else tok[:, None]}
        logits, cache = decode(params, cache, step_in, pos)
        tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        generated.append(np.asarray(tok))
    dt = time.time() - t0
    print(f"decoded {args.decode_steps} steps in {dt:.2f}s "
          f"({args.decode_steps * args.batch / dt:.1f} tok/s on CPU)")
    first = np.stack(generated, axis=1)[0]
    print("sample stream:", first.reshape(first.shape[0], -1)[:, 0][:16].tolist())


if __name__ == "__main__":
    main()
