"""Serve a small LM: batched prefill + token-by-token decode with the KV
cache (the serving path the ``decode_32k`` / ``long_500k`` dry-run cells
lower at production scale).

Run:  PYTHONPATH=src python examples/serve_lm.py [--tokens 12]
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.models import transformer as tf


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--tokens", type=int, default=12)
    ap.add_argument("--batch", type=int, default=4)
    args = ap.parse_args()

    cfg = tf.TransformerConfig(
        name="serve-demo", n_layers=2, d_model=128, n_heads=4, n_kv_heads=2,
        d_ff=512, vocab=2048, d_head=32, attn="gqa", tp=1, max_seq=128,
        param_dtype=jnp.float32, act_dtype=jnp.float32)
    params = tf.init_params(cfg, jax.random.PRNGKey(0))

    rng = np.random.default_rng(0)
    prompts = jnp.asarray(rng.integers(0, cfg.vocab, (args.batch, 16)),
                          jnp.int32)
    s_max = 16 + args.tokens

    prefill = jax.jit(lambda p, t: tf.prefill(p, t, cfg, s_max))
    decode = jax.jit(lambda p, c, t: tf.decode_step(p, c, t, cfg))

    t0 = time.perf_counter()
    logits, cache = prefill(params, prompts)
    jax.block_until_ready(logits)
    print(f"prefill: {prompts.shape} in {time.perf_counter() - t0:.3f}s")

    token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    generated = [token]
    t0 = time.perf_counter()
    for _ in range(args.tokens - 1):
        logits, cache = decode(params, cache, token)
        token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        generated.append(token)
    jax.block_until_ready(token)
    dt = time.perf_counter() - t0
    toks = jnp.stack(generated, axis=1)
    print(f"decoded {args.tokens - 1} steps x batch {args.batch} in "
          f"{dt:.3f}s ({dt / max(args.tokens - 1, 1) * 1e3:.1f} ms/step)")
    print("generated token ids:\n", np.asarray(toks))

    # consistency: decode continuation must match a longer prefill
    full = jnp.concatenate([prompts, toks[:, :-1]], axis=1)
    logits_ref, _ = tf.prefill(params, full, cfg, s_max)
    agree = jnp.argmax(logits_ref, -1).astype(jnp.int32) == token
    print(f"decode/prefill agreement on final token: "
          f"{int(agree.sum())}/{args.batch}")
    print("done.")


if __name__ == "__main__":
    main()
