"""Batched serving: prefill + KV-cache decode on an assigned architecture.

Demonstrates the serving path the decode_32k / long_500k dry-run cells lower:
greedy decoding with a batch of requests against a shared-shape KV cache
(ring caches for the sliding-window layers when --ring is set).

Run:  PYTHONPATH=src python examples/serve.py --arch gemma2-9b --ring
      (reduced config on CPU; full configs are dry-run/TPU territory)
"""
import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.models import build_model, reduce_for_smoke
from repro.train.serve_step import make_decode_step, make_prefill


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma2-9b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=24)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--ring", action="store_true",
                    help="window-sized ring caches for local layers")
    args = ap.parse_args()

    cfg = reduce_for_smoke(get_config(args.arch))
    if args.ring:
        cfg = dataclasses.replace(cfg, ring_cache=True)
    if cfg.is_encoder_only:
        raise SystemExit(f"{args.arch} is encoder-only: no decode path")
    model = build_model(cfg)
    key = jax.random.PRNGKey(0)
    params = model.init(key)

    B = args.batch
    prompts = jax.random.randint(key, (B, args.prompt_len), 0, cfg.vocab_size)
    max_seq = args.prompt_len + args.new_tokens
    cache = model.init_cache(B, max_seq)

    prefill = jax.jit(make_prefill(model))
    decode = jax.jit(make_decode_step(model))

    t0 = time.perf_counter()
    token, cache = prefill(params, {"tokens": prompts}, cache)
    token.block_until_ready()
    t_prefill = time.perf_counter() - t0

    generated = [token]
    t0 = time.perf_counter()
    for _ in range(args.new_tokens - 1):
        token, cache = decode(params, token, cache)
        generated.append(token)
    jax.block_until_ready(generated[-1])
    t_decode = time.perf_counter() - t0

    out = jnp.concatenate(generated, axis=1)
    print(f"arch={cfg.arch_id} (reduced) ring_cache={cfg.ring_cache}")
    print(f"prefill {args.prompt_len} toks x{B}: {t_prefill*1e3:.1f} ms")
    print(f"decode {args.new_tokens-1} steps: "
          f"{t_decode/(args.new_tokens-1)*1e3:.1f} ms/token (CPU, compiled)")
    print(f"generated token ids (seq 0): {list(map(int, out[0][:12]))} ...")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
