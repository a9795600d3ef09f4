"""Serving launcher: batched prefill + decode with sharded KV caches.

    python -m repro.launch.serve --arch smollm-135m --smoke --new-tokens 32
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import ModelConfig, get_model_config, reduced
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model


def generate(model: ModelConfig, *, batch: int, prompt_len: int,
             new_tokens: int) -> dict:
    """Greedy decode of ``new_tokens`` after random prompts, one token per
    step through the KV cache (the prompt is fed token by token too).

    Returns params, prompts, sequences (batch, prompt_len + new_tokens), the
    logits that predicted the first new token, and timings: ``first_step_s``
    holds the compile, ``steady_step_s`` is the mean of the other steps.
    """
    api = build_model(model)
    rng = jax.random.PRNGKey(0)
    params = api.init_params(rng)

    b, s = batch, prompt_len
    tokens = jax.random.randint(rng, (b, s), 0, model.vocab_size, dtype=jnp.int32)

    decode = jax.jit(api.decode_fn, donate_argnums=(1,))
    cache = api.init_cache(b, s + new_tokens)
    pos = jnp.zeros((b,), jnp.int32)
    tok = tokens[:, 0]
    out = [tok]
    first_logits = None
    t0 = time.monotonic()
    for t in range(1, s + new_tokens):
        logits, cache = decode(params, cache, tok, pos + (t - 1))
        if t == 1:
            logits.block_until_ready()
            t1 = time.monotonic()
        if t == s:
            first_logits = logits
        nxt = jnp.argmax(logits, -1).astype(jnp.int32)
        tok = tokens[:, t] if t < s else nxt
        out.append(tok)
    seqs = jax.block_until_ready(jnp.stack(out, axis=1))
    t2 = time.monotonic()
    n_steps = s + new_tokens - 1
    return {
        "params": params, "prompts": tokens, "seqs": seqs,
        "first_logits": first_logits,
        "first_step_s": t1 - t0,
        "steady_step_s": (t2 - t1) / max(n_steps - 1, 1),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="decode_32k")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=32)
    args = ap.parse_args()

    enable_compile_cache()
    model = get_model_config(args.arch)
    if args.smoke:
        model = reduced(model)
    r = generate(model, batch=args.batch, prompt_len=args.prompt_len,
                 new_tokens=args.new_tokens)
    print(f"[serve] {model.name}: {args.batch} seqs, {args.prompt_len} prompt + "
          f"{args.new_tokens} new tokens; first step (compile) "
          f"{r['first_step_s']:.2f}s, then {r['steady_step_s'] * 1e3:.2f} ms/step "
          f"({args.batch / r['steady_step_s']:.1f} tok/s)", flush=True)
    s = args.prompt_len
    print("[serve] sample continuation token ids:", r["seqs"][0, s : s + 8].tolist())


if __name__ == "__main__":
    main()
