"""Batched autoregressive serving with a KV/state cache.

Serves a reduced-config model from the zoo: prefill the prompt batch, then
step the jitted serve_step (one token per call against the cache).  Works
for every family -- attention KV caches, RWKV6 constant-size state, and
Hymba's hybrid window+SSM cache -- because each model implements
``init_cache`` / ``decode_step`` behind the same interface.

    PYTHONPATH=src python examples/serve_lm.py --arch rwkv6-1.6b --tokens 32
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.registry import get_config, get_model, list_archs
from repro.runtime.compile_cache import use_compile_cache
from repro.serve.serve_step import make_serve_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b", choices=list_archs())
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_config(args.arch).reduced()
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, P = args.batch, args.prompt_len
    max_len = P + args.tokens

    rng = np.random.default_rng(0)
    prompts = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, P)), jnp.int32)

    cache = model.init_cache(B, max_len, dtype=jnp.float32)
    step = jax.jit(make_serve_step(model, temperature=args.temperature))

    # prefill: teacher-force the prompt through decode_step (cache warmup)
    t0 = time.perf_counter()
    for i in range(P):
        _, _, cache = step(params, cache, prompts[:, i : i + 1],
                           jax.random.PRNGKey(i))
    jax.block_until_ready(cache)
    t_prefill = time.perf_counter() - t0

    # decode loop
    tok = prompts[:, -1:]
    out = []
    t0 = time.perf_counter()
    for i in range(args.tokens):
        tok, _, cache = step(params, cache, tok, jax.random.PRNGKey(1000 + i))
        out.append(tok)
    jax.block_until_ready(tok)
    t_decode = time.perf_counter() - t0

    gen = jnp.concatenate(out, axis=1)
    print(f"arch={cfg.name} family={cfg.family} batch={B}")
    print(f"prefill: {P} tokens in {t_prefill*1e3:.1f} ms")
    print(f"decode : {args.tokens} tokens in {t_decode*1e3:.1f} ms "
          f"({B*args.tokens/t_decode:.1f} tok/s)")
    print(f"sample row 0: {np.asarray(gen[0])[:16].tolist()}")


if __name__ == "__main__":
    main()
