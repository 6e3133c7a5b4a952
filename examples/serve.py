"""Serve a small model with batched decode requests through the serve_step
path (KV cache / SSM state), demonstrating the inference side of the
framework on any assigned architecture family.

    PYTHONPATH=src python examples/serve.py --arch mamba2-370m --tokens 32
"""
import argparse
import importlib
import time

import jax
import jax.numpy as jnp

from repro.models import model as zoo
from repro.utils.compile_cache import enable_compile_cache

ARCH_MODULES = {
    "granite-8b": "granite_8b", "mamba2-370m": "mamba2_370m",
    "jamba-v0.1-52b": "jamba_52b", "dbrx-132b": "dbrx_132b",
    "phi4-mini-3.8b": "phi4_mini",
}


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mamba2-370m", choices=sorted(ARCH_MODULES))
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--tokens", type=int, default=32)
    args = ap.parse_args()

    cfg = importlib.import_module(
        f"repro.configs.{ARCH_MODULES[args.arch]}").smoke_config()
    params, _ = zoo.init(cfg, jax.random.PRNGKey(0))
    cache, _ = zoo.init_cache(cfg, batch=args.batch, context=args.tokens + 8)
    step = jax.jit(lambda p, c, t: zoo.decode_fn(p, cfg, c, t))

    tok = jnp.zeros((args.batch, 1), jnp.int32)
    t0 = time.time()
    out = []
    for _ in range(args.tokens):
        logits, cache = step(params, cache, tok)     # (B, 1, V)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # greedy (B, 1)
        out.append(tok[:, 0])
    dt = time.time() - t0
    gen = jnp.stack(out, axis=1)
    print(f"{args.arch} ({cfg.name}): generated {gen.shape} tokens in {dt:.2f}s "
          f"({args.batch*args.tokens/dt:.1f} tok/s on "
          f"{jax.devices()[0].platform})")
    print("sample:", gen[0][:16].tolist())


if __name__ == "__main__":
    main()
