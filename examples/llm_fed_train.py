"""End-to-end driver: federated FIM-L-BFGS training of a ~100M-parameter
LLM (granite-8b family, reduced width/depth) on synthetic Zipf token data
for a few hundred steps on CPU — the llm-scale path of launch/train.py with
microbatch cohorts playing the client role.

    PYTHONPATH=src python examples/llm_fed_train.py [--steps 200]
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import checkpoint
from repro.configs.granite_8b import CONFIG
from repro.data.synthetic import zipf_tokens
from repro.launch import train as trainlib
from repro.utils.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--full", action="store_true",
                    help="~100M-param variant (slow on 1 CPU: ~15s/step)")
    ap.add_argument("--ckpt", default="/tmp/repro_llm_ck.npz")
    args = ap.parse_args()

    # reduced member of the granite family (exact arch, scaled dims);
    # --full gives the ~100M-param variant of the same stack.
    if args.full:
        cfg = CONFIG.replace(
            name="granite-100m", num_layers=12, d_model=768, num_heads=12,
            num_kv_heads=4, head_dim=64, d_ff=2304, vocab_size=16384,
            dtype="float32", remat=False, attn_q_chunk=64, lbfgs_m=10,
            lbfgs_dtype="float32")
    else:
        cfg = CONFIG.replace(
            name="granite-12m", num_layers=6, d_model=384, num_heads=6,
            num_kv_heads=2, head_dim=64, d_ff=1024, vocab_size=4096,
            dtype="float32", remat=False, attn_q_chunk=64, lbfgs_m=10,
            lbfgs_dtype="float32")
    n_params_m = cfg.param_count() / 1e6
    print(f"arch {cfg.name}: {n_params_m:.1f}M params")

    ocfg = trainlib.opt_config(cfg, learning_rate=0.3)
    params, _, opt, _ = trainlib.init_train_state(cfg, ocfg, jax.random.PRNGKey(0))
    step = jax.jit(trainlib.make_train_step(cfg, ocfg, n_micro=2))

    data = zipf_tokens(512, args.seq + 1, cfg.vocab_size, seed=0)
    rng = np.random.default_rng(0)
    t0 = time.time()
    for t in range(args.steps):
        idx = rng.integers(0, len(data), size=args.batch)
        batch = {"tokens": jnp.asarray(data[idx, :args.seq])}
        params, opt, stats = step(params, opt, batch)
        if (t + 1) % 20 == 0:
            print(f"step {t+1:4d} loss {float(stats['loss']):.4f} "
                  f"|g| {float(stats['grad_norm']):.3f} "
                  f"({(time.time()-t0)/(t+1):.2f}s/step)")
    checkpoint.save(args.ckpt, params)
    print(f"saved checkpoint to {args.ckpt}")


if __name__ == "__main__":
    main()
