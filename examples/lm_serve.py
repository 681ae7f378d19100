"""Batched serving example: prefill + decode over the cache pytree, for a
dense, an MoE, and an attention-free (Mamba2) architecture.

  PYTHONPATH=src python examples/lm_serve.py --tokens 24
"""
import argparse
import time

import jax.numpy as jnp
import numpy as np

from repro.configs import smoke_config
from repro.launch.serve import ServeSession
from repro.models import model as M
from repro.models.params import init_params
from repro.runtime.compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tokens", type=int, default=24)
    ap.add_argument("--batch", type=int, default=4)
    args = ap.parse_args()

    rng = np.random.default_rng(0)
    for arch in ("qwen3-32b", "qwen2-moe-a2.7b", "mamba2-370m"):
        cfg = smoke_config(arch)
        params = init_params(M.model_specs(cfg), seed=0)
        sess = ServeSession(cfg, params,
                            max_len=16 + args.tokens + 1)
        prompts = rng.integers(0, cfg.vocab, (args.batch, 16)).astype(
            np.int32)
        t0 = time.perf_counter()
        out = sess.generate(prompts, args.tokens, temperature=0.8, seed=1)
        dt = time.perf_counter() - t0
        assert out.shape == (args.batch, args.tokens)
        assert (out >= 0).all() and (out < cfg.vocab).all()
        print(f"{arch:20s} generated {out.shape[0]}x{out.shape[1]} tokens "
              f"in {dt:.2f}s (incl. compile); sample: {out[0, :8].tolist()}")
    print("OK")


if __name__ == "__main__":
    enable_compile_cache()
    main()
