"""Serve a small model with batched requests: continuous batching, paged
KV bookkeeping, mixed prompt lengths.

  PYTHONPATH=src python examples/serve_lm.py
"""
import time

import jax
import numpy as np

from repro.models.transformer import ModelConfig, init_params
from repro.serve import DecodeServeEngine, Request


def main():
    cfg = ModelConfig(
        name="demo-serve",
        num_layers=4,
        d_model=256,
        num_heads=8,
        num_kv_heads=2,
        d_ff=1024,
        vocab=512,
        compute_dtype="float32",
        remat=False,
    )
    params = init_params(jax.random.PRNGKey(7), cfg)
    eng = DecodeServeEngine(params, cfg, slots=8, max_len=256)
    rng = np.random.default_rng(3)
    n_req = 24
    for i in range(n_req):
        prompt = rng.integers(0, cfg.vocab, int(rng.integers(4, 24))).astype(np.int32)
        eng.submit(Request(rid=i, prompt=prompt, max_new=32))
    t0 = time.time()
    eng.run()
    dt = time.time() - t0
    tok = n_req * 32
    print(f"served {n_req} requests / {tok} new tokens in {eng.steps} batched decode steps")
    print(f"{dt:.1f}s on CPU -> {tok / dt:.1f} tok/s; free KV pages: {len(eng.pages.free)}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
