"""End-to-end driver: train a ~small LM for a few hundred steps on CPU,
with checkpoint/resume and a demonstrably decreasing loss (Markov data).

  PYTHONPATH=src python examples/train_lm.py [--steps 300]
"""
import argparse
import tempfile

import jax
import jax.numpy as jnp

from repro.models.transformer import ModelConfig, init_params
from repro.train import AdamWConfig, TrainConfig, checkpoint, make_train_step
from repro.train.data import DataConfig, markov_batch
from repro.train.optimizer import init_state


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=150)
    ap.add_argument("--resume-demo", action="store_true", help="kill + resume mid-run")
    args = ap.parse_args()

    # ~7M params: a few hundred steps finish in minutes on one CPU core;
    # scale num_layers/d_model up freely on real hardware.
    cfg = ModelConfig(
        name="demo-7m",
        num_layers=3,
        d_model=192,
        num_heads=6,
        num_kv_heads=3,
        d_ff=768,
        vocab=512,
        compute_dtype="float32",
        remat=False,
    )
    tcfg = TrainConfig(adamw=AdamWConfig(lr=1e-3, warmup_steps=30, total_steps=args.steps))
    params = init_params(jax.random.PRNGKey(0), cfg)
    opt = init_state(tcfg.adamw, params)
    n = sum(x.size for x in jax.tree.leaves(params))
    print(f"model: {n / 1e6:.1f}M params")
    step_fn = jax.jit(make_train_step(cfg, tcfg), donate_argnums=(0, 1))
    dcfg = DataConfig(vocab=cfg.vocab, seq_len=96, global_batch=8)

    ckpt_dir = tempfile.mkdtemp(prefix="repro-ckpt-")
    first_loss = last_loss = None
    for step in range(args.steps):
        batch = jax.tree.map(jnp.asarray, markov_batch(dcfg, step))
        params, opt, m = step_fn(params, opt, batch)
        if first_loss is None:
            first_loss = float(m["loss"])
        last_loss = float(m["loss"])
        if (step + 1) % 50 == 0:
            print(f"step {step + 1:4d}  loss {last_loss:.4f}  lr {float(m['lr']):.2e}")
            checkpoint.save(ckpt_dir, step + 1, {"params": params, "opt": opt})
        if args.resume_demo and step == args.steps // 2:
            print("-- simulating failure: restoring from latest checkpoint --")
            latest = checkpoint.latest_step(ckpt_dir)
            if latest:
                state = checkpoint.restore(ckpt_dir, latest, {"params": params, "opt": opt})
                params, opt = state["params"], state["opt"]
    print(f"\nloss: {first_loss:.3f} -> {last_loss:.3f} "
          f"({'LEARNED' if last_loss < first_loss - 0.5 else 'check hyperparams'})")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
