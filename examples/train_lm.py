"""End-to-end training driver: train a small LM from the zoo on synthetic
data and watch the loss fall.

CPU demo (default, ~25M params):
    PYTHONPATH=src python examples/train_lm.py --steps 30

The ~100M configuration used for the checked-in loss curve:
    PYTHONPATH=src python examples/train_lm.py --d-model 768 --layers 12 \
        --steps 300 --batch 8 --seq 512
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.data.synth_tokens import synthetic_lm_batches
from repro.models import Batch
from repro.training.step import init_train_state, make_train_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b")
    ap.add_argument("--d-model", type=int, default=512)
    ap.add_argument("--layers", type=int, default=8)
    ap.add_argument("--vocab", type=int, default=2048)
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    args = ap.parse_args()

    cfg = get_config(args.arch).replace(
        n_layers=args.layers, d_model=args.d_model,
        n_heads=max(4, args.d_model // 128), n_kv_heads=2,
        head_dim=64, d_ff=4 * args.d_model, vocab=args.vocab)
    n_params = cfg.param_count()
    print(f"arch={cfg.name} (reduced) params={n_params/1e6:.1f}M")

    state = init_train_state(jax.random.PRNGKey(0), cfg)
    step = jax.jit(make_train_step(cfg, peak_lr=args.lr, warmup=20,
                                   total_steps=args.steps,
                                   microbatches=args.microbatches))

    batches = synthetic_lm_batches(jax.random.PRNGKey(1), vocab=cfg.vocab,
                                   batch=args.batch, seq=args.seq)
    t0 = time.time()
    for i, batch in zip(range(args.steps), batches):
        state, metrics = step(state, batch)
        if i % max(1, args.steps // 10) == 0 or i == args.steps - 1:
            print(f"step {i:4d}  loss={float(metrics['loss']):.4f}  "
                  f"grad_norm={float(metrics['grad_norm']):.3f}  "
                  f"lr={float(metrics['lr']):.2e}  "
                  f"({(time.time()-t0)/(i+1):.2f}s/step)")
    print("done.")


if __name__ == "__main__":
    from repro.substrate import enable_compile_cache
    enable_compile_cache()
    main()
