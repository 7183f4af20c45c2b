"""Batched serving demo: prefill a batch of prompts into a KV cache, then
greedy-decode new tokens (the serve_step the dry-run lowers at 32k/500k).

    PYTHONPATH=src python examples/serve_batch.py --arch mamba2-1.3b
"""
import argparse
import time

import jax

from repro.configs import get_config, smoke
from repro.models import init_params
from repro.serving.engine import greedy_generate


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=32)
    args = ap.parse_args()

    cfg = smoke(get_config(args.arch))
    params = init_params(jax.random.PRNGKey(0), cfg)
    print(f"serving {cfg.name} (reduced): batch={args.batch}, "
          f"prompt={args.prompt_len}, generate={args.new_tokens}")

    prompt = jax.random.randint(jax.random.PRNGKey(1),
                                (args.batch, args.prompt_len), 0, cfg.vocab)
    fe = None
    if cfg.frontend:
        fe = 0.1 * jax.random.normal(jax.random.PRNGKey(2),
                                     (args.batch, cfg.n_frontend_tokens,
                                      cfg.d_model))
    gen = jax.jit(lambda p: greedy_generate(params, cfg, p,
                                            steps=args.new_tokens,
                                            frontend=fe))
    t0 = time.time()
    out = jax.block_until_ready(gen(prompt))
    t_compile = time.time() - t0
    t0 = time.time()
    out = jax.block_until_ready(gen(prompt))
    t_run = time.time() - t0
    tok_s = args.batch * args.new_tokens / t_run
    print(f"compile {t_compile:.1f}s; decode {t_run:.2f}s "
          f"({tok_s:.0f} tok/s on CPU)")
    print("sample continuation token ids:", out[0, args.prompt_len:][:16].tolist())


if __name__ == "__main__":
    from repro.substrate import enable_compile_cache
    enable_compile_cache()
    main()
