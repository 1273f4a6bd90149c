"""Serve a small LM with batched requests: prefill + sampled decode.

Uses the qwen1.5-0.5b *reduced* config (same code path as the production
serve_step that the dry-run lowers for decode_32k / long_500k).

Run:  PYTHONPATH=src python examples/serve_lm.py [--batch 4 --new 24]
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.serve import generate
from repro.models import lm


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new", type=int, default=24)
    ap.add_argument("--temperature", type=float, default=0.8)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch).reduced()
    params = lm.init(jax.random.PRNGKey(0), cfg)
    prompts = jnp.asarray(
        np.random.default_rng(0).integers(
            0, cfg.vocab_size, (args.batch, args.prompt_len)), jnp.int32)
    out, stats = generate(params, cfg, prompts,
                          max_new_tokens=args.new,
                          temperature=args.temperature, verbose=True)
    print(f"prefill: {stats['prefill_s']*1e3:.1f} ms   "
          f"decode: {stats['decode_tok_per_s']:.1f} tok/s "
          f"(batch {args.batch})")
    print("generated token ids (first request):",
          np.asarray(out[0, args.prompt_len:]).tolist())


if __name__ == "__main__":
    main()
