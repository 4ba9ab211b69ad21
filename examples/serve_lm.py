"""Batched serving example: prefill + greedy decode with KV caches on any
assigned architecture (reduced config so it runs on CPU in seconds).

    PYTHONPATH=src python examples/serve_lm.py --arch gemma3-1b --requests 4
"""
import argparse
import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.compile_cache import use_compile_cache
from repro.configs import all_archs, get_reduced
from repro.models import build
from repro.serve.engine import greedy_generate


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-1b", choices=all_archs())
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--gen-len", type=int, default=24)
    args = ap.parse_args()
    use_compile_cache(pathlib.Path(__file__).resolve().parents[1]
                      / ".jax_cache")

    cfg = get_reduced(args.arch)
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    print(f"serving {cfg.name} ({cfg.family}); batch={args.requests}")

    prompts = jax.random.randint(
        jax.random.PRNGKey(1), (args.requests, args.prompt_len), 0,
        cfg.vocab_size, dtype=jnp.int32)
    s_max = args.prompt_len + args.gen_len + 1

    t0 = time.time()
    out = greedy_generate(model, params, prompts, n_steps=args.gen_len,
                          s_max=s_max)
    dt = time.time() - t0
    total_new = args.requests * args.gen_len
    print(f"generated {total_new} tokens in {dt:.2f}s "
          f"({total_new / dt:.1f} tok/s incl. compile)")
    for i, row in enumerate(np.asarray(out)):
        print(f"  request {i}: prompt={row[:args.prompt_len].tolist()} "
              f"-> {row[args.prompt_len:args.prompt_len + 8].tolist()}...")
    assert out.shape == (args.requests, args.prompt_len + args.gen_len)


if __name__ == "__main__":
    main()
