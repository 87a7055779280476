"""Batched serving demo: waves of one prompt length through prefill + decode.

Run:  PYTHONPATH=src python examples/serve_demo.py [--arch qwen3-8b]
(all archs run as tiny variants on CPU; --no-tiny for the full config)
"""

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config, list_archs, tiny_variant
from repro.models import init_params
from repro.serving import ServeEngine


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-8b", choices=list_archs())
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--batch-size", type=int, default=4)
    ap.add_argument("--max-new-tokens", type=int, default=24)
    args = ap.parse_args()

    cfg = tiny_variant(get_config(args.arch))
    params = init_params(cfg, jax.random.PRNGKey(0))
    engine = ServeEngine(cfg, params, batch_size=args.batch_size)

    rng = np.random.default_rng(0)
    prompts = [list(rng.integers(0, cfg.vocab, size=rng.integers(8, 48)))
               for _ in range(args.requests)]
    frontend = None
    if cfg.frontend != "none":
        frontend = jax.numpy.ones(
            (args.batch_size, cfg.frontend_len, cfg.d_model), jax.numpy.bfloat16)

    t0 = time.time()
    results = engine.generate(prompts, max_new_tokens=args.max_new_tokens,
                              frontend=frontend)
    dt = time.time() - t0
    n_tok = sum(len(r.tokens) for r in results)
    print(f"{cfg.name}: {len(results)} requests -> {n_tok} tokens "
          f"in {dt:.1f}s ({n_tok / dt:.1f} tok/s, "
          f"batch={args.batch_size})")
    for r in results[:5]:
        print(f"  req {r.request_id} (prompt {len(r.prompt)} toks): "
              f"{r.tokens[:10]}...")


if __name__ == "__main__":
    main()
