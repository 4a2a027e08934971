"""Serving driver: a thin CLI over the continuous-batching engine.

    PYTHONPATH=src python -m repro.launch.serve --arch gemma-2b --reduced \
        --requests 8 --prompt-len 16 --new-tokens 32

Requests with random prompts stream into ``serving.ServeEngine`` —
admission, page allocation and prefill/decode interleaving happen inside
the engine; this file only builds the model, submits, and reports.
"""
from __future__ import annotations

import argparse
import time

import jax

from repro.configs import get_config
from repro.launch.cache import enable_compile_cache
from repro.models import registry
from repro.serving import ServeEngine


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--max-slots", type=int, default=4)
    ap.add_argument("--page", type=int, default=None,
                    help="KV page size (default: solve_recurrence_blocks)")
    ap.add_argument("--pool-pages", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch, reduced=args.reduced)
    params, _ = registry.init(cfg, jax.random.PRNGKey(args.seed))
    max_len = args.prompt_len + args.new_tokens
    engine = ServeEngine(cfg, params, max_slots=args.max_slots,
                         max_len=max_len, page=args.page,
                         pool_pages=args.pool_pages)
    prompts = jax.random.randint(
        jax.random.PRNGKey(args.seed + 1),
        (args.requests, args.prompt_len), 0, cfg.vocab_size)
    t0 = time.perf_counter()
    rids = [engine.submit(row.tolist(), args.new_tokens,
                          now=time.perf_counter() - t0)
            for row in prompts]
    results = engine.run(clock=lambda: time.perf_counter() - t0)
    wall = time.perf_counter() - t0
    n_tok = sum(len(results[r]["tokens"]) for r in rids)
    print(f"arch={cfg.name} paged={engine.paged} page={engine.page} "
          f"slots={engine.max_slots}")
    print(f"{args.requests} requests, {n_tok} tokens in {wall:.2f}s "
          f"(incl. compile) = {n_tok / wall:.1f} tok/s")
    print("sample output ids:", results[rids[0]]["tokens"][:16])
    return results


if __name__ == "__main__":
    main()
