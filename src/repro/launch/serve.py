"""Serving driver: continuous-batching inference through ``ServeEngine``.

    PYTHONPATH=src python -m repro.launch.serve --arch lm16m --batch 4 \\
        --prompt-len 64 --gen 32

Routes through the same engine as the serving benchmark — a fixed slot
pool, one jitted prefill and one jitted decode compiled once, per-slot
decode positions — instead of a hand-rolled decode loop, so the driver
exercises exactly the code path ``benchmarks/serving.py`` measures.
Timings use ``time.perf_counter`` (monotonic, high resolution; wall-clock
``time.time`` can step backwards under NTP) and the decode rate counts
every generated token across the batch.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs.lm_small import SMALL_CONFIGS
from repro.data.synthetic import make_token_stream
from repro.models import api
from repro.serving import ServeEngine


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="lm16m", choices=list(SMALL_CONFIGS))
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = SMALL_CONFIGS[args.arch]
    params = api.init(cfg, jax.random.PRNGKey(args.seed))
    stream = make_token_stream(args.batch * (args.prompt_len + 1) * 4,
                               cfg.vocab_size, seed=args.seed)
    prompts = stream[: args.batch * args.prompt_len].reshape(
        args.batch, args.prompt_len).astype(np.int32)

    engine = ServeEngine(cfg, params, slots=args.batch,
                         max_prompt=args.prompt_len,
                         max_seq=args.prompt_len + args.gen)

    out = {}
    t0 = time.perf_counter()
    for rid in range(args.batch):
        fin = engine.submit(rid, prompts[rid], args.gen)
        if fin is not None:                      # gen == 1 finishes at prefill
            out[fin.rid] = fin.tokens
    t_prefill = time.perf_counter() - t0

    t1 = time.perf_counter()
    while engine.num_active:
        for fin in engine.step():
            out[fin.rid] = fin.tokens
    t_decode = time.perf_counter() - t1

    gen = np.asarray([out[rid] for rid in range(args.batch)], np.int32)
    n_decoded = args.batch * (args.gen - 1)      # first token comes from prefill
    tok_s = n_decoded / max(t_decode, 1e-9)
    print(f"# {cfg.name}: prefill {args.batch}x{args.prompt_len} in "
          f"{t_prefill:.2f}s; decode {args.gen - 1} steps at {tok_s:.1f} tok/s "
          f"({engine.slots} slots, compile counts {engine.compile_counts()})")
    print("# first sequence:", gen[0][:16].tolist())
    return gen


if __name__ == "__main__":
    main()
