"""Batched serving demo: continuous batching through the paged-KV engine
whose page table is a big-atomic CacheHash.

  PYTHONPATH=src python examples/serve_batched.py
  PYTHONPATH=src python examples/serve_batched.py --strategy seqlock

Submits a staggered stream of requests (different lengths and arrival times),
decodes them concurrently, and prints per-request tokens plus engine
throughput.  `--strategy` switches the page-table big-atomic implementation —
the serving loop is oblivious, which is the point: big atomics are a
substrate, not an API change.
"""

import argparse

import jax
import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import use_compile_cache
from repro.models.transformer import init_params
from repro.serving import Request, ServingEngine


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="deepseek-7b")
    ap.add_argument("--strategy", default="cached_me",
                    choices=["cached_me", "cached_wf", "seqlock", "indirect"])
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--max-new", type=int, default=10)
    args = ap.parse_args()

    cfg = get_config(args.arch, reduced=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, params, max_batch=3, n_pages=64, page_size=8,
                        max_pages_per_seq=8, strategy=args.strategy)

    rng = np.random.default_rng(0)
    import time
    t0 = time.time()
    pending = [
        Request(rid=i, prompt=rng.integers(0, cfg.vocab,
                                           int(rng.integers(8, 30))
                                           ).astype(np.int32),
                max_new_tokens=args.max_new)
        for i in range(args.requests)
    ]
    # staggered arrivals: submit two up front, one more every 2 steps
    eng.submit(pending.pop(0))
    eng.submit(pending.pop(0))
    steps = 0
    while True:
        live = eng.step()
        steps += 1
        if steps % 2 == 0 and pending:
            eng.submit(pending.pop(0))
        if live == 0 and not pending and not eng.pending():
            break
    dt = time.time() - t0
    out = {r.rid: r.out_tokens for r in eng.requests.values()}
    total = sum(len(v) for v in out.values())
    for rid in sorted(out):
        print(f"[serve] request {rid} ({len(out[rid])} tokens): {out[rid]}")
    print(f"[serve] {total} tokens / {steps} engine steps / {dt:.2f}s "
          f"({total/dt:.1f} tok/s) page-table strategy={args.strategy}")


if __name__ == "__main__":
    main()
