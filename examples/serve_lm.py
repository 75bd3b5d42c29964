"""Continuous-batching serving demo: batched requests through ServeLoop.

  PYTHONPATH=src python examples/serve_lm.py --requests 12 --slots 4
"""
import argparse
import time

import jax
import numpy as np

from repro.configs import get_smoke_config
from repro.models import get_model
from repro.serve import Request, ServeLoop
from repro.util import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--arch", default="qwen3-4b")
    args = ap.parse_args()

    cfg = get_smoke_config(args.arch)
    api = get_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    loop = ServeLoop(api, params, slots=args.slots, max_len=128)

    rng = np.random.RandomState(0)
    for r in range(args.requests):
        plen = int(rng.randint(4, 24))
        loop.submit(Request(rid=r,
                            prompt=rng.randint(1, cfg.vocab, plen)
                            .astype(np.int32),
                            max_new=args.max_new))
    t0 = time.time()
    results = loop.run()
    dt = time.time() - t0
    tokens = sum(len(r.tokens) for r in results)
    print(f"served {len(results)} requests, {tokens} tokens in {dt:.1f}s "
          f"({tokens / dt:.1f} tok/s with {args.slots} slots)")
    for r in sorted(results, key=lambda x: x.rid)[:5]:
        print(f"  rid={r.rid} prefill={r.prefill_len} "
              f"decoded={r.decode_steps} first tokens {r.tokens[:6]}")
    assert len(results) == args.requests


if __name__ == "__main__":
    enable_compile_cache()
    main()
