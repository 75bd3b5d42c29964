"""End-to-end training driver: ~100M-param LM, few hundred steps, with
checkpointing + fault tolerance + deterministic data.

  PYTHONPATH=src python examples/train_lm.py --preset small --steps 100
  PYTHONPATH=src python examples/train_lm.py --preset 100m --steps 300

The 100m preset is the deliverable configuration (run it on real
hardware); `small` (~13M) finishes in minutes on this CPU container and
exercises the identical code path.  Use --crash-at to demo restart.
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.data import TokenPipeline
from repro.ft import FailurePlan, TrainDriver
from repro.models import get_model
from repro.models.layers import ModelConfig
from repro.train import AdamWConfig, make_train_step
from repro.train import init as opt_init
from repro.util import enable_compile_cache

PRESETS = {
    "tiny": ModelConfig(name="tiny-2m", n_layers=2, d_model=128, n_heads=4,
                        n_kv=2, d_head=32, d_ff=512, vocab=4096),
    "small": ModelConfig(name="small-13m", n_layers=6, d_model=384,
                         n_heads=6, n_kv=2, d_head=64, d_ff=1536,
                         vocab=8192),
    "100m": ModelConfig(name="lm-100m", n_layers=12, d_model=768,
                        n_heads=12, n_kv=4, d_head=64, d_ff=3072,
                        vocab=32768, qk_norm=True),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", choices=PRESETS, default="small")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--ckpt-dir", default="experiments/train_lm_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--crash-at", type=int, default=-1,
                    help="inject a crash at this step (restart demo)")
    args = ap.parse_args()

    cfg = PRESETS[args.preset]
    api = get_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    n_params = sum(int(np.prod(l.shape))
                   for l in jax.tree_util.tree_leaves(params))
    print(f"model {cfg.name}: {n_params / 1e6:.1f}M params, "
          f"batch {args.batch}x{args.seq}")

    ocfg = AdamWConfig(total_steps=args.steps, warmup_steps=args.steps // 20)
    opt = opt_init(ocfg, params)
    step = jax.jit(make_train_step(api, ocfg), donate_argnums=(0, 1))
    pipe = TokenPipeline(vocab=cfg.vocab, batch=args.batch, seq=args.seq)

    plan = FailurePlan(at_steps={args.crash_at: "crash"}
                       if args.crash_at >= 0 else {})
    drv = TrainDriver(
        step_fn=step,
        batch_fn=lambda s: {k: jnp.asarray(v)
                            for k, v in pipe.batch_at(s).items()},
        ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
        failure_plan=plan)
    t0 = time.time()
    params, opt, info = drv.run(params, opt, args.steps)
    dt = time.time() - t0
    hist = info["history"]
    tok_s = args.batch * args.seq * len(hist) / dt
    print(f"done: {len(hist)} steps in {dt:.0f}s ({tok_s:.0f} tok/s), "
          f"restarts={info['restarts']}")
    print(f"loss {hist[0]['loss']:.3f} -> {hist[-1]['loss']:.3f}")
    assert hist[-1]["loss"] < hist[0]["loss"], "loss did not improve"


if __name__ == "__main__":
    enable_compile_cache()
    main()
