"""Training launcher: pjit train loop with checkpoint/restart + straggler
policy.  CPU-sized by default (reduced arch) — the mesh/sharding code path
is identical to the production one (same step builder as the dry-run).

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b \
        --steps 200 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import time

import jax

from repro.chip import enable_compile_cache
from repro.configs import get_config, reduced
from repro.data import DataConfig, batch_at
from repro.launch.step import init_train_state, make_train_step
from repro.models import build_model
from repro.optim import OptConfig
from repro.runtime import DriverConfig, run_with_restarts


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--full-size", action="store_true",
                    help="use the full config (needs a real pod)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if not args.full_size:
        cfg = reduced(cfg)
    model = build_model(cfg)
    opt_cfg = OptConfig(lr=args.lr, warmup_steps=20, total_steps=args.steps)
    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                      global_batch=args.batch)

    train_step = jax.jit(make_train_step(model, opt_cfg),
                         donate_argnums=(0,))

    def init_state():
        return init_train_state(model, jax.random.PRNGKey(0))

    t0 = time.time()

    def on_metrics(step, metrics):
        if step % args.log_every == 0:
            print(f"step {step:5d} loss {float(metrics['loss']):.4f} "
                  f"gnorm {float(metrics['grad_norm']):.3f} "
                  f"lr {float(metrics['lr']):.2e} "
                  f"({(time.time() - t0):.1f}s)", flush=True)

    drv = DriverConfig(ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
                       max_steps=args.steps)
    state = run_with_restarts(
        drv, init_state=init_state, train_step=train_step,
        batch_fn=lambda step: batch_at(dcfg, step), on_metrics=on_metrics)
    print("done; final step", int(state.opt.step))


if __name__ == "__main__":
    main()
