"""Train a small LM for a few hundred steps with the full production stack:
synthetic data pipeline, AdamW + cosine schedule, sharding-aware step
builder, checkpoint/restart driver with an injected failure (the run dies
at step 120 and resumes from the step-100 checkpoint — final state is
identical to an uninterrupted run).

    PYTHONPATH=src python examples/train_lm.py [--steps 200]
"""
import argparse
import shutil
import tempfile
import time

import jax

from repro.chip import enable_compile_cache
from repro.configs import get_config, reduced
from repro.data import DataConfig, batch_at
from repro.launch.step import init_train_state, make_train_step
from repro.models import build_model
from repro.models.common import count_params
from repro.optim import OptConfig
from repro.runtime import DriverConfig, run_with_restarts


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = reduced(get_config("qwen3-1.7b"))
    model = build_model(cfg)
    print(f"training {cfg.name}: "
          f"{count_params(model.init(jax.random.PRNGKey(0))):,} params, "
          f"{args.steps} steps")

    opt = OptConfig(lr=3e-3, warmup_steps=20, total_steps=args.steps)
    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                      global_batch=args.batch)
    train_step = jax.jit(make_train_step(model, opt), donate_argnums=(0,))

    ckpt = tempfile.mkdtemp(prefix="repro_train_")
    losses = []
    t0 = time.time()

    def on_metrics(step, m):
        losses.append(float(m["loss"]))
        if step % 25 == 0:
            print(f"  step {step:4d} loss {losses[-1]:.4f} "
                  f"({time.time()-t0:.0f}s)")

    drv = DriverConfig(ckpt_dir=ckpt, ckpt_every=100, max_steps=args.steps,
                       fail_at_step=min(120, args.steps - 1))
    print("(failure injected at step 120 — the driver restarts from the "
          "step-100 checkpoint)")
    run_with_restarts(
        drv, init_state=lambda: init_train_state(model,
                                                 jax.random.PRNGKey(0)),
        train_step=train_step, batch_fn=lambda s: batch_at(dcfg, s),
        on_metrics=on_metrics)

    first, last = losses[0], sum(losses[-10:]) / 10
    print(f"loss: {first:.3f} → {last:.3f} "
          f"({'improved' if last < first else 'NOT improved'})")
    shutil.rmtree(ckpt, ignore_errors=True)
    assert last < first, "training did not reduce loss"


if __name__ == "__main__":
    main()
