"""Quickstart: build an assigned architecture (reduced), train a few steps,
then prefill + decode — the whole public API in one file.

    PYTHONPATH=src python examples/quickstart.py [--arch qwen3-1.7b]
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.chip import enable_compile_cache
from repro.configs import ARCHS, get_config, reduced
from repro.data import DataConfig, batch_at
from repro.launch.step import init_train_state, make_train_step
from repro.models import build_model
from repro.models.common import count_params
from repro.optim import OptConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b", choices=sorted(ARCHS))
    ap.add_argument("--steps", type=int, default=20)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = reduced(get_config(args.arch))
    print(f"arch={args.arch} family={cfg.family} (reduced for CPU)")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    print(f"params: {count_params(params):,}")

    # --- train a few steps on the synthetic pipeline -----------------------
    if cfg.family in ("vlm", "encdec"):
        print("quickstart trains token-LM families; see tests for "
              f"{cfg.family} coverage")
    else:
        step = jax.jit(make_train_step(model, OptConfig(lr=3e-3,
                                                        warmup_steps=5,
                                                        total_steps=200)),
                       donate_argnums=(0,))
        state = init_train_state(model, jax.random.PRNGKey(0))
        dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=32,
                          global_batch=8)
        t0 = time.time()
        for s in range(args.steps):
            state, metrics = step(state, batch_at(dcfg, s))
            if s % 5 == 0 or s == args.steps - 1:
                print(f"  step {s:3d} loss {float(metrics['loss']):.4f}")
        print(f"trained {args.steps} steps in {time.time()-t0:.1f}s")
        params = state.params

        # --- decode a continuation (replay prompt, then sample greedily) ---
        prompt = batch_at(dcfg, 999)["tokens"][:2, :16]
        caches = model.init_cache(2, 32)
        logits = None
        for t in range(16):
            logits, caches = model.decode_step(
                params, caches, {"token": prompt[:, t:t + 1],
                                 "pos": jnp.int32(t)})
        out = [int(x) for x in jnp.argmax(logits, -1)]
        for t in range(16, 24):
            nxt = jnp.argmax(logits, -1)[:, None]
            logits, caches = model.decode_step(
                params, caches, {"token": nxt, "pos": jnp.int32(t)})
        print("decoded 8 tokens greedily — public API round trip OK")


if __name__ == "__main__":
    main()
