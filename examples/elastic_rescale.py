"""Elastic scaling demo: checkpoint under one device topology, restore under
another, and continue training bit-compatibly (the fleet shrank or grew —
deliverable: elastic scaling + checkpoint/restart).

Runs as a parent process that launches two children with different
simulated device counts (jax fixes the device count at first init):

    PYTHONPATH=src python examples/elastic_rescale.py
"""
import json
import os
import subprocess
import sys
import tempfile

CHILD = r"""
import os, sys, json
n_dev, ckpt, phase = int(sys.argv[1]), sys.argv[2], sys.argv[3]
os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_dev}"
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.configs import get_config, reduced
from repro.models import build_model
from repro.chip import enable_compile_cache
from repro.launch.mesh import auto_mesh, shardings
from repro.launch.step import init_train_state, make_train_step, TrainState
from repro.optim import OptConfig, opt_specs
from repro.checkpoint import save, restore, latest_step
from repro.data import DataConfig, batch_at

enable_compile_cache()
mesh = auto_mesh((n_dev // 2, 2), ("data", "model"))
cfg = reduced(get_config("qwen3-1.7b"))
model = build_model(cfg, mesh=mesh)
opt = OptConfig(lr=1e-3, warmup_steps=2, total_steps=20)
dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=16, global_batch=4)
step_fn = jax.jit(make_train_step(model, opt))

def specs():
    ps = model.specs()
    return TrainState(ps, opt_specs(ps))

if phase == "start":
    state = init_train_state(model, jax.random.PRNGKey(0))
    start = 0
else:
    like = init_train_state(model, jax.random.PRNGKey(0))
    state, man = restore(ckpt, like, mesh=mesh,
                         specs=jax.tree.map(lambda s: s, specs(),
                                            is_leaf=lambda x: isinstance(x, P)))
    start = man["step"]

with mesh:
    sh = shardings(specs(), mesh, state)
    state = jax.device_put(state, sh)
    loss = None
    for s in range(start, start + 10):
        state, metrics = step_fn(state, batch_at(dcfg, s))
        loss = float(metrics["loss"])
save(ckpt, start + 10, jax.device_get(state))
print(json.dumps({"devices": n_dev, "mesh": str(mesh.shape),
                  "from": start, "to": start + 10, "loss": loss}))
"""


def run_child(n_dev, ckpt, phase):
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), os.pardir,
                                       "src"))
    r = subprocess.run([sys.executable, "-c", CHILD, str(n_dev), ckpt,
                        phase], capture_output=True, text=True, env=env,
                       timeout=600)
    if r.returncode != 0:
        raise RuntimeError(r.stderr[-2000:])
    out = json.loads(r.stdout.strip().splitlines()[-1])
    print(f"  {out['devices']} devices, mesh {out['mesh']}: steps "
          f"{out['from']}→{out['to']}, loss {out['loss']:.4f}")
    return out


def main():
    ckpt = tempfile.mkdtemp(prefix="repro_elastic_")
    print("phase 1: train 10 steps on 8 devices (4×2 mesh)")
    a = run_child(8, ckpt, "start")
    print("phase 2: fleet shrinks — resume on 4 devices (2×2 mesh)")
    b = run_child(4, ckpt, "resume")
    print("phase 3: fleet grows — resume on 16 devices (8×2 mesh)")
    c = run_child(16, ckpt, "resume")
    assert b["from"] == 10 and c["from"] == 20
    assert c["loss"] < a["loss"], "loss should keep improving across rescales"
    print("elastic rescale OK: checkpoints re-shard across mesh shapes")


if __name__ == "__main__":
    main()
