"""Distribution correctness: these tests need a multi-device jax runtime,
which requires XLA_FLAGS before import — so they exec a child process with
16 host devices and assert on its output (the dry-run itself covers the
full 256/512-chip meshes)."""
import json
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), os.pardir, "src")

CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
import json
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P, NamedSharding

from repro.configs import get_config, reduced
from repro.launch.mesh import (auto_mesh, batch_axes_of,
                               make_production_mesh, resolve_spec,
                               sanitize_spec, shardings)
from repro.models import build_model

out = {}

# --- mesh + spec resolution -------------------------------------------------
mesh = auto_mesh((4, 4), ("data", "model"))
sp = sanitize_spec(P("model", "data"), (49155, 1024), mesh)
out["sanitize_vocab"] = list(sp)           # model must drop (49155 % 4 != 0)
sp2 = sanitize_spec(P("data", "model"), (64, 64), mesh)
out["sanitize_ok"] = list(sp2)

mp = auto_mesh((2, 2, 4), ("pod", "data", "model"))
rp = resolve_spec(P("data", None), mp)
out["resolve_pod"] = [list(e) if isinstance(e, tuple) else e for e in rp]

# --- MoE expert-parallel numerics vs single device ---------------------------
cfg = reduced(get_config("qwen3-moe-30b-a3b"))
model_1 = build_model(cfg)                       # no mesh: single shard
params = model_1.init(jax.random.PRNGKey(0))
toks = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, cfg.vocab_size)
batch = {"tokens": toks, "labels": toks}
loss_1, _ = model_1.loss(params, batch)

model_n = build_model(cfg, mesh=mesh)            # shard_map EP over 4 shards
with mesh:
    pshard = shardings(model_n.specs(), mesh, params)
    params_n = jax.device_put(params, pshard)
    loss_n, _ = jax.jit(model_n.loss)(params_n, batch)
out["moe_loss_single"] = float(loss_1)
out["moe_loss_sharded"] = float(loss_n)

# --- dense train step lowers + runs on the mesh ------------------------------
cfg_d = reduced(get_config("qwen3-1.7b"))
model_d = build_model(cfg_d, mesh=mesh)
params_d = model_d.init(jax.random.PRNGKey(0))
with mesh:
    pshard = shardings(model_d.specs(), mesh, params_d)
    params_ds = jax.device_put(params_d, pshard)
    loss_d, _ = jax.jit(model_d.loss)(params_ds, batch)
loss_ref, _ = build_model(cfg_d).loss(params_d, batch)
out["dense_loss_mesh"] = float(loss_d)
out["dense_loss_ref"] = float(loss_ref)

print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def child_out():
    env = dict(os.environ, PYTHONPATH=SRC)
    r = subprocess.run([sys.executable, "-c", CHILD], capture_output=True,
                       text=True, env=env, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    line = [l for l in r.stdout.splitlines() if l.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


def test_sanitize_drops_nondivisible(child_out):
    assert child_out["sanitize_vocab"] == [None, "data"]
    assert child_out["sanitize_ok"] == ["data", "model"]


def test_pod_axis_resolution(child_out):
    assert child_out["resolve_pod"][0] == ["pod", "data"]


def test_moe_expert_parallel_matches_single_device(child_out):
    assert abs(child_out["moe_loss_single"]
               - child_out["moe_loss_sharded"]) < 2e-2


def test_dense_mesh_loss_matches_reference(child_out):
    assert abs(child_out["dense_loss_mesh"]
               - child_out["dense_loss_ref"]) < 2e-2
