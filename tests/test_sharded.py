"""Multi-device behaviour (8 host devices via subprocess — jax locks the
device count at init, so these fork): sharded train step numerics vs single
device, checkpoint elastic reshard, context-parallel decode equivalence."""
import json
import subprocess
import sys

import pytest

# Each test forks a fresh 8-device jax process (~20 s apiece): slow tier.
pytestmark = pytest.mark.slow

COMMON = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, sys
sys.path.insert(0, "/root/repo/src")
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.configs import get_reduced
from repro.models import build
from repro.models.layers import Axes
from repro.sharding import param_pspecs, named_shardings, cache_pspecs
from repro.launch.mesh import make_mesh, axis_sizes
"""


def run_py(body: str, timeout=600):
    out = subprocess.run([sys.executable, "-c", COMMON + body],
                         capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_sharded_train_step_matches_single_device():
    body = r"""
from repro.train.step import TrainConfig, init_train_state, make_train_step
from repro.optim import AdamWConfig

cfg = get_reduced("qwen2.5-3b")
model = build(cfg)
tcfg = TrainConfig(optimizer=AdamWConfig(lr=1e-3, warmup_steps=0,
                                         mixed_precision=False),
                   xent_chunk=8)
state = init_train_state(model, tcfg, jax.random.PRNGKey(0))
tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0,
                            cfg.vocab_size, dtype=jnp.int32)
batch = {"tokens": tokens, "labels": jnp.roll(tokens, -1, axis=1)}

# single device
step1 = jax.jit(make_train_step(model, None, tcfg))
s1, m1 = step1(state, batch)

# 2x4 mesh
mesh = make_mesh((2, 4), ("data", "model"))
sizes = axis_sizes(mesh)
pspecs = param_pspecs(state["params"], sizes)
state_specs = {"params": pspecs,
               "opt": {"step": P(), "m": pspecs, "v": pspecs},
               "error": jax.tree_util.tree_map(lambda _: P(), state["error"])}
axes = Axes(batch=("data",), model="model", fsdp="data",
            sizes=tuple(axis_sizes(mesh).items()))
with jax.set_mesh(mesh):
    step8 = jax.jit(make_train_step(model, axes, tcfg),
                    in_shardings=(named_shardings(state_specs, mesh),
                                  named_shardings({"tokens": P("data", None),
                                                   "labels": P("data", None)}, mesh)))
    s8, m8 = step8(state, batch)

d = max(float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max())
        for a, b in zip(jax.tree_util.tree_leaves(s1["params"]),
                        jax.tree_util.tree_leaves(s8["params"])))
print(json.dumps({"loss1": float(m1["loss"]), "loss8": float(m8["loss"]),
                  "max_param_diff": d}))
"""
    rec = run_py(body)
    assert rec["loss1"] == pytest.approx(rec["loss8"], rel=1e-3)
    assert rec["max_param_diff"] < 5e-3


def test_cp_decode_matches_replicated():
    """Context-parallel (sequence-sharded cache) decode == plain decode."""
    body = r"""
from repro.serve.engine import make_decode_step

cfg = get_reduced("gemma3-1b")
model = build(cfg)
params = model.init(jax.random.PRNGKey(2))
b, s_max = 1, 32
cache = model.init_cache(b, s_max)
tokens = jnp.asarray([[5]], jnp.int32)
pos = jnp.asarray([3], jnp.int32)
# warm the cache rows 0..2 with noise so attention has context
import numpy as np
rng = np.random.default_rng(0)
cache = jax.tree_util.tree_map(
    lambda x: jnp.asarray(rng.standard_normal(x.shape), x.dtype) * 0.1
    if x.ndim >= 4 else x, cache)

plain, _ = jax.jit(make_decode_step(model, None))(params, cache, tokens, pos)

mesh = make_mesh((8,), ("data",))
axes = Axes(batch=(), model="model", fsdp="data", seq="data",
            sizes=tuple(axis_sizes(mesh).items()))
cspecs = cache_pspecs(cache, (), axis_sizes(mesh), seq_shard=True)
from repro.sharding import named_shardings
with jax.set_mesh(mesh):
    stepc = jax.jit(make_decode_step(model, axes),
                    in_shardings=(None, named_shardings(cspecs, mesh),
                                  None, None))
    cp, _ = stepc(params, cache, tokens, pos)
diff = float(jnp.abs(plain.astype(jnp.float32) - cp.astype(jnp.float32)).max())
print(json.dumps({"diff": diff}))
"""
    rec = run_py(body)
    assert rec["diff"] < 2e-3


def test_checkpoint_elastic_reshard():
    """A checkpoint written under a (2,4) mesh restores onto (4,2)."""
    body = r"""
import tempfile
from repro.checkpoint import save, restore

cfg = get_reduced("qwen1.5-0.5b")
model = build(cfg)
params = model.init(jax.random.PRNGKey(0))

mesh_a = make_mesh((2, 4), ("data", "model"))
sh_a = named_shardings(param_pspecs(params, axis_sizes(mesh_a)), mesh_a)
params_a = jax.tree_util.tree_map(jax.device_put, params,
                                  jax.tree_util.tree_leaves(sh_a) and sh_a)
d = tempfile.mkdtemp()
save(d, params_a, step=1)

mesh_b = make_mesh((4, 2), ("data", "model"))
sh_b = named_shardings(param_pspecs(params, axis_sizes(mesh_b)), mesh_b)
like = jax.tree_util.tree_map(
    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
restored, manifest = restore(d, like, shardings=sh_b)
ok = all(np.allclose(np.asarray(a, np.float32), np.asarray(b, np.float32))
         for a, b in zip(jax.tree_util.tree_leaves(params),
                         jax.tree_util.tree_leaves(restored)))
one = [x for x in jax.tree_util.tree_leaves(restored) if x.ndim >= 2][0]
print(json.dumps({"ok": bool(ok), "step": manifest["step"],
                  "n_shards": len(one.sharding.device_set)}))
"""
    rec = run_py(body)
    assert rec["ok"] and rec["step"] == 1
    assert rec["n_shards"] >= 2
