"""Coverage for the §Perf paths: step builders compile on a small mesh,
EP MoE matches the portable path, DLRM sparse update matches dense grads."""
import os
import subprocess
import sys

import numpy as np
import pytest

SUB = '''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
import dataclasses
from jax.sharding import AxisType
from repro.launch.steps import make_train_step, make_decode_step
from repro import configs as cfglib

mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,) * 2)

# 1) train step compiles + runs for a smoke MoE config on the mesh
cfg = cfglib.get_smoke_config("deepseek-v2-236b")
cfg = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, num_experts=8))
b = make_train_step(cfg, mesh, batch=4, seq=32)
step = b.jit()
params_a, opt_a, batch_a = b.abstract_args
params = jax.tree.map(lambda s: 0.02*jnp.ones(s.shape, s.dtype), params_a)
opt = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), opt_a)
bt = {"tokens": jnp.ones((4,32), jnp.int32), "labels": jnp.ones((4,32), jnp.int32)}
p2, o2, m = step(params, opt, bt)
assert np.isfinite(float(m["loss"])), m
print("moe_train_ok", float(m["loss"]))

# 2) DLRM sparse train step on the mesh
dc = cfglib.get_smoke_config("dlrm-paper")
dc = dataclasses.replace(dc, vocab_per_table=1600)   # divisible by model=4
b2 = make_train_step(dc, mesh, batch=8, seq=0)
step2 = b2.jit()
pa, oa, ba = b2.abstract_args
params = jax.tree.map(lambda s: 0.05*jnp.ones(s.shape, s.dtype), pa)
opt = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), oa)
rng = np.random.default_rng(0)
bt = {
  "dense": jnp.asarray(rng.normal(0,1,(8, dc.num_dense)), jnp.float32),
  "sparse_ids": jnp.asarray(rng.integers(0, 1600, (8, dc.num_tables, dc.max_ids_per_feature)), jnp.int32),
  "sparse_mask": jnp.ones((8, dc.num_tables, dc.max_ids_per_feature), jnp.float32),
  "label": jnp.asarray(rng.integers(0,2,8), jnp.float32),
}
p2, o2, m = step2(params, opt, bt)
assert np.isfinite(float(m["loss"]))
# tables actually changed (sparse update applied)
delta = float(jnp.sum(jnp.abs(p2["tables"] - params["tables"])))
assert delta > 0
print("dlrm_sparse_ok", float(m["loss"]), delta)

# 3) decode step compiles on the mesh
cfg3 = cfglib.get_smoke_config("qwen3-8b")
b3 = make_decode_step(cfg3, mesh, batch=4, seq=16)
lowered = b3.lower()
lowered.compile()
print("decode_compile_ok")
'''


def test_steps_on_virtual_mesh():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env["JAX_PLATFORMS"] = "cpu"        # eight virtual CPU devices
    r = subprocess.run([sys.executable, "-c", SUB], capture_output=True,
                       text=True, env=env, timeout=900, cwd=os.path.dirname(os.path.dirname(__file__)))
    assert "moe_train_ok" in r.stdout, r.stdout + r.stderr
    assert "dlrm_sparse_ok" in r.stdout, r.stdout + r.stderr
    assert "decode_compile_ok" in r.stdout, r.stdout + r.stderr


def test_dlrm_sparse_update_matches_dense_gradient():
    """Row-wise sparse update direction == dense autodiff table gradient."""
    import jax
    import jax.numpy as jnp
    from repro import configs as cfglib
    from repro.models import build_model

    cfg = cfglib.get_smoke_config("dlrm-paper")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(1)
    bt = {
        "dense": jnp.asarray(rng.normal(0, 1, (8, cfg.num_dense)), jnp.float32),
        "sparse_ids": jnp.asarray(
            rng.integers(0, cfg.vocab_per_table, (8, cfg.num_tables, cfg.max_ids_per_feature)),
            jnp.int32),
        "sparse_mask": jnp.ones((8, cfg.num_tables, cfg.max_ids_per_feature), jnp.float32),
        "label": jnp.asarray(rng.integers(0, 2, 8), jnp.float32),
    }
    dense_grads = jax.grad(model.loss)(params, bt)["tables"]

    mlp = {"bottom": params["bottom"], "top": params["top"]}
    pooled = model.pooled_embeddings(params["tables"], bt)
    dpooled = jax.grad(model.loss_from_pooled, argnums=1)(mlp, pooled, bt)
    acc = jnp.zeros((cfg.num_tables, cfg.vocab_per_table), jnp.float32)
    new_tables, _ = model.sparse_table_update(
        params["tables"], acc, dpooled, bt, lr=jnp.asarray(1.0)
    )
    sparse_delta = np.asarray(new_tables - params["tables"], np.float64)
    dg = np.asarray(dense_grads, np.float64)
    # updates happen exactly where dense grads are nonzero, opposite sign
    touched = np.abs(dg) > 1e-12
    assert (np.abs(sparse_delta[~touched]) < 1e-9).all()
    dot = np.sum(sparse_delta * dg)
    assert dot < 0  # descent direction
