"""Plain reference for the MLPerf DLRM-DCNv2 configuration of the chip
benchmark.

It imports nothing of the program under test and takes nothing the
program made.  The data side (each batch the job's transform DAG should
produce, from the raw rows) and AdamW are the DLRM reference's
(``dlrm_reference.py``, loaded by its path).  The model is torchrec's
``DLRM_DCN`` as MLPerf Training's ``recommendation_v2/torchrec_dlrm``
runs it, in straightforward ``jax.numpy``: each table's bag sum-pooled
(torchrec's ``EmbeddingBagCollection``) over the padded (B, T, L) batch,
its mask weighing each slot; a bottom MLP; x_0, the bottom output and
the T pooled bags concatenated (d = (T+1)E wide); ``dcn_layers``
low-rank cross layers x_{l+1} = x_0 * (W_l (V_l x_l) + b_l) + x_l, V_l
(r, d) and W_l (d, r); a top MLP over the last x_l; the logistic loss.
``dtype=float32`` runs every matmul at "highest" precision (the
reference); ``dtype=bfloat16`` is the control, the same steps one
precision lower.

Initialisation (assumed; the weights only have to be the same on both
sides): tables N(0, 0.02^2), as the DLRM reference's; V_l, W_l and the
MLP weights N(0, 1/fan_in); biases zero.  The weights are made here from
the seed, in one jitted call, and the benchmark hands the same ones to
the program.
"""
from __future__ import annotations

import functools
import importlib.util
from pathlib import Path
from typing import Dict, Sequence

import numpy as np

import jax
import jax.numpy as jnp


def _load_base():
    path = Path(__file__).with_name("dlrm_reference.py")
    spec = importlib.util.spec_from_file_location("dlrm_dcnv2_reference_base", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_base = _load_base()
transform_rows = _base.transform_rows
seed_words = _base.seed_words
leaf_names = _base.leaf_names
leaf_norms = _base.leaf_norms

CROSS_KEY = 3          # key folds: 0 tables, 1 bottom MLP, 2 top MLP, 3 cross layers


def _dims(model: Dict):
    """Bottom and top MLP widths, the cross network's width d and rank r."""
    d = (model["num_tables"] + 1) * model["embed_dim"]
    bottom = [model["num_dense"]] + list(model["bottom_mlp"])
    top = [d] + list(model["top_mlp"])
    return bottom, top, d, model["dcn_low_rank"]


def _leaf_init(model: Dict, words: jax.Array, group: str, leaf: str) -> jax.Array:
    """The initial value of one MLP or cross-layer leaf, e.g. ("cross", "v1")."""
    kind, i = leaf[0], int(leaf[1:])
    bottom, top, d, r = _dims(model)
    if group != "cross":
        j = ("bottom", "top").index(group)
        dims = (bottom, top)[j]
        return _base._mlp_init(words, j, i, kind, dims[i], dims[i + 1])
    if kind == "b":
        return jnp.zeros((d,), jnp.float32)
    k = jax.random.fold_in(jax.random.fold_in(_base._seed_key(words), CROSS_KEY), i)
    k = jax.random.fold_in(k, ("v", "w").index(kind))
    shape, fan_in = ((r, d), d) if kind == "v" else ((d, r), r)
    return jax.random.normal(k, shape, jnp.float32) / np.sqrt(fan_in)


def _leaves(model: Dict):
    """(group, leaf) of every MLP and cross-layer parameter."""
    bottom, top, _, _ = _dims(model)
    for group, dims in (("bottom", bottom), ("top", top)):
        for i in range(len(dims) - 1):
            yield from ((group, f"{k}{i}") for k in "wb")
    for i in range(model["dcn_layers"]):
        yield from (("cross", f"{k}{i}") for k in "vwb")


@functools.partial(jax.jit, static_argnums=(0,))
def _init(model_items, words):
    model = dict(model_items)
    params = {"tables": jax.vmap(lambda t: _base.table_init(model, words, t))(
        jnp.arange(model["num_tables"]))}
    for group, leaf in _leaves(model):
        params.setdefault(group, {})[leaf] = _leaf_init(model, words, group, leaf)
    return params


def init_params(model: Dict, seed: int):
    """Every parameter from the seed, on the device, in one jitted call."""
    return _init(_base._items(model), jnp.asarray(seed_words(seed)))


def loss(model: Dict, params, batch, dtype=jnp.float32) -> jax.Array:
    bottom, top, _, _ = _dims(model)
    bot = _base._mlp(params["bottom"], batch["dense"].astype(dtype), len(bottom) - 1,
                     False, dtype)                                  # (B, E)
    ids = jnp.clip(batch["sparse_ids"], 0, model["vocab_per_table"] - 1)
    mask = batch["sparse_mask"].astype(dtype)
    pooled = []
    for j in range(model["num_tables"]):
        rows = jnp.take(params["tables"][j].astype(dtype), ids[:, j, :], axis=0)
        pooled.append(jnp.sum(rows * mask[:, j, :, None], axis=1))  # (B, E)
    x0 = x = jnp.concatenate([bot] + pooled, axis=1)                # (B, d)
    for i in range(model["dcn_layers"]):
        v, w, b = (params["cross"][f"{k}{i}"].astype(dtype) for k in "vwb")
        x = x0 * (jnp.dot(jnp.dot(x, v.T), w.T) + b) + x
    logit = _base._mlp(params["top"], x, len(top) - 1, True, dtype)[:, 0]
    y = batch["label"].astype(dtype)
    per_row = jnp.maximum(logit, 0) - logit * y + jnp.log1p(jnp.exp(-jnp.abs(logit)))
    return jnp.mean(per_row).astype(jnp.float32)


def change_norms(model: Dict, params, seed: int) -> jax.Array:
    """Per-leaf norm of (params - their initial value), each table's
    initial value made again from the seed one table at a time."""
    return _change_norms(_base._items(model), params, jnp.asarray(seed_words(seed)))


@functools.partial(jax.jit, static_argnums=(0,))
def _change_norms(model_items, params, words):
    model = dict(model_items)

    def one(t):
        d = params["tables"][t].astype(jnp.float32) - _base.table_init(model, words, t)
        return jnp.sum(d * d)

    sq = jax.lax.map(one, jnp.arange(model["num_tables"]))
    out = []
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        names = [str(getattr(k, "key", k)) for k in path]
        if names == ["tables"]:
            out.append(jnp.sqrt(jnp.sum(sq)))
            continue
        init = _leaf_init(model, words, *names)
        out.append(jnp.sqrt(jnp.sum(jnp.square(leaf.astype(jnp.float32) - init))))
    return jnp.stack(out)


def train_readings(model: Dict, opt: Dict, seed: int,
                   batches: Sequence[Dict[str, np.ndarray]], dtype) -> Dict:
    """Run ``len(batches)`` AdamW steps from the seed's weights.  Returns
    each step's loss, the per-leaf norm of the first (clipped) gradient,
    and the per-leaf norm of the parameters' change after the last step."""
    dt = jnp.dtype(dtype)
    params = jax.tree.map(lambda p: p.astype(dt), init_params(model, seed))
    names = leaf_names(params)
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    precision = "highest" if dt == jnp.float32 else "default"

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(params, mu, nu, batch, k):
        lval, grads = jax.value_and_grad(lambda p: loss(model, p, batch, dt))(params)
        params, mu, nu, clipped = _base._adamw(opt, params, grads, mu, nu, k)
        return params, mu, nu, lval, leaf_norms(clipped)

    losses, first_grad = [], None
    with jax.default_matmul_precision(precision):
        for k, b in enumerate(batches, start=1):
            jb = {key: jnp.asarray(v) for key, v in b.items()}
            params, mu, nu, lval, gn = step(params, mu, nu, jb, jnp.int32(k))
            losses.append(float(lval))
            if first_grad is None:
                first_grad = np.asarray(gn, np.float64)
    del mu, nu
    change = np.asarray(change_norms(model, params, seed), np.float64)
    return {"losses": losses,
            "grad_norms": dict(zip(names, first_grad.tolist())),
            "change_norms": dict(zip(names, change.tolist()))}
