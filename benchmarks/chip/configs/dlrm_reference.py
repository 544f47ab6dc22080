"""Plain reference for the DLRM configurations of the chip benchmark.

It imports nothing of the program under test and takes nothing the
program made.  From the raw rows the benchmark generated it builds each
batch the job's transform DAG should produce (numpy), and it trains the
published DLRM (Naumov et al., arXiv:1906.00091) with AdamW for a few
steps in straightforward ``jax.numpy``: embedding bags pooled by their
mean, a bottom MLP, the pairwise dot interaction of the bottom output and
the pooled bags (upper triangle, row-major), a top MLP, and the logistic
loss.  ``dtype=float32`` runs every matmul at "highest" precision (the
reference); ``dtype=bfloat16`` is the control, the same steps one
precision lower.

The weights are made here too, from the seed, in one jitted call: the
benchmark hands the same ones to the program.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence

import numpy as np

import jax
import jax.numpy as jnp

# -- data: the job's transforms, from raw rows --------------------------------


def _mix32(x: np.ndarray) -> np.ndarray:
    x = x.astype(np.uint32, copy=True)
    with np.errstate(over="ignore"):
        x ^= x >> np.uint32(16)
        x *= np.uint32(0x7FEB352D)
        x ^= x >> np.uint32(15)
        x *= np.uint32(0x846CA68B)
        x ^= x >> np.uint32(16)
    return x


def _mix64(x: np.ndarray) -> np.ndarray:
    x = x.astype(np.uint64, copy=True)
    with np.errstate(over="ignore"):
        x ^= x >> np.uint64(30)
        x *= np.uint64(0xBF58476D1CE4E5B9)
        x ^= x >> np.uint64(27)
        x *= np.uint64(0x94D049BB133111EB)
        x ^= x >> np.uint64(31)
    return x


def _dense_op(op: str, col: np.ndarray, params: Dict) -> np.ndarray:
    if op == "BoxCox":                      # lambda 0.5
        x = np.maximum(np.nan_to_num(col, nan=0.0), 0.0) + 1.0
        return ((x ** 0.5 - 1.0) / 0.5).astype(np.float32)
    if op == "Logit":                       # eps 1e-6
        p = np.clip(np.nan_to_num(col, nan=0.5), 1e-6, 1.0 - 1e-6)
        return np.log(p / (1.0 - p)).astype(np.float32)
    if op == "Clamp":
        return np.clip(np.nan_to_num(col, nan=0.0), params["lo"],
                       params["hi"]).astype(np.float32)
    raise ValueError(f"no reference for dense op {op!r}")


def _ragged(starts: np.ndarray, lengths: np.ndarray) -> np.ndarray:
    """Flat indices of ``lengths[i]`` consecutive elements from ``starts[i]``."""
    within = np.arange(int(lengths.sum())) - np.repeat(np.cumsum(lengths) - lengths, lengths)
    return np.repeat(starts, lengths) + within


def transform_rows(raw: Dict, lo: int, hi: int, job: Dict, max_ids: int
                   ) -> Dict[str, np.ndarray]:
    """The batch of rows ``[lo, hi)`` of one raw partition, as the job's
    DAG defines it: dense (B, D) f32, sparse_ids (B, T, L) i32,
    sparse_mask (B, T, L) f32, label (B,) f32.  Each bag is a flat
    value array with per-row lengths."""
    n = hi - lo
    dense = np.stack(
        [np.nan_to_num(_dense_op(op, raw["dense"][fid][lo:hi], params), nan=0.0)
         for fid, op, params in job["dense"]], axis=1,
    ).astype(np.float32)

    hashed = {}                              # fid -> (values, lengths)
    for fid in job["sparse"]:                # FirstX, then SigridHash
        off, vals = raw["sparse"][fid][:2]
        lengths = np.minimum(np.diff(off[lo: hi + 1]), job["firstx"])
        v = vals[_ragged(off[lo:hi], lengths)]
        h = _mix32(v.astype(np.uint32) ^ np.uint32(fid & 0xFFFFFFFF))
        hashed[fid] = ((h % np.uint32(job["hash_size"])).astype(np.int64), lengths)
    bags = [hashed[fid] for fid in job["sparse"]]
    mod = np.uint64(job["hash_size"])
    for kind, args in job["derived"]:
        if kind == "NGram":                  # bigrams within each hashed list
            v, lengths = hashed[args[0]]
            starts = np.cumsum(lengths) - lengths
            out_len = np.maximum(lengths - 1, 0)
            first = _ragged(starts, out_len)
            with np.errstate(over="ignore"):
                acc = (v[first].astype(np.uint64) * np.uint64(1000003)
                       + v[first + 1].astype(np.uint64))
            bags.append(((_mix64(acc) % mod).astype(np.int64), out_len))
        elif kind == "Cartesian":            # a x b, a-major; the first max_ids kept
            (va, la), (vb, lb) = hashed[args[0]], hashed[args[1]]
            count = np.minimum(la * lb, max_ids)
            row = np.repeat(np.arange(n), count)
            k = np.arange(int(count.sum())) - np.repeat(np.cumsum(count) - count, count)
            ia = (np.cumsum(la) - la)[row] + k // np.maximum(lb[row], 1)
            ib = (np.cumsum(lb) - lb)[row] + k % np.maximum(lb[row], 1)
            prod = va[ia] * np.int64(1000003) + vb[ib]
            bags.append(((_mix64(prod.astype(np.uint64)) % mod).astype(np.int64), count))
        elif kind == "Bucketize":            # one bucket id per row
            v = np.nan_to_num(raw["dense"][args[0]][lo:hi], nan=0.0).astype(np.float32)
            b = np.asarray(np.linspace(-3, 3, 63), np.float32)
            bags.append((np.searchsorted(b, v).astype(np.int64), np.ones(n, np.int64)))
        else:
            raise ValueError(f"no reference for derived op {kind!r}")

    ids = np.zeros((n, len(bags), max_ids), np.int32)
    mask = np.zeros((n, len(bags), max_ids), np.float32)
    for t, (v, lengths) in enumerate(bags):
        keep = np.minimum(lengths, max_ids)
        r = np.repeat(np.arange(n), keep)
        c = np.arange(int(keep.sum())) - np.repeat(np.cumsum(keep) - keep, keep)
        ids[r, t, c] = v[_ragged(np.cumsum(lengths) - lengths, keep)]
        mask[r, t, c] = 1.0
    return {"dense": dense, "sparse_ids": ids, "sparse_mask": mask,
            "label": raw["labels"][lo:hi].astype(np.float32)}


# -- model --------------------------------------------------------------------


def _seed_key(seed_words: jax.Array) -> jax.Array:
    """A threefry key from the seed's two 32-bit words (high, low)."""
    return jax.random.wrap_key_data(seed_words.astype(jnp.uint32))


def seed_words(seed: int) -> np.ndarray:
    return np.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF], np.uint32)


def _mlp_dims(model: Dict):
    t = model["num_tables"]
    bottom = [model["num_dense"]] + list(model["bottom_mlp"])
    top = [model["bottom_mlp"][-1] + (t + 1) * t // 2] + list(model["top_mlp"])
    return bottom, top


def table_init(model: Dict, words: jax.Array, t) -> jax.Array:
    """Table ``t`` at initialisation: N(0, 0.02^2)."""
    k = jax.random.fold_in(jax.random.fold_in(_seed_key(words), 0), t)
    return 0.02 * jax.random.normal(
        k, (model["vocab_per_table"], model["embed_dim"]), jnp.float32)


def _mlp_init(words: jax.Array, j: int, i: int, name: str, din: int, dout: int):
    """Layer ``i`` of MLP ``j`` (0 bottom, 1 top): weights N(0, 1/din),
    biases zero."""
    if name == "b":
        return jnp.zeros((dout,), jnp.float32)
    k = jax.random.fold_in(jax.random.fold_in(_seed_key(words), 1 + j), i)
    return jax.random.normal(k, (din, dout), jnp.float32) / np.sqrt(din)


@functools.partial(jax.jit, static_argnums=(0,))
def _init(model_items, words):
    model = dict(model_items)
    tables = jax.vmap(lambda t: table_init(model, words, t))(
        jnp.arange(model["num_tables"]))
    params = {"tables": tables}
    for j, (name, dims) in enumerate(zip(("bottom", "top"), _mlp_dims(model))):
        params[name] = {
            f"{kind}{i}": _mlp_init(words, j, i, kind, din, dout)
            for i, (din, dout) in enumerate(zip(dims[:-1], dims[1:]))
            for kind in ("w", "b")
        }
    return params


def _items(model: Dict):
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in model.items()))


def init_params(model: Dict, seed: int):
    """Every parameter from the seed, on the device, in one jitted call."""
    return _init(_items(model), jnp.asarray(seed_words(seed)))


def _mlp(layers, x, n, last_linear, dtype):
    for i in range(n):
        x = jnp.dot(x, layers[f"w{i}"].astype(dtype)) + layers[f"b{i}"].astype(dtype)
        if not (last_linear and i == n - 1):
            x = jnp.maximum(x, 0)
    return x


def loss(model: Dict, params, batch, dtype=jnp.float32) -> jax.Array:
    t = model["num_tables"]
    bottom, top = _mlp_dims(model)
    x = batch["dense"].astype(dtype)
    bot = _mlp(params["bottom"], x, len(bottom) - 1, False, dtype)   # (B, E)
    ids = jnp.clip(batch["sparse_ids"], 0, model["vocab_per_table"] - 1)
    mask = batch["sparse_mask"].astype(dtype)
    pooled = []
    for j in range(t):
        rows = jnp.take(params["tables"][j].astype(dtype), ids[:, j, :], axis=0)
        num = jnp.sum(rows * mask[:, j, :, None], axis=1)
        den = jnp.maximum(jnp.sum(mask[:, j, :], axis=1), 1)
        pooled.append(num / den[:, None])
    feats = jnp.stack([bot] + pooled, axis=1)                       # (B, T+1, E)
    gram = jnp.einsum("bte,bse->bts", feats, feats)
    a, b = np.triu_indices(t + 1, k=1)
    z = jnp.concatenate([bot, gram[:, a, b]], axis=1)
    logit = _mlp(params["top"], z, len(top) - 1, True, dtype)[:, 0]
    y = batch["label"].astype(dtype)
    per_row = jnp.maximum(logit, 0) - logit * y + jnp.log1p(jnp.exp(-jnp.abs(logit)))
    return jnp.mean(per_row).astype(jnp.float32)


def _adamw(opt: Dict, params, grads, mu, nu, step):
    """One AdamW step: clip by global norm, warmup-stable-decay rate,
    bias-corrected moments, decoupled decay on matrices."""
    leaves = jax.tree.leaves(grads)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in leaves))
    scale = jnp.minimum(1.0, opt["clip_norm"] / jnp.maximum(gnorm, 1e-9))
    grads = jax.tree.map(lambda g: g * scale.astype(g.dtype), grads)
    s = step.astype(jnp.float32)
    warm = jnp.minimum(s / max(opt["warmup_steps"], 1), 1.0)
    d0 = 0.8 * opt["total_steps"]
    frac = jnp.clip((s - d0) / max(opt["total_steps"] - d0, 1), 0.0, 1.0)
    lr = opt["learning_rate"] * warm * (1.0 - 0.9 * frac)
    b1, b2 = opt["beta1"], opt["beta2"]
    bc1, bc2 = 1.0 - b1 ** s, 1.0 - b2 ** s

    def upd(p, g, m, v):
        dt = p.dtype
        m = (m * b1 + (1 - b1) * g).astype(dt)
        v = (v * b2 + (1 - b2) * g * g).astype(dt)
        d = (m / bc1.astype(dt)) / (jnp.sqrt(v / bc2.astype(dt)) + opt["eps"])
        if p.ndim >= 2:
            d = d + opt["weight_decay"] * p
        return (p - lr.astype(dt) * d).astype(dt), m, v

    out = jax.tree.map(upd, params, grads, mu, nu)
    pick = lambda i: jax.tree.map(lambda o: o[i], out,
                                  is_leaf=lambda o: isinstance(o, tuple))
    return pick(0), pick(1), pick(2), grads


def leaf_names(params) -> List[str]:
    return ["/".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in jax.tree_util.tree_leaves_with_path(params)]


def leaf_norms(tree) -> jax.Array:
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


def change_norms(model: Dict, params, seed: int) -> jax.Array:
    """Per-leaf norm of (params - their initial value), each table's
    initial value made again from the seed one table at a time."""
    return _change_norms(_items(model), params, jnp.asarray(seed_words(seed)))


@functools.partial(jax.jit, static_argnums=(0,))
def _change_norms(model_items, params, words):
    model = dict(model_items)

    def one(t):
        d = params["tables"][t].astype(jnp.float32) - table_init(model, words, t)
        return jnp.sum(d * d)

    sq = jax.lax.map(one, jnp.arange(model["num_tables"]))
    out = []
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        names = [str(getattr(k, "key", k)) for k in path]
        if names == ["tables"]:
            out.append(jnp.sqrt(jnp.sum(sq)))
            continue
        j = ("bottom", "top").index(names[0])
        i = int(names[1][1:])
        dims = _mlp_dims(model)[j]
        init = _mlp_init(words, j, i, names[1][0], dims[i], dims[i + 1])
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
        lval, grads = jax.value_and_grad(
            lambda p: loss(model, p, batch, dt))(params)
        params, mu, nu, clipped = _adamw(opt, params, grads, mu, nu, k)
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
