"""Pluggable TransformEngine: fused Pallas execution of the transform DAG.

The paper's §7.2 flagship observation is that launching one kernel over a
tensor combining ~1000 sparse features is ~3 orders of magnitude faster
than per-feature dispatch, and §6.3 shows transform dominating DPP worker
cycles.  This module closes the gap between that observation and the DPP
worker's production path:

  * ``NumpyEngine`` — the reference engine: executes the per-feature DAG
    exactly like ``TransformPipeline.__call__`` (one vectorized numpy call
    per spec), while accounting per-op "kernel launches".
  * ``PallasEngine`` — compiles the DAG into **waves** of fusable ops
    (SigridHash, PositiveModulus, Clamp, Bucketize), packs each wave into
    the (rows, features) op-code/param layout of
    ``repro.kernels.fused_transform`` and executes the whole wave in ONE
    ``pallas_call`` (interpret mode on CPU, compiled on TPU).  Ops the
    kernel cannot express (NGram, Cartesian, MapId, FirstX, ...) fall back
    to the numpy implementations: in each pass of consecutive fallback
    ops, the features that share an element-wise dense op (BoxCox,
    Logit, ...) or FirstX, with the same kwargs, run as ONE numpy call
    over all of them; every other op runs once per feature.

Both engines produce **byte-identical** environments (and therefore
byte-identical minibatches): the SigridHash mixer is the shared 32-bit
two-round multiply-xor-shift (``transforms._mix32`` == kernel
``_hash_u32``), bucketize compares in float32 on both paths, and any op
whose inputs would break bit-parity (ids outside int32 for
PositiveModulus, non-float32 dense columns, ...) is *demoted* to the
numpy fallback at run time.  TensorCache entries therefore stay
engine-agnostic.

``EngineStats`` feeds ``WorkerMetrics`` (fused vs fallback feature counts,
kernel launches, per-path transform seconds) so Table-9-style breakdowns
can compare engines.  Each fused wave is a ``transform.fused`` span, each
pass of consecutive numpy ops a ``transform.fallback`` span, and each wave
launch a ``kernel.fused_transform`` span inside its wave.  Grouped numpy
calls are counted in ``fallback_groups`` and the features they serve in
``grouped_features``.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from repro.core.schema import ColumnBatch, SparseColumn
from repro.core.transforms import (
    _OPS,
    Column,
    TransformPipeline,
    TransformSpec,
    firstx_many,
)
from repro.obs import NULL_TRACER, counter, phase

# Op codes mirror repro.kernels.fused_transform (kept import-light: jax is
# only pulled in when a PallasEngine actually launches a wave).
OP_IDENTITY = 0
OP_SIGRID_HASH = 1
OP_POSITIVE_MODULUS = 2
OP_CLAMP = 3
OP_BUCKETIZE = 4
OP_CLAMP_F = 5
OP_BUCKETIZE_F = 6

_I32_MIN = -(2 ** 31)
_I32_MAX = 2 ** 31 - 1
_MAX_BORDERS = 512
_F32_TINY = float(np.finfo(np.float32).tiny)   # smallest normal float32


def _subnormal(arr: np.ndarray) -> bool:
    """XLA's CPU/TPU paths may flush subnormal float32 to zero (FTZ/DAZ)
    while numpy preserves them — values in (0, tiny) break bit-parity."""
    a = np.abs(arr, dtype=np.float32)
    return bool(np.any((a > 0) & (a < _F32_TINY)))


# ---------------------------------------------------------------------------
# Engine accounting
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class EngineStats:
    """Cumulative per-engine accounting (mirrored into ``WorkerMetrics``)."""

    fused_features: int = counter()      # op executions served by a fused kernel
    fallback_features: int = counter()   # op executions served by numpy
    demoted_features: int = counter()    # fused-eligible ops demoted at run time
    kernel_launches: int = counter()     # fused pallas_calls + numpy op calls
    fallback_groups: int = counter()     # numpy calls over several features
    grouped_features: int = counter()    # of fallback_features: served grouped
    fused_launches: int = counter()      # fused wave launches alone
    fused_s: float = counter(0.0)        # transform_s attribution: fused path
    fallback_s: float = counter(0.0)     # transform_s attribution: numpy path
    launch_s: float = counter(0.0)       # of fused_s: launches, copy in to out


# ---------------------------------------------------------------------------
# Compilation: transform DAG -> waves of packed fused ops + fallback steps
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class FusedOp:
    """One packed column of a fused wave: op code + int32 params (float
    params ride as float32 bit patterns, like in the kernel)."""

    spec: TransformSpec
    code: int
    p0: int
    p1: int
    kind: str                              # "sparse" | "dense" | "dense_bucket"
    borders: Optional[np.ndarray] = None   # (nb,) float32, BUCKETIZE_F only


@dataclasses.dataclass(frozen=True)
class FusedWave:
    ops: Tuple[FusedOp, ...]


@dataclasses.dataclass(frozen=True)
class FallbackStep:
    spec: TransformSpec


@dataclasses.dataclass(frozen=True)
class CompiledPlan:
    """Ordered execution steps: each step is a FusedWave (one kernel
    launch) or a FallbackStep (one numpy op; ``group_pass`` merges a
    pass's like ops into shared calls)."""

    steps: Tuple[Union[FusedWave, FallbackStep], ...]

    @property
    def fused_ops(self) -> List[FusedOp]:
        return [op for s in self.steps if isinstance(s, FusedWave) for op in s.ops]

    @property
    def fallback_specs(self) -> List[TransformSpec]:
        return [s.spec for s in self.steps if isinstance(s, FallbackStep)]


def _f32_exact(x: Any) -> bool:
    try:
        x = float(x)
    except (TypeError, ValueError):
        return False
    # NaN params stay on the numpy path: XLA min/max NaN propagation
    # differs from numpy's.  (NaN != NaN, so the equality rejects it.)
    f = float(np.float32(x))
    return f == x


def _f32_bits(x: float) -> int:
    return int(np.float32(x).view(np.int32))


def _bits_f32(b: int) -> float:
    return float(np.int32(b).view(np.float32))


def _try_fuse(spec: TransformSpec) -> Optional[FusedOp]:
    """Static fusability: can this spec be expressed as one fused-kernel
    column with bit-exact numpy parity?  Returns None for fallback."""
    kw = spec.kwargs
    if len(spec.inputs) != 1:
        return None
    if spec.op == "SigridHash" and set(kw) == {"salt", "max_value"}:
        salt, mv = kw["salt"], kw["max_value"]
        if isinstance(salt, (int, np.integer)) and isinstance(mv, (int, np.integer)) \
                and 0 <= salt <= _I32_MAX and 1 <= mv <= _I32_MAX:
            return FusedOp(spec, OP_SIGRID_HASH, int(salt), int(mv), "sparse")
    elif spec.op == "PositiveModulus" and set(kw) == {"m"}:
        m = kw["m"]
        if isinstance(m, (int, np.integer)) and 1 <= m <= _I32_MAX:
            return FusedOp(spec, OP_POSITIVE_MODULUS, int(m), int(m), "sparse")
    elif spec.op == "Clamp" and set(kw) == {"lo", "hi"}:
        lo, hi = kw["lo"], kw["hi"]
        if (
            _f32_exact(lo) and _f32_exact(hi)
            and not _subnormal(np.array([lo, hi], np.float32))
        ):
            return FusedOp(
                spec, OP_CLAMP_F, _f32_bits(float(lo)), _f32_bits(float(hi)),
                "dense",
            )
    elif spec.op == "Bucketize" and set(kw) == {"borders"}:
        b = np.asarray(kw["borders"], np.float32)
        if (
            b.ndim == 1 and 1 <= b.size <= _MAX_BORDERS
            and np.all(np.isfinite(b)) and np.all(np.diff(b) >= 0)
            and not _subnormal(b)
        ):
            return FusedOp(spec, OP_BUCKETIZE_F, 0, 0, "dense_bucket", b)
    return None


def _single_assignment(specs: Sequence[TransformSpec]) -> bool:
    """Single-assignment check with read-before-overwrite detection: if a
    spec's output key was already read (by an earlier spec, or by itself)
    or already written, sequential execution order is load-bearing — an
    earlier reader must see the PRE-overwrite value, which reordering
    would destroy.  ``{outputs} & ({inputs} - {outputs})`` is NOT
    sufficient: a later spec overwriting a raw batch key that an earlier
    spec reads leaves that key out of the external set entirely."""
    seen_inputs: set = set()
    written: set = set()
    for s in specs:
        seen_inputs.update(s.inputs)       # reads happen before this write
        if s.output in seen_inputs or s.output in written:
            return False
        written.add(s.output)
    return True


def compile_pipeline(
    specs: Sequence[TransformSpec],
) -> CompiledPlan:
    """Greedy level scheduling: at every round, every not-yet-executed
    fusable spec whose inputs are already materialized joins one fused
    wave (one kernel launch); otherwise the next spec in topological
    order runs as a per-feature fallback.  DAGs that reassign an output
    key compile to pure fallback (wave reordering would change the
    sequential-overwrite semantics of ``TransformPipeline``)."""
    specs = list(specs)
    if not _single_assignment(specs):
        return CompiledPlan(tuple(FallbackStep(s) for s in specs))
    written = {s.output for s in specs}
    external = {i for s in specs for i in s.inputs} - written

    fusable = {id(s): _try_fuse(s) for s in specs}
    avail = set(external)
    remaining = list(specs)
    steps: List[Union[FusedWave, FallbackStep]] = []
    while remaining:
        # drain every ready fallback FIRST: postponing fusable ops until no
        # fallback can run widens each wave (e.g. all FirstX feeds complete
        # before their SigridHashes fuse into ONE launch).  Safe because
        # single-assignment makes execution order irrelevant to results.
        progressed = True
        while progressed:
            progressed = False
            for s in list(remaining):
                if fusable[id(s)] is None and all(i in avail for i in s.inputs):
                    steps.append(FallbackStep(s))
                    avail.add(s.output)
                    remaining.remove(s)
                    progressed = True
        wave = [
            s for s in remaining
            if fusable[id(s)] is not None and all(i in avail for i in s.inputs)
        ]
        if not wave:
            # nothing ready at all: an unsatisfiable input.  Preserve the
            # sequential pipeline's behavior (KeyError at execution time).
            steps.extend(FallbackStep(s) for s in remaining)
            break
        # split by row class: sparse columns pack nnz values (~rows x
        # avg_len lanes) while dense columns pack one value per row —
        # co-packing would pad every dense column to the tallest nnz and
        # drag the borders compare over the tall tile.  Two well-shaped
        # launches beat one badly-shaped one; amortization stays
        # O(features) per launch.
        sparse_ops = tuple(
            fusable[id(s)] for s in wave if fusable[id(s)].kind == "sparse"
        )
        dense_ops = tuple(
            fusable[id(s)] for s in wave if fusable[id(s)].kind != "sparse"
        )
        for ops in (sparse_ops, dense_ops):
            if ops:
                steps.append(FusedWave(ops))
        for s in wave:
            avail.add(s.output)
            remaining.remove(s)
    return CompiledPlan(tuple(steps))


def decode_plan(plan: CompiledPlan) -> List[TransformSpec]:
    """Reconstruct the fused specs from their packed op-code/param columns
    — the round-trip witness that packing loses nothing (borders are
    canonicalized to float32, the precision the kernel compares in)."""
    out: List[TransformSpec] = []
    for op in plan.fused_ops:
        src = op.spec
        if op.code == OP_SIGRID_HASH:
            params = (("salt", op.p0), ("max_value", op.p1))
        elif op.code == OP_POSITIVE_MODULUS:
            params = (("m", op.p0),)
        elif op.code == OP_CLAMP_F:
            params = (("lo", _bits_f32(op.p0)), ("hi", _bits_f32(op.p1)))
        elif op.code == OP_BUCKETIZE_F:
            params = (("borders", op.borders),)
        else:  # pragma: no cover - no other codes are emitted by _try_fuse
            raise ValueError(f"unknown fused op code {op.code}")
        out.append(TransformSpec(src.op, src.inputs, src.output, params))
    return out


# Fallback ops one numpy call may serve for many features: the dense ops
# are element-wise, so over a (features, rows) matrix they give each
# feature's per-feature result as one row; FirstX has ``firstx_many``.
_ELEMENTWISE = frozenset({"BoxCox", "Logit", "Clamp", "GetLocalHour"})


def _group_key(spec: TransformSpec) -> Optional[Tuple]:
    """Specs with equal keys may share one numpy call; None for an op
    that runs once per feature.  Each param's type is part of the key:
    ``0.5 == np.float64(0.5)``, but they round differently."""
    if len(spec.inputs) != 1 or (
        spec.op not in _ELEMENTWISE and spec.op != "FirstX"
    ):
        return None
    key = (spec.op, tuple((k, type(v), v) for k, v in spec.params))
    try:
        hash(key)
    except TypeError:            # array-valued params: never grouped
        return None
    return key


def group_pass(
    specs: Sequence[TransformSpec],
) -> List[Tuple[TransformSpec, ...]]:
    """One pass of consecutive fallback specs as numpy calls, in order:
    the specs of one group key at the same depth in the pass (no spec
    reads another's output) form one call, every other spec a call of
    its own.  A spec runs after every spec of the pass whose output it
    reads; a pass that reassigns a key keeps its sequential order."""
    if not _single_assignment(specs):
        return [(s,) for s in specs]
    depth: Dict[str, int] = {}
    calls: Dict[Tuple, List[TransformSpec]] = {}     # in order of first spec
    for n, s in enumerate(specs):
        d = 1 + max((depth[i] for i in s.inputs if i in depth), default=-1)
        depth[s.output] = d
        key = _group_key(s)
        calls.setdefault((d, ("one", n) if key is None else key), []).append(s)
    ordered = sorted(calls.items(), key=lambda kv: kv[0][0])   # stable
    return [tuple(c) for _, c in ordered]


# ---------------------------------------------------------------------------
# Engines
# ---------------------------------------------------------------------------


class TransformEngine:
    """Executes a session's transform DAG over a ColumnBatch."""

    name = "base"
    tracer = NULL_TRACER        # the owning worker's, for the phase spans

    def __init__(self, pipeline: TransformPipeline):
        self.pipeline = pipeline
        self.stats = EngineStats()

    def run(self, batch: ColumnBatch) -> Dict[str, Column]:
        raise NotImplementedError

    def __call__(self, batch: ColumnBatch) -> Dict[str, Column]:
        return self.run(batch)

    # -- shared helpers -----------------------------------------------------

    @staticmethod
    def _seed_env(batch: ColumnBatch) -> Dict[str, Column]:
        env: Dict[str, Column] = {}
        for fid, col in batch.dense.items():
            env[f"f{fid}"] = col
        for fid, col in batch.sparse.items():
            env[f"f{fid}"] = col
        return env

    def _fallback_pass(self, specs: Sequence[TransformSpec],
                       env: Dict[str, Column]) -> None:
        """Run consecutive per-feature numpy ops as one traced pass."""
        with phase(self.tracer, "transform.fallback", self.stats, "fallback_s"):
            for spec in specs:
                self._call(spec, env)

    def _call(self, spec: TransformSpec, env: Dict[str, Column]) -> None:
        """One per-feature numpy op."""
        env[spec.output] = _OPS[spec.op](*[env[i] for i in spec.inputs],
                                         **spec.kwargs)
        self.stats.fallback_features += 1
        self.stats.kernel_launches += 1


class NumpyEngine(TransformEngine):
    """Per-feature reference execution — one vectorized numpy call per
    spec, each accounted as one kernel launch (the per-feature dispatch
    regime of §7.2)."""

    name = "numpy"

    def run(self, batch: ColumnBatch) -> Dict[str, Column]:
        env = self._seed_env(batch)
        self._fallback_pass(self.pipeline.specs, env)
        return env


class PallasEngine(TransformEngine):
    """Wave-fused execution via ``kernels.fused_transform``.

    ``row_quantum`` pads the packed tile's row count up to a multiple, so
    ragged stripe sizes reuse a handful of compiled kernel shapes instead
    of recompiling per batch (pad lanes compute garbage that is sliced
    away on unpack).

    ``use_pallas`` is the wave dispatch (the ``repro.kernels`` contract):
    ``None`` (default) runs the compiled Pallas kernel on TPU and the
    XLA-compiled static-codes oracle elsewhere — the fast fused path for
    whatever backend is present, so ``engine="pallas"`` never regresses a
    CPU deployment into emulation.  ``True`` always runs the Pallas
    kernel — compiled on TPU, **interpret mode** off-TPU (bit-accurate
    but emulation-slow: how the differential suite validates the kernel
    on CPU).  All paths compute identical bits, so the engine stays
    byte-compatible with ``NumpyEngine`` either way.
    """

    name = "pallas"

    def __init__(
        self,
        pipeline: TransformPipeline,
        block_rows: int = 256,
        block_cols: int = 512,
        row_quantum: int = 512,
        use_pallas: Optional[bool] = None,
    ):
        super().__init__(pipeline)
        self.plan = compile_pipeline(pipeline.specs)
        # the plan as passes: runs of consecutive fallback steps, each as
        # its numpy calls (``group_pass``), and runs of waves
        self._passes = []
        for fallback, steps in itertools.groupby(
                self.plan.steps, key=lambda st: isinstance(st, FallbackStep)):
            steps = tuple(steps)
            if fallback:
                steps = tuple(group_pass([st.spec for st in steps]))
            self._passes.append((fallback, steps))
        self.block_rows = block_rows
        self.block_cols = block_cols
        self.row_quantum = max(1, row_quantum)
        self.use_pallas = use_pallas

    def run(self, batch: ColumnBatch) -> Dict[str, Column]:
        env = self._seed_env(batch)
        for fallback, steps in self._passes:
            if fallback:
                self._grouped_pass(steps, env)
            else:
                for wave in steps:
                    self._run_wave(wave, env)
        return env

    # -- grouped numpy passes -----------------------------------------------

    def _grouped_pass(self, calls: Sequence[Tuple[TransformSpec, ...]],
                      env: Dict[str, Column]) -> None:
        """Run one pass of fallback ops, each group of specs as one numpy
        call where its inputs allow (``_run_group``)."""
        with phase(self.tracer, "transform.fallback", self.stats, "fallback_s"):
            for specs in calls:
                if len(specs) == 1:
                    self._call(specs[0], env)
                else:
                    self._run_group(specs, env)

    def _run_group(self, specs: Sequence[TransformSpec],
                   env: Dict[str, Column]) -> None:
        """One op over several features.  The inputs are bucketed by what
        the grouped call needs to give each feature's per-feature bits:
        dense, 1-D float32 of one length; FirstX, ``SparseColumn``s of one
        row count, values dtype and scores dtype.  A bucket of two or more
        is one call; every other feature runs per feature."""
        firstx = specs[0].op == "FirstX"
        buckets: Dict[Any, List[TransformSpec]] = {}
        for n, spec in enumerate(specs):
            col = env[spec.inputs[0]]
            if firstx and isinstance(col, SparseColumn):
                key = (col.rows, col.values.dtype,
                       None if col.scores is None else col.scores.dtype)
            elif not firstx and isinstance(col, np.ndarray) \
                    and col.ndim == 1 and col.dtype == np.float32:
                key = len(col)
            else:
                key = ("one", n)
            buckets.setdefault(key, []).append(spec)
        kwargs = specs[0].kwargs
        for members in buckets.values():
            if len(members) == 1:
                self._call(members[0], env)
                continue
            cols = [env[s.inputs[0]] for s in members]
            if firstx:
                outs = firstx_many(cols, **kwargs)
            else:
                # features-major: each output is a contiguous row view
                outs = _OPS[specs[0].op](np.stack(cols), **kwargs)
            for s, out in zip(members, outs):
                env[s.output] = out
            self.stats.fallback_features += len(members)
            self.stats.grouped_features += len(members)
            self.stats.fallback_groups += 1
            self.stats.kernel_launches += 1

    # -- wave execution -----------------------------------------------------

    def _pack_column(self, fop: FusedOp, col: Column) -> Optional[np.ndarray]:
        """Return this op's input as int32-assignable lanes (int64 sparse
        ids wrap to their low 32 bits on assignment; dense float32 rides
        as bit patterns), or None to demote the op to the numpy fallback."""
        if fop.kind == "sparse":
            if not isinstance(col, SparseColumn):
                return None
            v = col.values
            if fop.code == OP_POSITIVE_MODULUS and v.size and (
                v.min() < _I32_MIN or v.max() > _I32_MAX
            ):
                return None      # int32 wrap would diverge from int64 numpy
            # SigridHash truncates to the low 32 bits on both paths, so
            # any int64 id packs exactly (setitem wrap == astype wrap).
            return v
        if not isinstance(col, np.ndarray) or col.ndim != 1:
            return None
        if fop.kind == "dense" and col.dtype != np.float32:
            return None          # f64 clamp-then-cast can diverge from f32
        v32 = np.nan_to_num(col, nan=0.0).astype(np.float32)
        if _subnormal(v32):
            return None          # XLA flush-to-zero would diverge from numpy
        return v32.view(np.int32)

    def _run_wave(self, wave: FusedWave, env: Dict[str, Column]) -> None:
        with phase(self.tracer, "transform.fused", self.stats, "fused_s"):
            demoted = self._run_fused(wave, env)
        if demoted:
            self.stats.demoted_features += len(demoted)
            self._fallback_pass([fop.spec for fop in demoted], env)

    def _run_fused(self, wave: FusedWave, env: Dict[str, Column]) -> List[FusedOp]:
        """Pack, launch and unpack the wave's fusable ops; returns the ops
        demoted to the numpy fallback."""
        entries: List[Tuple[FusedOp, Column, np.ndarray]] = []
        demoted: List[FusedOp] = []
        for fop in wave.ops:
            col = env[fop.spec.inputs[0]]
            packed = self._pack_column(fop, col)
            if packed is None:
                demoted.append(fop)
            else:
                entries.append((fop, col, packed))
        if not entries:
            return demoted

        rows = max(len(p) for _, _, p in entries)
        feats = len(entries)
        if rows == 0:
            out32 = np.zeros((feats, 0), np.int32)
        else:
            # features-major packing: one contiguous row per feature
            # (fast fills; int64 ids wrap to their low 32 bits on
            # assignment, matching the kernel's lane truncation)
            q = self.row_quantum
            rows_pad = -(-rows // q) * q
            mat = np.zeros((feats, rows_pad), np.int32)
            codes = np.zeros(feats, np.int32)
            p0 = np.zeros(feats, np.int32)
            p1 = np.zeros(feats, np.int32)
            nb = max(
                [f.borders.size for f, _, _ in entries if f.borders is not None],
                default=1,
            )
            borders = np.full((feats, nb), np.inf, np.float32)
            for j, (fop, _, packed) in enumerate(entries):
                mat[j, : len(packed)] = packed
                codes[j] = fop.code
                p0[j] = fop.p0
                p1[j] = fop.p1
                if fop.borders is not None:
                    borders[j, : fop.borders.size] = fop.borders
            with phase(self.tracer, "kernel.fused_transform", self.stats,
                       "launch_s"):
                out32 = self._launch(mat, codes, p0, p1, borders)
            self.stats.fused_launches += 1
        self.stats.kernel_launches += 1
        self.stats.fused_features += feats
        # vectorized unpack: at most one widening cast for the whole
        # wave; per-feature outputs are contiguous row views
        out64 = (
            out32.astype(np.int64)
            if any(f.kind != "dense" for f, _, _ in entries) else None
        )
        for j, (fop, col, packed) in enumerate(entries):
            env[fop.spec.output] = self._unpack(
                fop, col, out32, out64, j, len(packed)
            )
        return demoted

    def _launch(self, mat, codes, p0, p1, borders) -> np.ndarray:
        """Run one wave over the (features, rows) packed tile; returns the
        transformed tile in the same layout."""
        import jax.numpy as jnp

        from repro.kernels import ops as kops

        use = kops._on_tpu() if self.use_pallas is None else self.use_pallas
        if use:
            # the Pallas kernel tiles (rows, features) with features on
            # the 128-lane minor axis; transposes happen device-side
            out = kops.fused_transform(
                jnp.asarray(mat).T, jnp.asarray(codes), jnp.asarray(p0),
                jnp.asarray(p1), jnp.asarray(borders),
                block_rows=self.block_rows, block_cols=self.block_cols,
                use_pallas=True,
            )
            return np.ascontiguousarray(np.asarray(out).T)
        # oracle dispatch: the wave's op codes are known at compile time,
        # so the static-codes oracle skips every absent candidate branch
        # and computes directly in the packing layout (no transposes)
        out = _static_oracle()(
            jnp.asarray(mat), tuple(int(c) for c in codes),
            jnp.asarray(p0), jnp.asarray(p1), jnp.asarray(borders),
            features_major=True,
        )
        return np.asarray(out)

    @staticmethod
    def _unpack(
        fop: FusedOp, col: Column,
        out32: np.ndarray, out64: Optional[np.ndarray], j: int, n: int,
    ) -> Column:
        if fop.kind == "sparse":
            return SparseColumn(
                offsets=col.offsets, values=out64[j, :n], scores=col.scores,
            )
        if fop.kind == "dense":        # Clamp: float32 bits back to floats
            return out32[j, :n].view(np.float32)
        # dense_bucket: one bucket id per row, arange offsets — exactly
        # the transforms.bucketize output shape
        return SparseColumn(
            offsets=np.arange(n + 1, dtype=np.int64),
            values=out64[j, :n], scores=None,
        )


_STATIC_ORACLE = None


def _static_oracle():
    """Lazily-jitted ``ref.fused_transform_static`` (op codes static)."""
    global _STATIC_ORACLE
    if _STATIC_ORACLE is None:
        import jax

        from repro.kernels import ref

        _STATIC_ORACLE = jax.jit(
            ref.fused_transform_static,
            static_argnums=(1,), static_argnames=("features_major",),
        )
    return _STATIC_ORACLE


ENGINES = {"numpy": NumpyEngine, "pallas": PallasEngine}


def make_engine(
    engine: Union[str, TransformEngine, None],
    pipeline: TransformPipeline,
) -> TransformEngine:
    """Resolve an engine choice (name, instance, or factory) for one
    exclusive owner (engines accumulate stats; don't share instances
    across workers)."""
    if engine is None:
        return NumpyEngine(pipeline)
    if isinstance(engine, TransformEngine):
        return engine
    if isinstance(engine, str):
        try:
            return ENGINES[engine](pipeline)
        except KeyError:
            raise ValueError(
                f"unknown transform engine {engine!r}; "
                f"expected one of {sorted(ENGINES)}"
            ) from None
    return engine(pipeline)      # factory callable
