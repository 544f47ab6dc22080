"""Compile the main path for a described TPU v5e chip — no chip needed.

The TPU compiler is installed with JAX and compiles for a topology that is
described, not attached.  These tests compile every Pallas kernel the
warehouse -> DPP -> DLRM path launches on the chip, at the largest shapes
``chip_smoke.py`` logs, plus the full-width DLRM train step at
``chip_smoke.py``'s size.  The compiler refuses what interpret mode
accepts: unsupported primitives, untiled blocks, VMEM overflow, a step
that does not fit HBM.  Nothing runs, so results are the differential
suites' business (``test_decode.py``, ``test_engine.py``,
``test_kernels.py``).

The topology is described inside a module fixture, never at import time:
only one process may load the TPU library, and every test worker imports
every test file.
"""
import dataclasses
import importlib
import os
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

V5E_HBM_BYTES = 16 * 1024 ** 3
ROWS = 125_000                  # chip_smoke.py's rows per table (1/16 of 2M)
BATCH = 512


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no compiler logs in /tmp
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    # a broken or missing TPU compiler fails these tests: it is the gate
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _spec(sharding, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_kernel(fn, sharding, *shapes, name=None):
    """Compile ``fn`` for the described chip.  With ``name``, the kernel's
    custom call must carry it, as the device trace's readers match it
    (``%<name>.<n> = ... custom-call(...)``)."""
    args = [_spec(sharding, s, d) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    if name is not None:
        assert re.search(rf"%{name}\.\d+ = [^\n]*custom-call\(", text), name
    return compiled


I32, F32 = jnp.int32, jnp.float32


def test_xor_decrypt_compiles(one_chip):
    from repro.kernels.decode import xor_decrypt

    _compile_kernel(xor_decrypt, one_chip, ((1374, 128), I32), name="xor_decrypt")


@pytest.mark.parametrize("stripe_rows,values", [
    (512, 483),         # chip_smoke.py's stripes
    (2048, 2040),       # the DWRF writer's default stripe
    (4096, 4090),       # benchmarks/bench_optimizations.py's stripe
])
def test_dense_unpack_compiles(one_chip, stripe_rows, values):
    """504 dense features; the kernel unrolls one lane gather per pair of
    128-row chunks, so the unroll grows with the square of the stripe."""
    from repro.kernels.decode import dense_unpack

    _compile_kernel(dense_unpack, one_chip,
                    ((504, stripe_rows // 32), I32), ((504, values), I32),
                    name="dense_unpack")


def test_ragged_gather_compiles(one_chip):
    """The whole-stripe ``src`` block lives in VMEM for every grid step:
    the largest stripe's payload must fit."""
    from repro.kernels.decode import ragged_gather

    _compile_kernel(ragged_gather, one_chip,
                    ((2004, 128), I32), ((2002, 128), I32), ((2002, 128), I32),
                    name="ragged_gather")


@pytest.mark.parametrize("rows,feats,nb", [
    (8704, 32, 1),      # sparse wave: SigridHash over every id of 32 features
    (512, 171, 63),     # dense wave: Clamp + Bucketize over 512 rows
])
def test_fused_transform_compiles(one_chip, rows, feats, nb):
    fused = importlib.import_module("repro.kernels.fused_transform")
    _compile_kernel(fused.fused_transform, one_chip,
                    ((rows, feats), I32), ((feats,), I32), ((feats,), I32),
                    ((feats,), I32), ((feats, nb), F32), name="fused_transform")


def test_embedding_bag_compiles(one_chip):
    bag = importlib.import_module("repro.kernels.embedding_bag")
    _compile_kernel(bag.embedding_bag, one_chip,
                    ((ROWS, 128), F32), ((BATCH, 32), I32), ((BATCH, 32), F32))


def test_dlrm_train_step_compiles_at_full_width(one_chip):
    """dlrm-paper at its published widths, 125,000 rows per table, batch
    512: the Trainer's dense step compiles and fits one chip's HBM."""
    from repro import configs as cfglib
    from repro.optim import OptimizerConfig, adamw_init
    from repro.train import Trainer

    cfg = dataclasses.replace(cfglib.get_config("dlrm-paper"),
                              vocab_per_table=ROWS)
    opt_cfg = OptimizerConfig()
    trainer = Trainer(cfg, opt_cfg)
    params = jax.eval_shape(trainer.model.init, jax.random.PRNGKey(0))
    opt = jax.eval_shape(lambda p: adamw_init(p, opt_cfg), params)
    batch = trainer.model.input_specs(BATCH)
    place = lambda s: _spec(one_chip, s.shape, s.dtype)   # noqa: E731
    args = jax.tree.map(place, (params, opt, batch))
    compiled = trainer._train_step.lower(*args).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert mem.argument_size_in_bytes > 3 * cfg.num_tables * ROWS * 128 * 4
    assert used < V5E_HBM_BYTES, used


def test_dlrm_dcnv2_train_step_compiles_at_full_width(one_chip):
    """MLPerf's DLRM-DCNv2 at its published widths, 200,000 rows per table
    (the benchmark's cut), batch 2,048 a chip: the Trainer's dense step
    takes the packed (B, 214) ids, gathers (B, 214, E) and never the
    padded (B, T, 100, E), and the compiler takes it for one chip's HBM
    (it refuses a step that does not fit).  Its temporary bytes are those
    of the one-hot dlrm-criteo-1tb step, whose peak on the chip is 10.7 GB:
    the dense table gradient, and no more for the multi-hot bags."""
    from repro import configs as cfglib
    from repro.optim import OptimizerConfig, adamw_init
    from repro.train import Trainer

    cfg = dataclasses.replace(cfglib.get_config("dlrm-dcnv2"), vocab_per_table=200_000)
    opt_cfg = OptimizerConfig()
    trainer = Trainer(cfg, opt_cfg)
    params = jax.eval_shape(trainer.model.init, jax.random.PRNGKey(0))
    opt = jax.eval_shape(lambda p: adamw_init(p, opt_cfg), params)
    batch = trainer.model.input_specs(2048)
    assert batch["sparse_ids"].shape == (2048, 214) and "sparse_mask" not in batch
    place = lambda s: _spec(one_chip, s.shape, s.dtype)   # noqa: E731
    args = jax.tree.map(place, (params, opt, batch))
    compiled = trainer._train_step.lower(*args).compile()
    text = compiled.as_text()
    assert "[2048,214,128]" in text and "[2048,26,100,128]" not in text
    table_bytes = cfg.num_tables * cfg.vocab_per_table * cfg.embed_dim * 4
    assert compiled.memory_analysis().temp_size_in_bytes < 4.01 * table_bytes
