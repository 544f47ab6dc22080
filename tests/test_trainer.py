import numpy as np
import pytest

from repro import configs as cfglib
from repro.optim import OptimizerConfig
from repro.train import Trainer, TrainerConfig


def _batches(cfg, n, bs=32, seed=0):
    # labels are a fixed linear function of the dense features (not random
    # coin flips), so a fit over a few dozen steps has signal to learn and
    # the decreasing-loss assertion is deterministic rather than marginal
    rng = np.random.default_rng(seed)
    w = np.random.default_rng(1234).normal(0, 1, cfg.num_dense).astype(np.float32)
    for _ in range(n):
        dense = rng.normal(0, 1, (bs, cfg.num_dense)).astype(np.float32)
        yield {
            "dense": dense,
            "sparse_ids": rng.integers(0, cfg.vocab_per_table,
                                       (bs, cfg.num_tables, cfg.max_ids_per_feature)).astype(np.int32),
            "sparse_mask": np.ones((bs, cfg.num_tables, cfg.max_ids_per_feature), np.float32),
            "label": (dense @ w > 0).astype(np.float32),
        }


def test_fit_decreases_loss(tmp_path):
    cfg = cfglib.get_smoke_config("dlrm-paper")
    tr = Trainer(cfg, OptimizerConfig(learning_rate=1e-2, warmup_steps=2, total_steps=40),
                 TrainerConfig(max_steps=40, checkpoint_dir=str(tmp_path)))
    state = tr.fit(_batches(cfg, 40))
    losses = [m.loss for m in tr.history]
    assert losses[-1] < losses[0]
    assert state["step"] == 40


def test_resume_from_checkpoint(tmp_path):
    cfg = cfglib.get_smoke_config("dlrm-paper")
    opt = OptimizerConfig(learning_rate=1e-2, warmup_steps=2, total_steps=40)
    tr1 = Trainer(cfg, opt, TrainerConfig(max_steps=20, checkpoint_dir=str(tmp_path),
                                          checkpoint_every=10))
    tr1.fit(_batches(cfg, 20))
    tr2 = Trainer(cfg, opt, TrainerConfig(max_steps=30, checkpoint_dir=str(tmp_path),
                                          checkpoint_every=10))
    state = tr2.fit(_batches(cfg, 30, seed=1))
    assert tr2.history[0].step == 21        # resumed, not restarted
    assert state["step"] == 30


def test_stall_accounting():
    cfg = cfglib.get_smoke_config("dlrm-paper")
    tr = Trainer(cfg, OptimizerConfig(warmup_steps=1, total_steps=5),
                 TrainerConfig(max_steps=5))
    import time

    def slow():
        for b in _batches(cfg, 5):
            time.sleep(0.05)
            yield b

    tr.fit(slow())
    assert tr.stall_fraction() > 0.05


def test_step_spans_split_copy_dispatch_and_loss_wait():
    """``train.step`` holds the batch copy and the dispatch; ``train.wait``
    follows it around the loss read.  No span borrows the benchmark's
    ``trainer.`` prefix."""
    from repro.obs import Tracer

    cfg = cfglib.get_smoke_config("dlrm-paper")
    tracer = Tracer()
    tr = Trainer(cfg, OptimizerConfig(warmup_steps=1, total_steps=3),
                 TrainerConfig(max_steps=3), tracer=tracer)
    tr.fit(_batches(cfg, 3))
    spans = tracer.spans()
    by = {}
    for s in spans:
        by.setdefault(s.name, []).append(s)
    for name in ("client.stall", "train.step", "train.put", "train.dispatch",
                 "train.wait"):
        assert [s.labels["step"] for s in by[name]] == [1, 2, 3], name
    assert all(s.parent == "train.step" for s in by["train.put"] + by["train.dispatch"])
    assert all(s.parent is None for s in by["train.wait"] + by["train.step"])
    for step, put, dispatch, wait in zip(by["train.step"], by["train.put"],
                                         by["train.dispatch"], by["train.wait"]):
        assert step.t0 <= put.t0 <= put.t1 <= dispatch.t0 <= dispatch.t1 <= step.t1
        assert step.t1 <= wait.t0
        assert all("cpu_s" in s.labels for s in (step, put, dispatch, wait))
    assert not any(s.name.startswith("trainer.") for s in spans)
    assert tracer.open_spans() == 0
    # the step metrics keep their meaning: copy and dispatch, then the loss
    for m, step in zip(tr.history, by["train.step"]):
        assert m.step_time_s == pytest.approx(step.duration, abs=0.05)
        assert np.isfinite(m.loss)
