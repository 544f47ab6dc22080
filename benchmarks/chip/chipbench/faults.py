"""Faults planted under the timed path, to show the check catches each:
a step that returns its state unchanged, a loss over half of the batch
(its mean taken over the rest), and an id altered where DPP produces it.
Each is a context manager that patches the program and restores it."""
from __future__ import annotations

import contextlib


def _state_unchanged():
    import jax
    from repro.train import trainer

    def build(self):
        model = self.model

        def step(params, opt, batch):
            return params, opt, model.loss(params, batch), 0.0
        return jax.jit(step)
    return trainer.Trainer, "_build_step", build


def _half_batch():
    from repro.models import dlrm

    real = dlrm.DLRM.loss

    def half(self, params, batch):
        n = batch["label"].shape[0] // 2
        return real(self, params, {k: v[:n] for k, v in batch.items()})
    return dlrm.DLRM, "loss", half


def _id_altered():
    from repro.core.dpp import worker

    real = worker.materialize_dlrm_batch

    def alter(*a, **kw):
        out = real(*a, **kw)
        ids = out["sparse_ids"]          # padded (B, T, L) or packed (B, ids)
        ids[(0,) * ids.ndim] += 1
        return out
    return worker, "materialize_dlrm_batch", alter


FAULTS = {"state_unchanged": _state_unchanged, "half_batch": _half_batch,
          "id_altered": _id_altered}


@contextlib.contextmanager
def planted(name: str):
    owner, attr, fake = FAULTS[name]()
    real = owner.__dict__[attr]
    setattr(owner, attr, fake)
    try:
        yield
    finally:
        setattr(owner, attr, real)
