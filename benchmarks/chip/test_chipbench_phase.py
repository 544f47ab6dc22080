"""The phase readers (trainer put and loss wait, DPP split, fetch wait,
unpack, launch and CPU share) on a synthetic window, and on one from a
program that has neither the phase spans nor the phase counters."""
from __future__ import annotations

from types import SimpleNamespace as NS

import pytest

from chipbench.layout import reader

PHASE_READERS = ["batch_put_ms", "loss_wait_ms", "split_ms",
                 "fetch_wait_ms_per_krow", "unpack_ms_per_krow",
                 "launch_ms_per_krow", "dpp_cpu_share"]

PHASE_COUNTERS = dict(fetch_wait_s=0.5, unpack_s=2.0, extract_launch_s=1.0,
                      transform_launch_s=0.5, cpu_s=6.0)


def _span(name, t0, t1):
    return NS(name=name, t0=t0, t1=t1, labels={})


def _wm(scale, **fields):
    from repro.core.dpp.worker import WorkerMetrics

    return WorkerMetrics(rows_decoded=int(4000 * scale), extract_s=4.0 * scale,
                         transform_s=3.0 * scale, load_s=1.0 * scale,
                         **{k: v * scale for k, v in fields.items()})


@pytest.mark.parametrize("name,value", [
    ("batch_put_ms", 2.0),                 # train.put: 1 and 3 ms
    ("loss_wait_ms", 60.0),                # train.wait: 50 and 70 ms
    ("split_ms", 450.0),                   # worker.split: 400 and 500 ms
    ("fetch_wait_ms_per_krow", 125.0),     # 0.5 s over 4,000 rows
    ("unpack_ms_per_krow", 500.0),
    ("launch_ms_per_krow", 375.0),         # decode 1.0 s + transform 0.5 s
    ("dpp_cpu_share", 75.0),               # 6 CPU s over 8 busy s
])
def test_phase_readers_on_a_synthetic_window(name, value):
    spans = [_span("train.put", 0.0, 0.001), _span("train.put", 1.0, 1.003),
             _span("train.wait", 0.0, 0.05), _span("train.wait", 1.0, 1.07),
             _span("worker.split", 0.0, 0.4), _span("worker.split", 1.0, 1.5),
             _span("train.step", 0.0, 0.01)]
    ctx = NS(spans=spans, wm0=_wm(1, **PHASE_COUNTERS), wm1=_wm(2, **PHASE_COUNTERS))
    assert reader(name)(ctx) == pytest.approx(value)


@pytest.mark.parametrize("name", PHASE_READERS)
def test_phase_readers_read_nothing_from_a_program_without_them(name):
    """A program without the phase spans and counters (only the older
    spans, and worker metrics without the phase fields) reads None."""
    old = ("rows_decoded", "extract_s", "transform_s", "load_s")
    wm0, wm1 = (NS(**{k: getattr(_wm(s), k) for k in old}) for s in (1, 2))
    ctx = NS(spans=[_span("train.step", 0.0, 0.01), _span("client.stall", 0.0, 1.0)],
             wm0=wm0, wm1=wm1)
    assert reader(name)(ctx) is None
    replay = NS(spans=[], wm0=None, wm1=None)    # no DPP session in the window
    assert reader(name)(replay) is None
