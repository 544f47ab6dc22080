"""One run of one cell: set-up, the measured window, the check.

The window drives ``Trainer.fit`` with the dense DLRM step and AdamW.
``live`` traffic feeds it from a running ``DPPSession`` (the pool's
partitions, then byte-identical copies of them, so the window meets no
shape set-up did not compile); ``replay`` traffic feeds it round-robin
from batches a DPP session made in set-up.  Set-up trains through the
pool once in the same session the window continues.
"""
from __future__ import annotations

import dataclasses
import itertools
import math
import os
import shutil
import tempfile
import threading
import time
from types import SimpleNamespace
from typing import Callable, Dict, List, Optional

import numpy as np

from chipbench import check, cost, devtrace, traffic
from chipbench.layout import ROOT, Cell, reader

CHECK_STEPS = 3        # steps the reference follows


class NoChip(RuntimeError):
    pass


# -- compilation: cache and counts -------------------------------------------


class CompileLog:
    """Backend compiles (a persistent-cache hit logs one too, its read)
    and the persistent cache's hits and misses, with their times."""

    def __init__(self):
        self.compiles: List[tuple] = []      # (perf_counter, seconds)
        self.hits: List[float] = []
        self.misses: List[float] = []
        self._lock = threading.Lock()

    def on_duration(self, name, secs, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.compiles.append((time.perf_counter(), secs))

    def on_event(self, name, **kw):
        with self._lock:
            if name == "/jax/compilation_cache/cache_hits":
                self.hits.append(time.perf_counter())
            elif name == "/jax/compilation_cache/cache_misses":
                self.misses.append(time.perf_counter())

    def between(self, t0: float, t1: float) -> Dict[str, float]:
        with self._lock:
            c = [s for t, s in self.compiles if t0 <= t <= t1]
            return {"compiles": len(c), "compile_s": sum(c),
                    "cache_hits": sum(t0 <= t <= t1 for t in self.hits),
                    "cache_misses": sum(t0 <= t <= t1 for t in self.misses)}


_LOG: Optional[CompileLog] = None


def compile_log() -> CompileLog:
    """The process's one log: JAX's listeners are process-wide."""
    global _LOG
    if _LOG is None:
        import jax

        _LOG = CompileLog()
        jax.monitoring.register_event_duration_secs_listener(_LOG.on_duration)
        jax.monitoring.register_event_listener(_LOG.on_event)
    return _LOG


def use_cache() -> str:
    """JAX's persistent cache in ``JAX_COMPILATION_CACHE_DIR`` when set,
    else at the checkout's fixed ``.jax_cache``; every program goes in,
    however fast it compiled."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


# -- the trainer's feed ------------------------------------------------------------


class Feed:
    """The batch iterator ``Trainer.fit`` reads.  Each request is the end
    of the step before it (the trainer synchronises on the loss), so the
    request times are the steps' completion times.  ``until`` ends the
    window; ``hooks`` run at each request (profiler start and stop)."""

    def __init__(self, source: Callable[[], Dict[str, np.ndarray]], marks: bool):
        self.source = source
        self.marks = marks               # host annotations for the device trace
        self.handed: List[Dict[str, np.ndarray]] = []
        self.requests: List[float] = []
        self.until: Optional[float] = None
        self.window: Optional[float] = None    # seconds, armed by open_window
        self.hooks: List[Callable[[float], None]] = []
        self._step_mark = None

    def open_window(self, seconds: float) -> None:
        self.window = seconds

    def __iter__(self):
        return self

    def __next__(self) -> Dict[str, np.ndarray]:
        now = time.perf_counter()
        if self._step_mark is not None:
            self._step_mark.__exit__(None, None, None)
            self._step_mark = None
        self.requests.append(now)
        if self.window is not None:
            self.until, self.window = now + self.window, None
        for h in self.hooks:
            h(now)
        if self.until is not None and now >= self.until:
            raise StopIteration
        if self.marks:
            import jax

            with jax.profiler.TraceAnnotation("trainer.wait_batch"):
                b = self.source()
            self._step_mark = jax.profiler.TraceAnnotation("trainer.step")
            self._step_mark.__enter__()
        else:
            b = self.source()
        self.handed.append(b)
        return b


def live_source(session, timeout_s: float = 120.0):
    def get() -> Dict[str, np.ndarray]:
        deadline = time.perf_counter() + timeout_s
        while time.perf_counter() < deadline:
            b = session.clients[0].get_batch(timeout=1.0)
            if b is not None:
                return b
            if session.master.finished and all(w.buffered == 0 for w in session.workers):
                errors = [f.last_error for f in session.failure_report()][:1]
                raise RuntimeError(f"the DPP session ended {session.state} inside "
                                   f"the run {errors}")
        raise RuntimeError(f"no batch from the DPP session in {timeout_s} s "
                           f"(state {session.state})")
    return get


def replay_source(batches: List[Dict[str, np.ndarray]]):
    return itertools.cycle(batches).__next__


def fleet_size(session) -> int:
    return sum(1 for w in session.workers if not w.retired) if session else 0


def drain(session, timeout_s: float = 120.0) -> int:
    """Stop handing out splits, let every worker deliver the one it
    holds, and take what is left.  Returns the rows taken."""
    session._stop.set()                      # no scaling or restarts from here
    if session._monitor is not None:
        session._monitor.join(timeout=10.0)
    for w in session.workers:
        w.drain()
    rows, deadline = 0, time.perf_counter() + timeout_s
    while time.perf_counter() < deadline:
        b = session.clients[0].get_batch(timeout=0.2)
        if b is not None:
            rows += len(b["label"])
            continue
        if all(not (w._thread and w._thread.is_alive()) for w in session.workers) \
                and all(w.buffered == 0 for w in session.workers):
            break
    return rows


# -- set-up pieces, shared with the readings script -----------------------------


def start_source(cell: Cell, pool, seed: int, seconds: float, tracer, marks: bool):
    """The trainer's feed.  Live: a started session over the pool and
    enough copies for the run.  Replay: the batches a session made from
    the pool, round-robin."""
    from repro.core.dpp import DPPSession

    tr = cell.traffic
    out = SimpleNamespace(session=None, made=[], made_ok=True, produced_rows=0)
    if tr["mode"] == "live":
        copies = math.ceil(tr["max_batches_per_s"] * (seconds + tr["warm_s"])
                           / pool.batches)
        traffic.add_copies(pool, copies)
        spec = traffic.session_spec(cell.config, pool,
                                    traffic.read_order(pool, copies, seed))
        out.session = DPPSession(spec, pool.table, n_workers=tr["workers"],
                                 auto_scale=tr["auto_scale"], lease_s=tr["lease_s"],
                                 engine=tr["engine"], decode_engine=tr["decode_engine"],
                                 tracer=tracer)
        out.session.start()
        out.feed = Feed(live_source(out.session), marks=marks)
        return out
    spec = traffic.session_spec(cell.config, pool, traffic.read_order(pool, 0, seed))
    maker = DPPSession(spec, pool.table, n_workers=tr["workers"], lease_s=tr["lease_s"],
                       engine=tr["engine"], decode_engine=tr["decode_engine"])
    out.made = maker.run_to_completion(timeout_s=300.0)
    out.produced_rows = maker.worker_metrics().rows_done
    out.made_ok = maker.state == "COMPLETED" and not maker.master.quarantined
    out.feed = Feed(replay_source(out.made), marks=marks)
    return out


def first_steps(cell: Cell, trainer, feed: Feed, state: Dict, seed: int):
    """Drive the trainer through its first steps on the window's own feed
    and read what the reference is compared on: each step's loss, the
    first gradient's per-leaf norm as AdamW got it (from its first
    moment after one step), and each leaf's change after the steps."""
    ref, model = cell.reference, cell.config["model"]
    trainer.cfg.max_steps = 1
    state = trainer.fit(feed, state)
    mu_norms = np.asarray(ref.leaf_norms(state["opt"]["mu"]))
    trainer.cfg.max_steps = CHECK_STEPS
    state = trainer.fit(feed, state)
    names = ref.leaf_names(state["params"])
    change = np.asarray(ref.change_norms(model, state["params"], seed))
    grads = check.first_grad_norms(mu_norms, cell.config["optimizer"]["beta1"])
    return state, {
        "losses": [h.loss for h in trainer.history[:CHECK_STEPS]],
        "grad_norms": dict(zip(names, grads.tolist())),
        "change_norms": dict(zip(names, change.astype(np.float64).tolist())),
    }


def reference_batches(cell: Cell, pool) -> Dict[str, Dict[str, np.ndarray]]:
    """The reference batch of every split of the pool, by ``batch_key``."""
    batch = cell.config["batch_size"]
    refs = {}
    for raw in pool.raw:
        for lo in range(0, raw["rows"], batch):
            r = cell.reference.transform_rows(
                raw, lo, lo + batch, pool.job,
                cell.config["model"]["max_ids_per_feature"])
            refs[check.batch_key(r, cell.config["model"])] = r
    return refs


# -- the run -----------------------------------------------------------------------


@dataclasses.dataclass
class Run:
    """What one run measured and checked."""

    result: Dict
    lines: List[str]


def _model_cfg(config: Dict):
    from repro.models.dlrm import DLRMConfig

    m = config["model"]
    return DLRMConfig(name=config["name"], **{
        k: tuple(v) if isinstance(v, list) else v for k, v in m.items()})


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, t_start: float,
             require_tpu: bool = True, profile_dir: Optional[str] = None) -> Run:
    import jax
    import jax.numpy as jnp

    devices = jax.devices()
    dev = devices[0]
    if require_tpu and dev.platform != "tpu":
        raise NoChip(f"no TPU: JAX found {dev.platform!r} devices")
    if len(devices) < cell.chips:
        raise NoChip(f"the cell needs {cell.chips} chips; JAX found {len(devices)}")
    cache_dir = use_cache()
    clog = compile_log()

    from repro.obs import NULL_TRACER, Tracer
    from repro.optim import OptimizerConfig, adamw_init
    from repro.train import Trainer, TrainerConfig

    cfg, tr, ref = cell.config, cell.traffic, cell.reference
    model, batch = cfg["model"], cfg["batch_size"]
    lines: List[str] = []
    say = lines.append
    say(f"device: {dev.platform} {dev.device_kind} count={len(devices)}")

    # -- set-up: traffic ------------------------------------------------------------
    pool = traffic.make_pool(cfg, tr)
    live = tr["mode"] == "live"
    tracer = Tracer() if trace else NULL_TRACER
    opt_cfg = OptimizerConfig(**cfg["optimizer"])
    trainer = Trainer(_model_cfg(cfg), opt_cfg, TrainerConfig(max_steps=0),
                      tracer=tracer)
    t_state = time.perf_counter()
    params = ref.init_params(model, seed)
    state = {"params": params, "opt": adamw_init(params, opt_cfg), "step": 0}
    jax.block_until_ready(state)
    del params
    t_state = time.perf_counter() - t_state

    src = start_source(cell, pool, seed, seconds, tracer, trace)
    session, feed, made = src.session, src.feed, src.made
    t_warm = time.perf_counter()
    state, prog = first_steps(cell, trainer, feed, state, seed)
    # -- set-up: the rest of the pool -------------------------------------------------
    trainer.cfg.max_steps = pool.batches + tr["warm_extra_batches"]
    state = trainer.fit(feed, state)
    # Then on until every pool batch (a copy reads the same) has come
    # through: a cold compile can hold one back past the warm steps, and
    # its kernels would then compile inside the window.
    for _ in range(pool.batches):
        if not live or len({check.batch_key(b, model)
                            for b in feed.handed}) >= pool.batches:
            break
        trainer.cfg.max_steps += 1
        state = trainer.fit(feed, state)
    t_warm = time.perf_counter() - t_warm
    warm_steps = len(feed.handed)

    # -- the window ------------------------------------------------------------------
    prof = SimpleNamespace(t0=None, t1=None, dir=None)
    if trace:
        prof.dir = profile_dir or tempfile.mkdtemp(prefix="chipbench-trace-")

        def profiler(now: float) -> None:
            if feed.until is None:
                return
            start = feed.until - seconds
            if prof.t0 is None and now >= start + tr["profile_start_s"]:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0        # host TraceMe marks only
                jax.profiler.start_trace(prof.dir, profiler_options=opts)
                prof.t0 = time.perf_counter()
            elif prof.t0 is not None and prof.t1 is None and \
                    now >= prof.t0 + tr["profile_seconds"]:
                prof.t1 = time.perf_counter()
                jax.profiler.stop_trace()
        feed.hooks.append(profiler)

    trainer.cfg.max_steps = 10 ** 9
    first = len(feed.requests)
    wm_start = session.worker_metrics() if session else None
    fleet = [fleet_size(session)]
    cpu0 = sum(os.times()[:2])
    feed.open_window(seconds)
    state = trainer.fit(feed, state)
    cpu1 = sum(os.times()[:2])
    wm_end = session.worker_metrics() if session else None
    fleet.append(fleet_size(session))
    win = feed.requests[first:]
    t_w0, t_w1 = win[0], win[-1]
    window_batches = feed.handed[warm_steps:]
    window_rows = sum(len(b["label"]) for b in window_batches)
    in_window = clog.between(t_w0, t_w1)
    setup = clog.between(t_start, t_w0)
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    if trace and prof.t1 is None and prof.t0 is not None:
        prof.t1 = time.perf_counter()
        jax.profiler.stop_trace()
    history = list(trainer.history)
    del state
    state = None

    # -- delivery: rows consumed against rows produced --------------------------------
    produced_rows, consumed_extra = src.produced_rows, 0
    if live:
        consumed_extra = drain(session)
        m = session.worker_metrics()
        produced_rows = m.rows_done
        delivered_ok = (session.master.progress[0] * batch == produced_rows
                        and not session.master.quarantined and m.data_errors == 0)
        bad_rows = len(session.master.quarantined) * batch + m.data_errors * batch
        session.stop()
    else:
        delivered_ok = src.made_ok
        bad_rows = 0 if src.made_ok else batch
    consumed = (sum(len(b["label"]) for b in (made if not live else feed.handed))
                + consumed_extra)
    rows_unaccounted = abs(produced_rows - consumed) + bad_rows + (0 if delivered_ok else 1)

    # -- the check ----------------------------------------------------------------------
    t_ref = time.perf_counter()
    refs = reference_batches(cell, pool)
    compared = made if not live else feed.handed
    data_numbers, bad = check.compare_batches(
        compared, refs, cfg["limits"]["dense_gap"], model)
    failed_keys = {check.batch_key(b, model) for b, f in zip(compared, bad) if f}
    firsts = [refs.get(check.batch_key(b, model)) for b in feed.handed[:CHECK_STEPS]]
    if all(r is not None for r in firsts):
        ref_read = ref.train_readings(model, cfg["optimizer"], seed, firsts, jnp.float32)
        numbers = check.train_numbers(prog, ref_read)
    else:
        numbers = {"loss_gap": math.nan, "grad_norm_gap": math.nan,
                   "change_norm_gap": math.nan}
    numbers.update(data_numbers)
    numbers["rows_unaccounted"] = rows_unaccounted
    correct, checked = check.verdict(numbers, cfg["limits"])
    t_ref = time.perf_counter() - t_ref
    window_failed = sum(len(b["label"]) for b in window_batches
                        if check.batch_key(b, model) in failed_keys)
    failed = window_failed + bad_rows

    # -- report -----------------------------------------------------------------------------
    say(f"cache: {cache_dir} set-up hits {setup['cache_hits']} misses "
        f"{setup['cache_misses']}")
    say(f"setup: data generation {pool.gen_s!r} s, table write {pool.write_s!r} s, "
        f"weights {t_state!r} s, warm pass {t_warm!r} s over {warm_steps} steps, "
        f"backend compiles {setup['compiles']} taking {setup['compile_s']!r} s")
    gaps = [float(g) for g in np.percentile(1e3 * np.diff(np.asarray(win)), [50, 90, 100])]
    say(f"window: {len(win) - 1} steps, {window_rows} rows in {t_w1 - t_w0!r} s, "
        "step intervals p50 {!r} p90 {!r} max {!r} ms; ".format(*gaps) +
        f"backend compiles inside the window {in_window['compiles']} "
        f"(cache hits {in_window['cache_hits']}, misses {in_window['cache_misses']})")
    say(f"window host: this process took {cpu1 - cpu0!r} CPU s")
    if wm_start is not None:
        rows = max(wm_end.rows_decoded - wm_start.rows_decoded, 1)
        say("window DPP: ms per 1,000 rows extract {!r}, transform {!r}, load {!r}".format(
            *(1e6 * (getattr(wm_end, k) - getattr(wm_start, k)) / rows
              for k in ("extract_s", "transform_s", "load_s"))))
    say(f"delivery: produced {produced_rows} rows, consumed {consumed} "
        f"(drained after the window {consumed_extra}); DPP workers at the "
        f"window's start and end {fleet}, scale events in all "
        f"{len(session.scale_events) if session else 0}")
    say(f"reference: {t_ref!r} s; program losses {prog['losses']!r}")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": bool(correct), "attempted": window_rows, "failed": failed,
              "metrics": {}, "device": device}
    if not trace:
        values = {
            "samples_per_s": window_rows / (t_w1 - t_w0),
            "peak_hbm_gb": (peak or math.nan) / 1e9,
            "setup_s": t_w0 - t_start,
        }
        for m in cell.end_to_end:
            result["metrics"][m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    else:
        profile = None
        if prof.t1 is not None and (path := devtrace.find_xplane(prof.dir)):
            profile = devtrace.reduce_file(path)
        ctx = SimpleNamespace(
            cell=cell, config=cfg, traffic=tr, pool=pool, profile=profile,
            samples=window_rows, samples_s=t_w1 - t_w0, steps=history[warm_steps:],
            step_ends=win,
            spans=[s for s in tracer.spans() if t_w0 <= s.t0 and s.t1 <= t_w1],
            wm0=wm_start, wm1=wm_end, cost=cost,
            peaks=cost.peaks(dev.device_kind) if dev.platform == "tpu" else None)
        if ctx.profile is not None:
            device["busy_s"] = ctx.profile.busy_s
            device["window_s"] = ctx.profile.window_s
            result["breakdown"] = {"device_ops": ctx.profile.top_modules(),
                                   "idle_gaps": ctx.profile.top_gaps()}
        for m in cell.per_layer:
            v = reader(m["name"])(ctx)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        if profile_dir is None and prof.dir:
            shutil.rmtree(prof.dir, ignore_errors=True)
    result["check"] = checked
    lines += check.summary_lines(checked)
    return Run(result, lines)
