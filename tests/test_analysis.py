"""Fixture tests for the ``repro.analysis`` invariant gate.

Each rule gets >= 2 positive fixtures (a violation the checker must flag)
and >= 1 negative fixture (compliant code it must stay silent on), built
as throwaway mini-repos under ``tmp_path``.  The CLI-level tests pin the
exit-code contract ``scripts/ci.sh`` relies on: 0 on a clean tree, 1 on
new findings, 2 on usage errors.
"""
from __future__ import annotations

import textwrap
from pathlib import Path

import pytest

from repro.analysis import all_rules, load_baseline, run_checks
from repro.analysis.__main__ import main as cli_main

REPO = Path(__file__).resolve().parents[1]


def _repo(tmp_path: Path, files: dict) -> Path:
    (tmp_path / "src" / "repro").mkdir(parents=True, exist_ok=True)
    for rel, text in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(text))
    return tmp_path


def _findings(repo: Path, rule: str):
    new, _known = run_checks(repo, rules=[rule])
    return new


# -- REPRO-L001: public mutation outside the lock ----------------------------

LOCKED_HEADER = """\
    import threading

    class C:
        def __init__(self):
            self._lock = threading.Lock()
            self.x = 0
            self._items = []
"""


def test_l001_assign_outside_lock(tmp_path):
    repo = _repo(tmp_path, {"src/repro/m.py": LOCKED_HEADER + """\

        def bump(self):
            self.x += 1
    """})
    f = _findings(repo, "REPRO-L001")
    assert len(f) == 1 and f[0].symbol == "C.bump" and "self.x" in f[0].message


def test_l001_mutator_call_outside_lock(tmp_path):
    repo = _repo(tmp_path, {"src/repro/m.py": LOCKED_HEADER + """\

        def push(self, v):
            self._items.append(v)
    """})
    f = _findings(repo, "REPRO-L001")
    assert len(f) == 1 and "_items" in f[0].message


def test_l001_negative_mutation_under_lock(tmp_path):
    repo = _repo(tmp_path, {"src/repro/m.py": LOCKED_HEADER + """\

        def bump(self):
            with self._lock:
                self.x += 1
                self._items.append(self.x)
    """})
    assert _findings(repo, "REPRO-L001") == []


def test_l001_negative_class_without_lock(tmp_path):
    # no declared lock -> the discipline doesn't apply
    repo = _repo(tmp_path, {"src/repro/m.py": """\
        class Plain:
            def __init__(self):
                self.x = 0
            def bump(self):
                self.x += 1
    """})
    assert _findings(repo, "REPRO-L001") == []


# -- REPRO-L002: _locked helper contract -------------------------------------


def test_l002_locked_helper_acquires_lock(tmp_path):
    repo = _repo(tmp_path, {"src/repro/m.py": LOCKED_HEADER + """\

        def _bump_locked(self):
            with self._lock:
                self.x += 1
    """})
    f = _findings(repo, "REPRO-L002")
    assert len(f) == 1 and "deadlock" in f[0].message


def test_l002_locked_helper_called_outside_lock(tmp_path):
    repo = _repo(tmp_path, {"src/repro/m.py": LOCKED_HEADER + """\

        def _bump_locked(self):
            self.x += 1

        def bump(self):
            self._bump_locked()
    """})
    f = _findings(repo, "REPRO-L002")
    assert len(f) == 1 and f[0].symbol == "C.bump"


def test_l002_negative_called_under_lock(tmp_path):
    repo = _repo(tmp_path, {"src/repro/m.py": LOCKED_HEADER + """\

        def _bump_locked(self):
            self.x += 1

        def bump(self):
            with self._lock:
                self._bump_locked()
    """})
    assert _findings(repo, "REPRO-L002") == []


# -- REPRO-L003: unlocked private helper without the suffix ------------------


def test_l003_private_helper_without_suffix(tmp_path):
    repo = _repo(tmp_path, {"src/repro/m.py": LOCKED_HEADER + """\

        def _drain(self):
            self._items.clear()

        def flush(self):
            with self._lock:
                self._drain()
    """})
    f = _findings(repo, "REPRO-L003")
    assert len(f) == 1 and f[0].symbol == "C._drain" \
        and "_locked" in f[0].message


def test_l003_uncalled_private_helper(tmp_path):
    repo = _repo(tmp_path, {"src/repro/m.py": LOCKED_HEADER + """\

        def _reset(self):
            self.x = 0
    """})
    assert len(_findings(repo, "REPRO-L003")) == 1


def test_l003_negative_suffixed_helper(tmp_path):
    repo = _repo(tmp_path, {"src/repro/m.py": LOCKED_HEADER + """\

        def _drain_locked(self):
            self._items.clear()
    """})
    assert _findings(repo, "REPRO-L003") == []


def test_l003_negative_init_only_callee(tmp_path):
    # helpers called only from __init__ touch pre-publication state
    repo = _repo(tmp_path, {"src/repro/m.py": """\
        import threading

        class C:
            def __init__(self):
                self._lock = threading.Lock()
                self.x = 0
                self._seed()

            def _seed(self):
                self.x = 42
    """})
    assert _findings(repo, "REPRO-L003") == []


# -- REPRO-C001: clock injection ---------------------------------------------


def test_c001_time_time_in_dpp(tmp_path):
    repo = _repo(tmp_path, {"src/repro/core/dpp/m.py": """\
        import time

        def deadline(s):
            return time.time() + s
    """})
    f = _findings(repo, "REPRO-C001")
    assert len(f) == 1 and "time.time" in f[0].message


def test_c001_time_monotonic_in_cache(tmp_path):
    repo = _repo(tmp_path, {"src/repro/core/cache/m.py": """\
        import time

        class T:
            def now(self):
                return time.monotonic()
    """})
    f = _findings(repo, "REPRO-C001")
    assert len(f) == 1 and f[0].symbol == "T.now"


def test_c001_negative_injection_default_and_scope(tmp_path):
    repo = _repo(tmp_path, {
        # references (not calls) are the injection idiom; perf_counter ok
        "src/repro/core/dpp/m.py": """\
            import time

            class M:
                def __init__(self, clock=time.time):
                    self._clock = clock

                def now(self):
                    t0 = time.perf_counter()
                    return self._clock(), time.perf_counter() - t0
        """,
        # out of scope: direct calls elsewhere are allowed
        "src/repro/core/other.py": """\
            import time

            def wall():
                return time.time()
        """,
    })
    assert _findings(repo, "REPRO-C001") == []


# -- REPRO-K001/K002: kernel parity ------------------------------------------


def _kernel_repo(tmp_path, fused: str, ref: str, suite: str) -> Path:
    return _repo(tmp_path, {
        "src/repro/kernels/fused_transform.py": fused,
        "src/repro/kernels/ref.py": ref,
        "tests/test_engine.py": suite,
    })


def test_k001_op_missing_from_ref(tmp_path):
    repo = _kernel_repo(
        tmp_path, "OP_FOO = 0\nOP_BAZ = 1\n", "OP_FOO = 0\n", "OP_FOO OP_BAZ",
    )
    f = _findings(repo, "REPRO-K001")
    assert len(f) == 1 and "OP_BAZ" in f[0].message \
        and "no parity oracle" in f[0].message


def test_k001_value_mismatch_and_dead_oracle(tmp_path):
    repo = _kernel_repo(
        tmp_path, "OP_FOO = 0\n", "OP_FOO = 3\nOP_QUX = 1\n", "OP_FOO",
    )
    msgs = sorted(x.message for x in _findings(repo, "REPRO-K001"))
    assert len(msgs) == 2
    assert "diverge" in msgs[0] and "OP_QUX" in msgs[1]


def test_k001_negative_matching_tables(tmp_path):
    repo = _kernel_repo(
        tmp_path, "OP_FOO = 0\nOP_BAR = 1\n", "OP_FOO = 0\nOP_BAR = 1\n", "x",
    )
    assert _findings(repo, "REPRO-K001") == []


def test_k002_op_not_exercised(tmp_path):
    repo = _kernel_repo(
        tmp_path, "OP_FOO = 0\nOP_BAR = 1\n", "OP_FOO = 0\nOP_BAR = 1\n",
        "def test_foo():\n    use('OP_FOO')\n",
    )
    f = _findings(repo, "REPRO-K002")
    assert len(f) == 1 and "OP_BAR" in f[0].message


def test_k002_suite_missing(tmp_path):
    repo = _repo(tmp_path, {
        "src/repro/kernels/fused_transform.py": "OP_FOO = 0\n",
        "src/repro/kernels/ref.py": "OP_FOO = 0\n",
    })
    f = _findings(repo, "REPRO-K002")
    assert len(f) == 1 and "suite missing" in f[0].message


def test_k002_negative_transform_name_counts(tmp_path):
    # OP_SIGRID_HASH is exercised via a "SigridHash" spec string
    repo = _kernel_repo(
        tmp_path, "OP_SIGRID_HASH = 1\nOP_CLAMP_F = 5\n",
        "OP_SIGRID_HASH = 1\nOP_CLAMP_F = 5\n",
        'TransformSpec("SigridHash", ...); TransformSpec("Clamp", ...)',
    )
    assert _findings(repo, "REPRO-K002") == []


def test_k002_dispatch_kernel_without_differential_test(tmp_path):
    # a public kernel in ops.py absent from tests/test_kernels.py is the
    # untested-op hole one layer up (ISSUE 9)
    repo = _repo(tmp_path, {
        "src/repro/kernels/fused_transform.py": "OP_FOO = 0\n",
        "src/repro/kernels/ref.py": "OP_FOO = 0\n",
        "tests/test_engine.py": "OP_FOO",
        "src/repro/kernels/ops.py": """\
            def embedding_bag(t, i, m):
                return t

            def _private_helper():
                pass
        """,
        "tests/test_kernels.py": "def test_nothing():\n    pass\n",
    })
    f = _findings(repo, "REPRO-K002")
    assert len(f) == 1 and "embedding_bag" in f[0].message \
        and "test_kernels" in f[0].message


def test_k002_dispatch_suite_missing_entirely(tmp_path):
    repo = _repo(tmp_path, {
        "src/repro/kernels/fused_transform.py": "OP_FOO = 0\n",
        "src/repro/kernels/ref.py": "OP_FOO = 0\n",
        "tests/test_engine.py": "OP_FOO",
        "src/repro/kernels/ops.py": "def flash_attention(q, k, v):\n"
                                    "    return q\n",
    })
    f = _findings(repo, "REPRO-K002")
    assert len(f) == 1 and "differential suite missing" in f[0].message


def test_k002_dispatch_negative_covered_and_ops_absent(tmp_path):
    # every public kernel named by the suite -> clean; and a repo with no
    # ops.py at all (the older fixtures) must stay clean too
    repo = _repo(tmp_path, {
        "src/repro/kernels/fused_transform.py": "OP_FOO = 0\n",
        "src/repro/kernels/ref.py": "OP_FOO = 0\n",
        "tests/test_engine.py": "OP_FOO",
        "src/repro/kernels/ops.py": "def embedding_bag(t, i, m):\n"
                                    "    return t\n",
        "tests/test_kernels.py": "def test_bag():\n"
                                 "    embedding_bag(1, 2, 3)\n",
    })
    assert _findings(repo, "REPRO-K002") == []
    bare = _kernel_repo(tmp_path / "bare", "OP_FOO = 0\n", "OP_FOO = 0\n",
                        "OP_FOO")
    assert _findings(bare, "REPRO-K002") == []


def test_k002_decode_kernel_without_differential_test(tmp_path):
    # a public kernel in kernels/decode.py absent from tests/test_decode.py
    # is an extract-path op outside the parity net (ISSUE 10)
    repo = _repo(tmp_path, {
        "src/repro/kernels/fused_transform.py": "OP_FOO = 0\n",
        "src/repro/kernels/ref.py": "OP_FOO = 0\n",
        "tests/test_engine.py": "OP_FOO",
        "src/repro/kernels/decode.py": """\
            def xor_decrypt_kernel(w):
                return w

            def _pad(w):
                return w
        """,
        "tests/test_decode.py": "def test_nothing():\n    pass\n",
    })
    f = _findings(repo, "REPRO-K002")
    assert len(f) == 1 and "xor_decrypt_kernel" in f[0].message \
        and "test_decode" in f[0].message


def test_k002_decode_suite_missing_entirely(tmp_path):
    repo = _repo(tmp_path, {
        "src/repro/kernels/fused_transform.py": "OP_FOO = 0\n",
        "src/repro/kernels/ref.py": "OP_FOO = 0\n",
        "tests/test_engine.py": "OP_FOO",
        "src/repro/kernels/decode.py": "def dense_unpack_kernel(b, v):\n"
                                       "    return v\n",
    })
    f = _findings(repo, "REPRO-K002")
    assert len(f) == 1 and "decode differential suite missing" in f[0].message


def test_k002_decode_negative_covered_and_module_absent(tmp_path):
    repo = _repo(tmp_path, {
        "src/repro/kernels/fused_transform.py": "OP_FOO = 0\n",
        "src/repro/kernels/ref.py": "OP_FOO = 0\n",
        "tests/test_engine.py": "OP_FOO",
        "src/repro/kernels/decode.py": "def ragged_gather_kernel(s, i, h):\n"
                                       "    return s\n",
        "tests/test_decode.py": "def test_gather():\n"
                                "    ragged_gather_kernel(1, 2, 3)\n",
    })
    assert _findings(repo, "REPRO-K002") == []
    bare = _kernel_repo(tmp_path / "bare2", "OP_FOO = 0\n", "OP_FOO = 0\n",
                        "OP_FOO")
    assert _findings(bare, "REPRO-K002") == []


# -- REPRO-M001/M002: metrics contract ---------------------------------------

WORKER_METRICS = """\
    import dataclasses

    from repro.obs import counter, gauge

    @dataclasses.dataclass
    class WorkerMetrics:
        batches: int = counter()
        bytes_read: int = counter()
        bytes_stored: int = gauge()
"""


def _bench_findings(repo):
    return [f for f in _findings(repo, "REPRO-M001")
            if f.path.startswith("benchmarks/")]


def test_m001_unknown_field_on_getter_local(tmp_path):
    repo = _repo(tmp_path, {
        "src/repro/core/dpp/worker.py": WORKER_METRICS,
        "benchmarks/bench_x.py": """\
            def main(sess):
                m = sess.worker_metrics()
                return m.batches + m.bogus_field
        """,
    })
    f = _bench_findings(repo)
    assert len(f) == 1 and ".bogus_field" in f[0].message


def test_m001_unknown_field_on_metrics_chain(tmp_path):
    repo = _repo(tmp_path, {
        "src/repro/core/dpp/worker.py": WORKER_METRICS,
        "benchmarks/bench_x.py": """\
            def main(sess):
                return sess.prefetcher.metrics.nonexistent
        """,
    })
    f = _bench_findings(repo)
    assert len(f) == 1 and ".nonexistent" in f[0].message


def test_m001_negative_known_fields_and_reassignment(tmp_path):
    repo = _repo(tmp_path, {
        "src/repro/core/dpp/worker.py": WORKER_METRICS,
        "benchmarks/bench_x.py": """\
            def main(sess, table, p):
                m = sess.worker_metrics()
                total = m.batches + m.bytes_read
                m = table.partitions[p]        # tracking must drop here
                return total + m.footer.num_rows
        """,
    })
    assert _bench_findings(repo) == []


def test_m002_counter_decrements(tmp_path):
    repo = _repo(tmp_path, {
        "src/repro/core/dpp/worker.py": WORKER_METRICS,
        "src/repro/core/foo.py": """\
            def oops(m):
                m.batches -= 1
                m.bytes_read = m.bytes_read - 4
        """,
    })
    f = _findings(repo, "REPRO-M002")
    assert len(f) == 2
    assert {".batches" in x.message or ".bytes_read" in x.message for x in f} == {True}


def test_m002_negative_gauge_and_increment(tmp_path):
    repo = _repo(tmp_path, {
        "src/repro/core/dpp/worker.py": WORKER_METRICS,
        "src/repro/core/foo.py": """\
            def fine(m, n):
                m.batches += 1
                m.bytes_stored -= n      # gauge: eviction shrinks it
        """,
    })
    assert _findings(repo, "REPRO-M002") == []


def test_m001_drift_when_no_metric_class_discovered(tmp_path):
    repo = _repo(tmp_path, {
        "src/repro/core/foo.py": """\
            import dataclasses

            @dataclasses.dataclass
            class NotMetrics:
                batches: int = 0
        """,
    })
    f = _findings(repo, "REPRO-M001")
    assert len(f) == 1 and "no metric class discovered" in f[0].message


def test_m001_discovery_needs_no_hand_kept_list(tmp_path):
    # a metric class in a brand-new module is picked up automatically
    repo = _repo(tmp_path, {
        "src/repro/core/shiny/new_module.py": WORKER_METRICS,
        "benchmarks/bench_x.py": """\
            def main(sess):
                m = sess.worker_metrics()
                return m.batches + m.bytes_stored + m.bogus
        """,
    })
    f = _bench_findings(repo)
    assert len(f) == 1 and ".bogus" in f[0].message


# -- REPRO-S001: span hygiene ------------------------------------------------


def test_s001_span_assigned_to_variable(tmp_path):
    repo = _repo(tmp_path, {"src/repro/core/foo.py": """\
        class Thing:
            def work(self):
                h = self.tracer.span("storage.read")
                h.__enter__()
    """})
    f = _findings(repo, "REPRO-S001")
    assert len(f) == 1 and f[0].symbol == "Thing.work"


def test_s001_bare_span_call_expression(tmp_path):
    repo = _repo(tmp_path, {"src/repro/core/foo.py": """\
        def work(tracer):
            tracer.span("cache.fill", bytes=1)
    """})
    assert len(_findings(repo, "REPRO-S001")) == 1


def test_s001_with_span_and_atomic_apis_ok(tmp_path):
    repo = _repo(tmp_path, {"src/repro/core/foo.py": """\
        class Thing:
            def work(self, row):
                with self.tracer.span("storage.read") as sp:
                    sp.set(bytes=2)
                self.tracer.record("client.stall", 0.0, 1.0)
                self.tracer.instant("cache.hit")
                return row.span("not-a-tracer")   # unrelated .span method
    """})
    assert _findings(repo, "REPRO-S001") == []


def test_s001_phase_outside_with(tmp_path):
    repo = _repo(tmp_path, {"src/repro/core/foo.py": """\
        from repro.obs import phase

        class Thing:
            def work(self, stats):
                with phase(self.tracer, "extract.host", stats, "host_s"):
                    pass
                p = phase(self.tracer, "extract.unpack", stats, "unpack_s")
                p.__enter__()
    """})
    f = _findings(repo, "REPRO-S001")
    assert len(f) == 1 and f[0].symbol == "Thing.work"


def test_s001_scope_is_core_only(tmp_path):
    repo = _repo(tmp_path, {"src/repro/train/foo.py": """\
        def work(tracer):
            return tracer.span("train.step")
    """})
    assert _findings(repo, "REPRO-S001") == []


# -- REPRO-T001/T002: thread hygiene -----------------------------------------


def test_t001_unbound_thread_never_joined(tmp_path):
    repo = _repo(tmp_path, {"src/repro/m.py": """\
        import threading

        def fire(fn):
            threading.Thread(target=fn).start()
    """})
    f = _findings(repo, "REPRO-T001")
    assert len(f) == 1 and f[0].symbol == "fire"


def test_t001_bound_thread_never_joined(tmp_path):
    repo = _repo(tmp_path, {"src/repro/m.py": """\
        import threading

        class S:
            def start(self):
                self._t = threading.Thread(target=self.run)
                self._t.start()
    """})
    assert len(_findings(repo, "REPRO-T001")) == 1


def test_t001_negative_daemon_join_and_loop_join(tmp_path):
    repo = _repo(tmp_path, {"src/repro/m.py": """\
        import threading

        def a(fn):
            threading.Thread(target=fn, daemon=True).start()

        def b(fn):
            t = threading.Thread(target=fn)
            t.start()
            t.join()

        def c(fns):
            ts = [threading.Thread(target=f) for f in fns]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
    """})
    assert _findings(repo, "REPRO-T001") == []


def test_t002_bare_except(tmp_path):
    repo = _repo(tmp_path, {"src/repro/m.py": """\
        def a():
            try:
                risky()
            except:
                pass

        def b():
            try:
                risky()
            except:
                return None
    """})
    assert len(_findings(repo, "REPRO-T002")) == 2


def test_t002_negative_typed_except(tmp_path):
    repo = _repo(tmp_path, {"src/repro/m.py": """\
        def a():
            try:
                risky()
            except Exception:
                pass
            except (KeyError, ValueError) as e:
                raise e
    """})
    assert _findings(repo, "REPRO-T002") == []


# -- suppression: inline noqa + baseline -------------------------------------


def test_noqa_on_finding_line(tmp_path):
    repo = _repo(tmp_path, {"src/repro/m.py": LOCKED_HEADER + """\

        def bump(self):
            self.x += 1  # repro: noqa(REPRO-L001)
    """})
    assert _findings(repo, "REPRO-L001") == []


def test_noqa_on_line_above_and_bare(tmp_path):
    repo = _repo(tmp_path, {"src/repro/m.py": LOCKED_HEADER + """\

        def bump(self):
            # repro: noqa
            self.x += 1
    """})
    assert _findings(repo, "REPRO-L001") == []


def test_noqa_for_other_rule_does_not_suppress(tmp_path):
    repo = _repo(tmp_path, {"src/repro/m.py": LOCKED_HEADER + """\

        def bump(self):
            self.x += 1  # repro: noqa(REPRO-T001)
    """})
    assert len(_findings(repo, "REPRO-L001")) == 1


def test_baseline_moves_finding_to_known(tmp_path):
    repo = _repo(tmp_path, {"src/repro/m.py": LOCKED_HEADER + """\

        def bump(self):
            self.x += 1
    """})
    new, known = run_checks(repo, rules=["REPRO-L001"])
    assert len(new) == 1 and known == []
    new2, known2 = run_checks(
        repo, rules=["REPRO-L001"], baseline=[new[0].key],
    )
    assert new2 == [] and len(known2) == 1
    # baseline keys are line-free: adding a blank line must not invalidate
    p = repo / "src/repro/m.py"
    p.write_text("\n" + p.read_text())
    new3, known3 = run_checks(
        repo, rules=["REPRO-L001"], baseline=[new[0].key],
    )
    assert new3 == [] and len(known3) == 1


# -- CLI contract -------------------------------------------------------------


def test_cli_clean_on_real_tree():
    """The acceptance bar: the gate exits 0 on the repo itself (with its
    checked-in baseline)."""
    assert cli_main(["--root", str(REPO), "-q"]) == 0


def test_cli_real_baseline_is_empty():
    assert load_baseline(REPO / "scripts" / "analysis_baseline.txt") == []


def test_cli_fails_on_violation(tmp_path, capsys):
    repo = _repo(tmp_path, {"src/repro/m.py": LOCKED_HEADER + """\

        def bump(self):
            self.x += 1
    """})
    rc = cli_main(["--root", str(repo), "--no-baseline",
                   "--rules", "REPRO-L001"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "REPRO-L001" in out and "src/repro/m.py" in out and "FAIL" in out


def test_cli_unknown_rule_is_usage_error(tmp_path):
    repo = _repo(tmp_path, {"src/repro/m.py": "x = 1\n"})
    assert cli_main(["--root", str(repo), "--rules", "REPRO-Z999"]) == 2


def test_cli_write_baseline_roundtrip(tmp_path, capsys):
    repo = _repo(tmp_path, {"src/repro/m.py": LOCKED_HEADER + """\

        def bump(self):
            self.x += 1
    """})
    base = repo / "scripts" / "analysis_baseline.txt"
    args = ["--root", str(repo), "--rules", "REPRO-L001",
            "--baseline", str(base)]
    assert cli_main(args + ["--write-baseline"]) == 0
    assert len(load_baseline(base)) == 1
    assert cli_main(args) == 0          # baselined -> green
    capsys.readouterr()


def test_cli_list_rules(capsys):
    assert cli_main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rid in all_rules():
        assert rid in out
    assert len(all_rules()) == 13


def test_rule_catalog_is_stable():
    assert sorted(all_rules()) == [
        "REPRO-C001",
        "REPRO-K001", "REPRO-K002",
        "REPRO-L001", "REPRO-L002", "REPRO-L003",
        "REPRO-M001", "REPRO-M002",
        "REPRO-R001", "REPRO-R002",
        "REPRO-S001",
        "REPRO-T001", "REPRO-T002",
    ]


# -- REPRO-R001: unlocked assignment on a race-instrumented class ------------

RACED_WORKER_HEADER = """\
    import threading

    class DPPWorker:
        def __init__(self):
            self._lock = threading.Lock()
            self.alive = True
"""


def test_r001_unlocked_assign_on_instrumented_class(tmp_path):
    repo = _repo(tmp_path, {"src/repro/core/dpp/worker.py":
                            RACED_WORKER_HEADER + """\

        def _run(self):
            self.alive = False
    """})
    f = _findings(repo, "REPRO-R001")
    assert len(f) == 1 and f[0].symbol == "DPPWorker._run"
    assert "_unshared" in f[0].message


def test_r001_lockless_instrumented_class_flagged(tmp_path):
    repo = _repo(tmp_path, {"src/repro/core/dpp/prefetch.py": """\
        class PrefetchPlanner:
            def __init__(self):
                self.depth = 4

            def set_depth(self, d):
                self.depth = d
    """})
    f = _findings(repo, "REPRO-R001")
    assert len(f) == 1 and f[0].symbol == "PrefetchPlanner.set_depth"


def test_r001_negative_unshared_declaration(tmp_path):
    repo = _repo(tmp_path, {"src/repro/core/dpp/prefetch.py": """\
        class PrefetchPlanner:
            _unshared = ("depth",)

            def __init__(self):
                self.depth = 4

            def set_depth(self, d):
                self.depth = d
    """})
    assert _findings(repo, "REPRO-R001") == []


def test_r001_negative_assign_under_lock_or_elsewhere(tmp_path):
    # locked assignment is fine; so is the same shape on a class that is
    # not in the instrumented set (plain module path)
    repo = _repo(tmp_path, {
        "src/repro/core/dpp/worker.py": RACED_WORKER_HEADER + """\

        def _run(self):
            with self._lock:
                self.alive = False
    """,
        "src/repro/other.py": """\
        class Uninstrumented:
            def __init__(self):
                self.alive = True

            def _run(self):
                self.alive = False
    """})
    assert _findings(repo, "REPRO-R001") == []


# -- REPRO-R002: double-checked locking --------------------------------------


def test_r002_unlocked_test_of_published_attr(tmp_path):
    repo = _repo(tmp_path, {"src/repro/fs.py": """\
        import threading

        class FS:
            def __init__(self):
                self._lock = threading.Lock()
                self.cache = None

            def attach(self, c):
                with self._lock:
                    self.cache = c

            def read(self):
                if self.cache is None:
                    return 0
                return 1
    """})
    f = _findings(repo, "REPRO-R002")
    assert len(f) == 1 and f[0].symbol == "FS.read"
    assert "self.cache" in f[0].message


def test_r002_chained_attr_test_flagged(tmp_path):
    repo = _repo(tmp_path, {"src/repro/fs.py": """\
        import threading

        class FS:
            def __init__(self):
                self._lock = threading.Lock()
                self.tracer = None

            def attach(self, t):
                with self._lock:
                    self.tracer = t

            def read(self):
                if self.tracer.enabled:
                    return 1
                return 0
    """})
    f = _findings(repo, "REPRO-R002")
    assert len(f) == 1 and "self.tracer" in f[0].message


def test_r002_negative_snapshot_into_local(tmp_path):
    repo = _repo(tmp_path, {"src/repro/fs.py": """\
        import threading

        class FS:
            def __init__(self):
                self._lock = threading.Lock()
                self.cache = None

            def attach(self, c):
                with self._lock:
                    self.cache = c

            def read(self):
                with self._lock:
                    cache = self.cache
                if cache is None:
                    return 0
                return 1
    """})
    assert _findings(repo, "REPRO-R002") == []
