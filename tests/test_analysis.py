"""Fixture suite for ``ray_tpu.analysis`` — proves every checker
family catches its seeded violation and stays quiet on the matching
clean variant.

Layout: each test writes small fixture modules into ``tmp_path`` and
runs the real pass over them (``run_lint`` falls back to scanning the
given root when it holds no ``ray_tpu/`` package), selecting only the
checker under test so fixture noise from other families can't leak in.
The I4xx tests are the meta-tests for the five lints migrated out of
``tests/test_concurrency_net.py``: each one proves the known-bad
fixture (a weak spawn, a silent transition, a missed gauge, a dropped
trace hop, a bypassed step-accounting feed) is still caught, including
the rename-erases-the-site case the old tests enforced.
"""

import json
import textwrap

import pytest

from ray_tpu.analysis import baseline as baseline_mod
from ray_tpu.analysis import run_lint
from ray_tpu.analysis.core import parse_porcelain


def lint(tmp_path, files, select, config=None):
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    return run_lint(tmp_path, select=select, use_baseline=False,
                    config=config)


# ---------------------------------------------------------------------------
# C101 — blocking calls under a held lock
# ---------------------------------------------------------------------------
def test_c101_direct_blocking_calls(tmp_path):
    rep = lint(tmp_path, {"svc.py": """\
        import threading, time

        class Svc:
            def __init__(self):
                self._lock = threading.Lock()

            def bad_sleep(self):
                with self._lock:
                    time.sleep(1)

            def bad_socket(self):
                with self._lock:
                    self.sock.sendall(b"x")

            def bad_queue(self):
                with self._lock:
                    self.out_q.get()

            def ok_timed_queue(self):
                with self._lock:
                    self.out_q.get(timeout=1)

            def ok_unlocked(self):
                time.sleep(1)
        """}, select="C101")
    by_sym = {f.symbol: f for f in rep.findings}
    assert set(by_sym) == {"Svc.bad_sleep", "Svc.bad_socket",
                           "Svc.bad_queue"}
    assert by_sym["Svc.bad_sleep"].severity == "P1"
    assert by_sym["Svc.bad_socket"].severity == "P0"
    assert by_sym["Svc.bad_queue"].severity == "P0"
    assert "Svc._lock" in by_sym["Svc.bad_sleep"].message


def test_c101_one_hop_through_a_helper(tmp_path):
    """``with self._lock: self._flush()`` where _flush blocks is just
    as wedged as inlining the helper — the finding names the callee
    and the blocking line."""
    rep = lint(tmp_path, {"svc.py": """\
        import threading

        class Svc:
            def __init__(self):
                self._lock = threading.Lock()

            def _flush(self):
                self.sock.sendall(b"x")

            def tick(self):
                with self._lock:
                    self._flush()
        """}, select="C101")
    tick = [f for f in rep.findings if f.symbol == "Svc.tick"]
    assert len(tick) == 1
    assert "self._flush()" in tick[0].message
    # The direct finding inside _flush itself does NOT fire (no lock
    # held lexically there).
    assert not [f for f in rep.findings if f.symbol == "Svc._flush"]


def test_c101_statement_level_acquire_release(tmp_path):
    rep = lint(tmp_path, {"svc.py": """\
        import threading, time

        class Svc:
            def __init__(self):
                self._lock = threading.Lock()

            def bad(self):
                self._lock.acquire()
                time.sleep(1)
                self._lock.release()

            def ok(self):
                self._lock.acquire()
                self._lock.release()
                time.sleep(1)
        """}, select="C101")
    assert [f.symbol for f in rep.findings] == ["Svc.bad"]


# ---------------------------------------------------------------------------
# C102 — await under a sync lock
# ---------------------------------------------------------------------------
def test_c102_await_under_sync_lock(tmp_path):
    rep = lint(tmp_path, {"svc.py": """\
        import asyncio, threading

        class Svc:
            def __init__(self):
                self._lock = threading.Lock()
                self._alock = asyncio.Lock()

            async def bad(self):
                with self._lock:
                    await asyncio.sleep(0)

            async def ok_async_lock(self):
                async with self._alock:
                    await asyncio.sleep(0)

            def ok_sync(self):
                with self._lock:
                    pass
        """}, select="C102")
    assert [f.symbol for f in rep.findings] == ["Svc.bad"]
    assert "event loop parks" in rep.findings[0].message


# ---------------------------------------------------------------------------
# C103 — lock-order inversion (3-lock cycle fixture)
# ---------------------------------------------------------------------------
def test_c103_three_lock_inversion_cycle(tmp_path):
    rep = lint(tmp_path, {"svc.py": """\
        import threading

        class Svc:
            def __init__(self):
                self._a_lock = threading.Lock()
                self._b_lock = threading.Lock()
                self._c_lock = threading.Lock()

            def ab(self):
                with self._a_lock:
                    with self._b_lock:
                        pass

            def bc(self):
                with self._b_lock:
                    with self._c_lock:
                        pass

            def ca(self):
                with self._c_lock:
                    with self._a_lock:
                        pass
        """}, select="C103")
    assert len(rep.findings) == 1
    f = rep.findings[0]
    assert f.severity == "P0"
    for lk in ("Svc._a_lock", "Svc._b_lock", "Svc._c_lock"):
        assert lk in f.snippet


def test_c103_consistent_ordering_is_clean(tmp_path):
    rep = lint(tmp_path, {"svc.py": """\
        import threading

        class Svc:
            def __init__(self):
                self._a_lock = threading.Lock()
                self._b_lock = threading.Lock()
                self._c_lock = threading.Lock()

            def ab(self):
                with self._a_lock:
                    with self._b_lock:
                        pass

            def ac(self):
                with self._a_lock:
                    with self._c_lock:
                        pass

            def bc(self):
                with self._b_lock:
                    with self._c_lock:
                        pass
        """}, select="C103")
    assert not rep.findings


def test_c103_one_hop_edge_through_a_method(tmp_path):
    """``with self._a: self._helper()`` where the helper takes
    ``self._b`` contributes the A→B edge interprocedurally."""
    rep = lint(tmp_path, {"svc.py": """\
        import threading

        class Svc:
            def __init__(self):
                self._a_lock = threading.Lock()
                self._b_lock = threading.Lock()

            def _helper(self):
                with self._b_lock:
                    pass

            def forward(self):
                with self._a_lock:
                    self._helper()

            def backward(self):
                with self._b_lock:
                    with self._a_lock:
                        pass
        """}, select="C103")
    assert len(rep.findings) == 1
    assert "self._helper()" in rep.findings[0].message


# ---------------------------------------------------------------------------
# C104 — guard inference + aliasing
# ---------------------------------------------------------------------------
def test_c104_alias_counts_as_the_same_guard(tmp_path):
    """``l = self._lock; with l:`` guards the same lock — the aliased
    write must count toward guard inference, not fire as bare."""
    rep = lint(tmp_path, {"svc.py": """\
        import threading

        class Svc:
            def __init__(self):
                self._lock = threading.Lock()
                self._buf = []

            def push(self, x):
                with self._lock:
                    self._buf.append(x)

            def push_aliased(self, x):
                l = self._lock
                with l:
                    self._buf.append(x)

            def racy(self, x):
                self._buf.append(x)
        """}, select="C104")
    assert len(rep.findings) == 1
    f = rep.findings[0]
    assert f.symbol == "Svc.racy"
    assert "Svc._lock" in f.message and "2 site(s)" in f.message


def test_c104_private_callee_entered_holding_guard_is_clean(tmp_path):
    """A private method only ever called with the guard already held
    is not a bare-write site — including when it recurses."""
    rep = lint(tmp_path, {"svc.py": """\
        import threading

        class Svc:
            def __init__(self):
                self._lock = threading.Lock()
                self._buf = []

            def push(self, x):
                with self._lock:
                    self._buf.append(x)

            def push2(self, x):
                with self._lock:
                    self._write(x)

            def _write(self, x):
                self._buf.append(x)
                if x:
                    self._write(None)
        """}, select="C104")
    assert not rep.findings


# ---------------------------------------------------------------------------
# E201 — swallowed broad excepts
# ---------------------------------------------------------------------------
def test_e201_variants(tmp_path):
    rep = lint(tmp_path, {"m.py": """\
        import logging

        def swallow():
            try:
                work()
            except Exception:
                pass

        def noqa_without_reason():
            try:
                work()
            except Exception:  # noqa: BLE001
                pass

        def annotated():
            try:
                work()
            except Exception:  # lint: allow-swallow(best-effort probe)
                pass

        def noqa_with_reason():
            try:
                work()
            except Exception:  # noqa: BLE001 - dead handle
                pass

        def logged():
            try:
                work()
            except Exception:
                logging.exception("boom")

        def reraised():
            try:
                work()
            except Exception:
                raise

        def narrow():
            try:
                work()
            except ValueError:
                pass

        def uses_bound_var():
            try:
                work()
            except Exception as e:
                record(str(e))
        """}, select="E201")
    assert sorted(f.symbol for f in rep.findings) == [
        "noqa_without_reason", "swallow"]


# ---------------------------------------------------------------------------
# D301 / D302 — device lane
# ---------------------------------------------------------------------------
def test_d301_host_sync_in_hot_loop(tmp_path):
    rep = lint(tmp_path, {"hot.py": """\
        import numpy as np
        import jax

        def step(xs):
            out = []
            for x in xs:
                out.append(np.asarray(jax.device_get(x)))
            return out

        def setup(x):
            return np.asarray(x)  # outside any loop: fine
        """}, select="D301",
               config={"device_hot_modules": ("hot.py",)})
    # np.asarray(jax.device_get(x)) is ONE sync — dedup reports the
    # outermost call only.
    assert len(rep.findings) == 1
    assert rep.findings[0].symbol == "step"
    assert "np.asarray" in rep.findings[0].message


def test_d301_only_fires_in_configured_hot_modules(tmp_path):
    rep = lint(tmp_path, {"cold.py": """\
        import numpy as np

        def step(xs):
            return [np.asarray(x) for x in xs]
        """}, select="D301",
               config={"device_hot_modules": ("hot.py",)})
    assert not rep.findings


def test_d302_shape_branch_in_jitted_fn(tmp_path):
    rep = lint(tmp_path, {"m.py": """\
        import jax

        @jax.jit
        def bad(x):
            if x.shape[0] > 1:
                return x * 2
            return x

        def plain(x):
            if x.shape[0] > 1:
                return x * 2
            return x

        def wrapped(x):
            while len(x) > 0:
                x = x[1:]
            return x

        step = jax.jit(wrapped)
        """}, select="D302")
    assert sorted(f.symbol for f in rep.findings) == ["bad", "wrapped"]
    assert "retraces" in rep.findings[0].message


# ---------------------------------------------------------------------------
# I401..I405 — the five migrated invariant lints (meta-tests)
# ---------------------------------------------------------------------------
def test_i401_catches_a_weak_spawn_site(tmp_path):
    rep = lint(tmp_path, {"fix/svc.py": """\
        import asyncio

        class S:
            def weak(self, coro):
                asyncio.ensure_future(coro)

            def kept(self, coro):
                self._keep_task(asyncio.ensure_future(coro))

            def assigned(self, coro):
                t = asyncio.create_task(coro)
                return t
        """}, select="I401", config={"spawn_packages": ("fix",)})
    assert len(rep.findings) == 1
    assert rep.findings[0].severity == "P0"
    assert "ensure_future(coro)" in rep.findings[0].snippet


def test_i402_catches_a_silent_transition_site(tmp_path):
    tables = (("svc.py", "_event", ("good", "bad", "gone"), "why"),)
    rep = lint(tmp_path, {"svc.py": """\
        class S:
            def good(self):
                self._event("x", 1)

            def bad(self):
                return 2
        """}, select="I402", config={"I402_tables": tables})
    missing = sorted(f.symbol for f in rep.findings)
    # "bad" emits nothing; "gone" was renamed away — both are exactly
    # the bug class the old test-file lint enforced.
    assert missing == ["bad", "gone"]
    assert all(f.severity == "P0" for f in rep.findings)


def test_i402_missing_file_is_a_finding(tmp_path):
    tables = (("vanished.py", "_event", ("m",), "why"),)
    rep = lint(tmp_path, {"other.py": "x = 1\n"},
               select="I402", config={"I402_tables": tables})
    assert len(rep.findings) == 1
    assert rep.findings[0].path == "vanished.py"
    assert "missing" in rep.findings[0].message


def test_i410_catches_a_silent_alert_transition(tmp_path):
    # Same driver as I402, aimed at the alert engine: an incident
    # open/resolve/refire that never appends to the incident's event
    # log is exactly the silent-pager-timeline bug class.
    tables = (("eng.py", "_event",
               ("_open_incident", "_resolve_incident", "_refire"),
               "why"),)
    rep = lint(tmp_path, {"eng.py": """\
        class Engine:
            def _open_incident(self, st, now):
                self._event(st, "open", now)

            def _resolve_incident(self, st, now):
                st.state = "resolved"

            def _refire(self, st, inc, now):
                self._event(inc, "refire", now)
        """}, select="I410", config={"I410_tables": tables})
    assert [f.symbol for f in rep.findings] == ["_resolve_incident"]
    assert all(f.severity == "P0" for f in rep.findings)


def test_i410_real_table_names_live_sites():
    # The shipped table must point at methods that actually exist in
    # ray_tpu/_private/alerting.py — run the checker against the real
    # repo subtree and require zero findings.
    from pathlib import Path

    import ray_tpu as _pkg

    root = Path(_pkg.__file__).resolve().parent.parent
    rep = run_lint(root, paths=["ray_tpu/_private/alerting.py"],
                   select="I410", use_baseline=False)
    assert not rep.findings, [f.message for f in rep.findings]


def test_i411_catches_an_import_against_the_layers(tmp_path):
    """``ops <- models <- llm <- serve``, by the real tables: an import
    of a higher package from a lower one is a finding at ANY depth of
    nesting, and so is a sibling's underscore name inside ``models/``,
    imported or reached through the module. What points down, a public
    name, a module's own private and a dunder are clean."""
    rep = lint(tmp_path, {
        "ray_tpu/ops/kernel.py": """\
            from ..models import serving            # finding: ops -> models
            def f():
                import ray_tpu.serve.llm            # finding: ops -> serve
            """,
        "ray_tpu/models/fam.py": """\
            from ..ops import moe                   # down: clean
            from .shared import rmsnorm, __all__    # public, dunder: clean
            from .other import _rmsnorm, head       # finding: _rmsnorm
            from . import other as ot, shared
            from .. import llm                      # finding: models -> llm
            def chunk():
                def inner():
                    from ..llm.kv_cache import scatter_span   # finding
                return ot._counters(1) + ot.counters(1)       # finding: 1
            def _mine(): return _mine
            """,
        "ray_tpu/models/other.py": "def _counters(x): return x\n",
        "ray_tpu/llm/engine.py": """\
            from ..models import serving            # down: clean
            def g():
                from ..serve import deployment      # finding: llm -> serve
            """,
        "ray_tpu/serve/llm.py": "from ..llm import engine\n",
    }, select="I411")
    got = sorted((f.path, f.line) for f in rep.findings)
    assert got == [("ray_tpu/llm/engine.py", 3),
                   ("ray_tpu/models/fam.py", 3),
                   ("ray_tpu/models/fam.py", 5),
                   ("ray_tpu/models/fam.py", 8),
                   ("ray_tpu/models/fam.py", 9),
                   ("ray_tpu/ops/kernel.py", 1),
                   ("ray_tpu/ops/kernel.py", 3)], got
    private = [f.message for f in rep.findings if "private" in f.message]
    assert len(private) == 2 and "_rmsnorm" in private[0] \
        and "ot._counters" in private[1]
    assert all(f.severity == "P0" for f in rep.findings)


def test_i403_catches_a_gaugeless_queue_mutation(tmp_path):
    tables = (("svc.py", "_gauge_queues", ("enq", "deq"), "why"),)
    rep = lint(tmp_path, {"svc.py": """\
        class S:
            def enq(self, x):
                self.pending.append(x)
                self._gauge_queues()

            def deq(self):
                return self.pending.pop()
        """}, select="I403", config={"I403_tables": tables})
    assert [f.symbol for f in rep.findings] == ["deq"]


def test_i404_catches_a_trace_dropping_hop(tmp_path):
    tables = (("svc.py", "trace_ctx", ("H.fwd", "H.drop"), "why"),)
    rep = lint(tmp_path, {"svc.py": """\
        class H:
            def fwd(self, req):
                return self.inner(req, trace_ctx=req.trace_ctx)

            def drop(self, req):
                return self.inner(req)
        """}, select="I404", config={"I404_tables": tables})
    assert [f.symbol for f in rep.findings] == ["H.drop"]


def test_i405_catches_a_bypassed_step_accounting_feed(tmp_path):
    tables = (("svc.py", "_step_perf", ("E.step", "E.decode"), "why"),)
    rep = lint(tmp_path, {"svc.py": """\
        class E:
            def step(self):
                self._step_perf.record(1)

            def decode(self):
                return 2
        """}, select="I405", config={"I405_tables": tables})
    assert [f.symbol for f in rep.findings] == ["E.decode"]


def test_i406_catches_an_unrecorded_collective_site(tmp_path):
    tables = (("svc.py", "record_op", ("G.allreduce", "G.barrier"),
               "why"),)
    rep = lint(tmp_path, {"svc.py": """\
        class G:
            def allreduce(self, arrays):
                with record_op(self.name, "allreduce", self.axis, arrays):
                    return sum(arrays)

            def barrier(self):
                return None
        """}, select="I406", config={"I406_tables": tables})
    assert [f.symbol for f in rep.findings] == ["G.barrier"]


def test_i407_catches_a_silent_batch_or_spill_site(tmp_path):
    # Two-table shape mirrors the real rows: the batch-inference
    # operator lifecycle (_event) and the store spill ledger
    # (_spill_event) are audited by the same checker.
    tables = (
        ("op.py", "_event", ("apply", "stop"), "why"),
        ("store.py", "_spill_event", ("spill", "restore"), "why"),
    )
    rep = lint(tmp_path, {"op.py": """\
        class W:
            def apply(self, blk):
                self._event("EMIT", rows=1)
                return blk

            def stop(self):
                return None
        """, "store.py": """\
        class S:
            def spill(self, oid):
                self._spill_event("S", oid, 4)

            def restore(self, oid):
                return open(oid)
        """}, select="I407", config={"I407_tables": tables})
    missing = sorted((f.path, f.symbol) for f in rep.findings)
    assert missing == [("op.py", "stop"), ("store.py", "restore")]
    assert all(f.severity == "P0" for f in rep.findings)


def test_i408_catches_a_silent_prefix_pool_transition(tmp_path):
    # Mirrors the real row: every prefix-pool state change (share,
    # COW split, evict) must flow through _event or the hit-rate
    # series diverge from what the allocator actually did.
    tables = (("pool.py", "_event", ("admit", "cow", "_evict_one"),
               "why"),)
    rep = lint(tmp_path, {"pool.py": """\
        class P:
            def admit(self, seq, need):
                self._event("share", tokens=8)
                return [], 8

            def cow(self, bid):
                return bid + 1

            def _evict_one(self):
                self._event("evict", block=3)
        """}, select="I408", config={"I408_tables": tables})
    missing = sorted((f.path, f.symbol) for f in rep.findings)
    assert missing == [("pool.py", "cow")]
    assert all(f.severity == "P0" for f in rep.findings)


def test_i409_catches_a_silent_spec_transition(tmp_path):
    # Mirrors the real row: every speculative-decode lifecycle
    # transition (PROPOSE/VERIFY/ACCEPT/ROLLBACK) must flow through
    # _event or accept_rate / the llm_spec_* series diverge from what
    # the verify step actually did.
    tables = (("spec.py", "_event",
               ("propose", "verify", "accept", "rollback"), "why"),)
    rep = lint(tmp_path, {"spec.py": """\
        class S:
            def propose(self, rid, toks, budget):
                self._event("propose", rid=rid, n=2)
                return toks[:2]

            def verify(self, rid, n):
                self._event("verify", rid=rid, n=n)

            def accept(self, rid, n_acc, n_prop, n_emit):
                self.accepted += n_acc

            def rollback(self, rid, n_rej, freed):
                self.rolled_back += n_rej
        """}, select="I409", config={"I409_tables": tables})
    missing = sorted((f.path, f.symbol) for f in rep.findings)
    assert missing == [("spec.py", "accept"), ("spec.py", "rollback")]
    assert all(f.severity == "P0" for f in rep.findings)


# ---------------------------------------------------------------------------
# Suppression surfaces
# ---------------------------------------------------------------------------
def test_inline_disable_point_suppresses(tmp_path):
    rep = lint(tmp_path, {"m.py": """\
        def f():
            try:
                work()
            except Exception:  # lint: disable=E201
                pass
        """}, select="E201")
    assert not rep.findings


def test_baseline_round_trip_and_staleness(tmp_path):
    src_bad = textwrap.dedent("""\
        def f():
            try:
                work()
            except Exception:
                pass
        """)
    src_fixed = textwrap.dedent("""\
        def f():
            try:
                work()
            except Exception:
                raise
        """)
    (tmp_path / "m.py").write_text(src_bad)
    bl_path = tmp_path / "bl.json"

    raw = run_lint(tmp_path, select="E201", use_baseline=False)
    assert len(raw.findings) == 1
    baseline_mod.save(bl_path, raw.findings, {raw.findings[0].key():
                                              "legacy, tracked"})

    # Baselined: clean pass, finding absorbed, nothing stale.
    rep = run_lint(tmp_path, select="E201", baseline_path=bl_path)
    assert not rep.findings
    assert len(rep.suppressed) == 1
    assert not rep.stale_baseline

    # Fixing the site makes its entry STALE — the prune-me signal that
    # keeps baselined counts monotonically decreasing.
    (tmp_path / "m.py").write_text(src_fixed)
    rep = run_lint(tmp_path, select="E201", baseline_path=bl_path)
    assert not rep.findings
    assert len(rep.stale_baseline) == 1

    # Regenerating over the old file preserves the reviewer reason.
    (tmp_path / "m.py").write_text(src_bad)
    raw = run_lint(tmp_path, select="E201", use_baseline=False)
    entries = baseline_mod.save(bl_path, raw.findings)
    assert list(entries.values())[0]["reason"] == "legacy, tracked"


def test_baseline_count_budget_is_per_key(tmp_path):
    """Two identical swallow sites in one function share a key; the
    baseline budget absorbs exactly ``count`` of them."""
    (tmp_path / "m.py").write_text(textwrap.dedent("""\
        def f():
            try:
                work()
            except Exception:
                pass
            try:
                work()
            except Exception:
                pass
        """))
    raw = run_lint(tmp_path, select="E201", use_baseline=False)
    assert len(raw.findings) == 2
    bl_path = tmp_path / "bl.json"
    entries = baseline_mod.save(bl_path, raw.findings[:1])
    assert list(entries.values())[0]["count"] == 1
    rep = run_lint(tmp_path, select="E201", baseline_path=bl_path)
    assert len(rep.findings) == 1 and len(rep.suppressed) == 1


# ---------------------------------------------------------------------------
# Selection / plumbing
# ---------------------------------------------------------------------------
def test_unknown_selector_raises(tmp_path):
    (tmp_path / "m.py").write_text("x = 1\n")
    with pytest.raises(ValueError, match="C999"):
        run_lint(tmp_path, select="C999", use_baseline=False)


def test_family_selector(tmp_path):
    rep = lint(tmp_path, {"m.py": """\
        def f():
            try:
                work()
            except Exception:
                pass
        """}, select="exceptions")
    assert rep.checkers_run == ["E201"]
    assert len(rep.findings) == 1


def test_parse_porcelain():
    out = (" M ray_tpu/core.py\n"
           "?? new_file.py\n"
           "R  old.py -> ray_tpu/renamed.py\n"
           " M README.md\n"
           "D  gone.py\n")
    assert parse_porcelain(out) == [
        "ray_tpu/core.py", "new_file.py", "ray_tpu/renamed.py",
        "gone.py"]


def test_syntax_error_file_is_skipped(tmp_path):
    rep = lint(tmp_path, {
        "broken.py": "def f(:\n",
        "m.py": """\
        def f():
            try:
                work()
            except Exception:
                pass
        """}, select="E201")
    assert [f.path for f in rep.findings] == ["m.py"]


def test_json_output_is_valid_and_sorted(tmp_path):
    from ray_tpu.analysis import format_json
    rep = lint(tmp_path, {"m.py": """\
        def f():
            try:
                work()
            except Exception:
                pass
        """}, select="E201")
    doc = json.loads(format_json(rep))
    assert doc["version"] == 1
    assert doc["summary"]["total"] == 1
    assert doc["findings"][0]["checker"] == "E201"
