"""End-to-end request tracing for the serving lane: proxy->replica->
engine waterfalls, head-side tail sampling, and SLO exemplars.

The acceptance surface for the request-plane tracing work:

  * unit: W3C traceparent interop, cheap span IDs, retroactive emits,
    the ASCII waterfall renderer;
  * unit: TraceStore tail sampling (errors + slowest p% always kept,
    the rest probabilistic, bounded per-deployment retention);
  * e2e: a streaming LLM request through the REAL HTTP proxy produces
    ONE connected trace (proxy root -> replica -> prefill -> decode
    steps, TTFT/last-token events), retrievable via state.get_trace
    and renderable by `rtpu trace show`;
  * e2e: preempt/resume under a tight KV pool lands llm.preempt /
    llm.resume spans on the VICTIM's own trace;
  * e2e: @serve.batch requests carry batch_wait slices + a
    batch_execute anchor span;
  * acceptance demo: serve.status()'s quantile row yields an exemplar
    trace_id whose waterfall shows the full request anatomy.
"""

import dataclasses
import json
import threading
import time
import urllib.request

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

import ray_tpu  # noqa: E402
from ray_tpu._private.telemetry import TraceStore  # noqa: E402
from ray_tpu.models.gpt import GPTConfig  # noqa: E402
from ray_tpu.util import state, tracing  # noqa: E402

CFG = GPTConfig(vocab_size=512, max_seq=128, d_model=64, n_layer=2,
                n_head=4, dtype=jnp.float32)

DEVICE = {"scheduling_strategy": "device"}


# ---------------------------------------------------------------------------
# Unit: traceparent / IDs / emit / waterfall (no runtime needed)
# ---------------------------------------------------------------------------
class TestTraceContext:
    def test_traceparent_roundtrip(self):
        ctx = {"trace_id": "a" * 32, "span_id": "b" * 16}
        hdr = tracing.format_traceparent(ctx)
        assert hdr == f"00-{'a' * 32}-{'b' * 16}-01"
        assert tracing.parse_traceparent(hdr) == ctx

    def test_traceparent_lowercases(self):
        hdr = f"00-{'A' * 32}-{'B' * 16}-01"
        assert tracing.parse_traceparent(hdr) == {
            "trace_id": "a" * 32, "span_id": "b" * 16}

    @pytest.mark.parametrize("bad", [
        None, "", "garbage",
        "00-short-0123456789abcdef-01",            # trace id wrong length
        f"00-{'a' * 32}-short-01",                 # span id wrong length
        f"00-{'g' * 32}-{'b' * 16}-01",            # non-hex trace id
        f"00-{'a' * 32}-{'b' * 16}",               # missing flags
        f"00-{'0' * 32}-{'b' * 16}-01",            # all-zero trace id
        f"00-{'a' * 32}-{'0' * 16}-01",            # all-zero span id
    ])
    def test_traceparent_rejects_malformed(self, bad):
        assert tracing.parse_traceparent(bad) is None

    def test_cheap_ids_unique_and_wellformed(self):
        tids = {tracing.new_trace_id() for _ in range(5000)}
        sids = {tracing.new_span_id() for _ in range(5000)}
        assert len(tids) == 5000 and len(sids) == 5000
        for t in list(tids)[:10]:
            assert len(t) == 32 and int(t, 16) >= 0
        for s in list(sids)[:10]:
            assert len(s) == 16 and int(s, 16) >= 0

    def test_emit_without_context_is_noop(self):
        tracing.drain_request_spans()
        assert tracing.emit("x", None, time.time(), 0.01) is None
        assert tracing.emit("x", {}, time.time(), 0.01) is None
        assert tracing.drain_request_spans() == []

    def test_emit_records_parented_retro_span(self):
        tracing.drain_request_spans()
        ctx = {"trace_id": "c" * 32, "span_id": "d" * 16}
        rec = tracing.emit("serve.replica_queue", ctx, 100.0, 0.25,
                           {"deployment": "d1"})
        spans = tracing.drain_request_spans()
        assert rec in spans
        assert rec["trace_id"] == ctx["trace_id"]
        assert rec["parent_id"] == ctx["span_id"]
        assert rec["end"] - rec["start"] == pytest.approx(0.25)
        assert rec["kind"] == "request"

    def test_request_spans_route_to_their_own_ring(self):
        """kind="request" spans never leak into the task plane (and so
        never reach get_spans / the opt-in exporters' task tables)."""
        tracing.drain_request_spans()
        tracing.drain_local_spans()
        with tracing.span("serve.request", kind="request"):
            pass
        assert tracing.local_spans() == []
        reqs = tracing.drain_request_spans()
        assert [s["name"] for s in reqs] == ["serve.request"]


class TestWaterfall:
    def _spans(self):
        t0 = 1000.0
        root = {"name": "serve.request", "trace_id": "t" * 32,
                "span_id": "r" * 16, "parent_id": None,
                "start": t0, "end": t0 + 0.010, "pid": 1,
                "attributes": {"deployment": "d"},
                "events": [{"name": "ttft", "ts": t0 + 0.004}]}
        child = {"name": "llm.prefill", "trace_id": "t" * 32,
                 "span_id": "c" * 16, "parent_id": "r" * 16,
                 "start": t0 + 0.002, "end": t0 + 0.004, "pid": 2,
                 "attributes": {"error": "ValueError: boom"}}
        return [root, child]

    def test_renders_bars_events_and_errors(self):
        text = tracing.render_waterfall(self._spans())
        lines = text.splitlines()
        assert lines[0].startswith(f"trace {'t' * 32}")
        assert "10.0 ms" in lines[0] and "2 spans" in lines[0]
        assert any(line.startswith("serve.request") and "#" in line
                   for line in lines)
        # Child indented under the root, flagged as the erroring span.
        assert any("  llm.prefill" in line and "ERROR" in line
                   for line in lines)
        assert any("` ttft" in line and "^" in line for line in lines)

    def test_empty_trace(self):
        assert tracing.render_waterfall([]) == "(empty trace)\n"

    def test_orphan_parent_becomes_root(self):
        spans = self._spans()[1:]  # child whose parent never arrived
        text = tracing.render_waterfall(spans)
        assert "llm.prefill" in text


# ---------------------------------------------------------------------------
# Unit: head-side tail sampling
# ---------------------------------------------------------------------------
def _mk_trace(tid, dur_ms=5.0, dep="dep", error=False, t0=1000.0,
              rootless=False):
    spans = []
    if not rootless:
        spans.append({
            "name": "serve.request", "trace_id": tid,
            "span_id": "a" + tid[:15], "parent_id": None,
            "start": t0, "end": t0 + dur_ms / 1e3, "pid": 1,
            "attributes": {"deployment": dep}, "kind": "request"})
    spans.append({
        "name": "serve.replica", "trace_id": tid,
        "span_id": "b" + tid[:15],
        "parent_id": None if rootless else "a" + tid[:15],
        "start": t0, "end": t0 + dur_ms / 2e3, "pid": 2,
        "attributes": ({"error": "RuntimeError: x"} if error else {}),
        "kind": "request"})
    return spans


class TestTraceStoreTailSampling:
    def test_keeps_errors_and_slow_drops_the_rest(self):
        ts = TraceStore(sample_rate=0.0, slow_fraction=0.05,
                        window=64, linger_s=0.0)
        # Warm the per-deployment duration history past the 20-sample
        # trust threshold with a spread of durations (1..30 ms).
        for i in range(30):
            ts.ingest(_mk_trace(f"{i:032x}", dur_ms=1.0 + i))
        # Fast trace, no error, sample_rate 0 -> dropped.
        ts.ingest(_mk_trace("f" * 32, dur_ms=2.0))
        assert ts.get("f" * 32) is None
        # Much slower than the p95 of recent history -> kept as "slow".
        ts.ingest(_mk_trace("e" * 32, dur_ms=500.0))
        slow_spans = ts.get("e" * 32)
        assert slow_spans and len(slow_spans) == 2
        # Fast but erroring -> always kept.
        ts.ingest(_mk_trace("d" * 32, dur_ms=2.0, error=True))
        assert ts.get("d" * 32) is not None
        rows = ts.list(deployment="dep", errors_only=True)
        assert [r["trace_id"] for r in rows] == ["d" * 32]
        assert rows[0]["reason"] == "error" and rows[0]["error"]
        by_id = {r["trace_id"]: r for r in ts.list(limit=100)}
        assert by_id["e" * 32]["reason"] == "slow"
        assert ts.stats["dropped"] >= 1

    def test_warmup_keeps_everything(self):
        """Until 20 durations exist for a deployment the slow threshold
        is untrusted: every trace is retained."""
        ts = TraceStore(sample_rate=0.0, linger_s=0.0)
        for i in range(10):
            ts.ingest(_mk_trace(f"{i:032x}", dur_ms=1.0))
        assert ts.stats["kept"] == 10 and ts.stats["dropped"] == 0

    def test_ring_eviction_bounds_retention(self):
        ts = TraceStore(sample_rate=0.0, window=2, linger_s=0.0)
        tids = [f"{i:032x}" for i in range(5)]
        for tid in tids:
            ts.ingest(_mk_trace(tid, dur_ms=3.0, error=True))
        rows = ts.list(limit=100)
        assert len(rows) == 2
        assert ts.get(tids[0]) is None       # evicted, spans freed too
        assert ts.get(tids[-1]) is not None
        assert ts.summary()["retained"] == 2

    def test_min_ms_filter_and_limit(self):
        ts = TraceStore(sample_rate=0.0, linger_s=0.0)
        for i in range(6):
            ts.ingest(_mk_trace(f"{i:032x}", dur_ms=10.0 * (i + 1),
                                t0=1000.0 + i))
        rows = ts.list(min_ms=35.0, limit=2)
        assert len(rows) == 2
        assert all(r["duration_ms"] >= 35.0 for r in rows)
        # Newest first.
        assert rows[0]["start"] > rows[1]["start"]

    def test_rootless_trace_expires_through_same_decision(self):
        ts = TraceStore(sample_rate=0.0, linger_s=0.0, max_age_s=0.0)
        ts.ingest(_mk_trace("c" * 32, dur_ms=2.0, error=True,
                            rootless=True))
        spans = ts.get("c" * 32)
        assert spans is not None and spans[0]["name"] == "serve.replica"
        rows = ts.list()
        assert rows and rows[0]["deployment"] == "?"

    def test_straggler_spans_graft_into_retained_trace(self):
        ts = TraceStore(sample_rate=0.0, linger_s=0.0)
        ts.ingest(_mk_trace("a" * 32, dur_ms=4.0))
        assert len(ts.get("a" * 32)) == 2
        # A worker's flusher delivers one more span after finalize.
        ts.ingest([{
            "name": "llm.decode_step", "trace_id": "a" * 32,
            "span_id": "z" * 16, "parent_id": "b" + "a" * 15,
            "start": 1000.001, "end": 1000.002, "pid": 3,
            "attributes": {}, "kind": "request"}])
        names = [s["name"] for s in ts.get("a" * 32)]
        assert "llm.decode_step" in names and len(names) == 3

    def test_pending_trace_visible_before_finalize(self):
        ts = TraceStore(linger_s=60.0)
        ts.ingest(_mk_trace("b" * 32, dur_ms=4.0))
        spans = ts.get("b" * 32)      # still pending: partial view
        assert spans and ts.summary()["pending"] == 1


# ---------------------------------------------------------------------------
# E2E fixtures (real proxy + head TraceStore; short linger so traces
# finalize quickly)
# ---------------------------------------------------------------------------
@pytest.fixture(autouse=True)
def _restore_global_config():
    from ray_tpu._private.config import get_config

    cfg = get_config()
    saved = dataclasses.asdict(cfg)
    yield
    for k, v in saved.items():
        setattr(cfg, k, v)


@pytest.fixture
def rt_trace():
    ray_tpu.shutdown()
    tracing.drain_request_spans()  # stale spans from unit tests
    rt = ray_tpu.init(num_cpus=2, system_config={
        "telemetry_sample_interval_s": 0.05,
        "trace_linger_s": 0.2})
    from ray_tpu import serve

    try:
        yield rt, serve
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


def _stream_http(url, payload, timeout=180, headers=None):
    """POST and fully drain a streaming response; returns
    (x-rtpu-trace-id header, ndjson frames)."""
    hdrs = {"Content-Type": "application/json"}
    hdrs.update(headers or {})
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(), headers=hdrs)
    with urllib.request.urlopen(req, timeout=timeout) as r:
        tid = r.headers.get("x-rtpu-trace-id")
        frames = [json.loads(line) for line in r.read().splitlines()
                  if line.strip()]
    return tid, frames


def _deploy_llm(serve, **kw):
    from ray_tpu.serve.llm import build_app

    serve.run(build_app(CFG, **kw), name="llm")
    proxy = serve.start(http_port=0)
    return f"http://127.0.0.1:{proxy.port}/"


def _poll_trace(tid, want_names, deadline_s=90.0):
    """Poll the head's TraceStore until every wanted span name has
    landed (root rides the node heartbeat; worker spans ride the 1s
    flusher, so arrival is staggered)."""
    deadline = time.monotonic() + deadline_s
    spans = None
    while time.monotonic() < deadline:
        spans = state.get_trace(tid)
        if spans and want_names <= {s["name"] for s in spans}:
            return spans
        time.sleep(0.3)
    got = sorted({s["name"] for s in (spans or [])})
    raise AssertionError(
        f"trace {tid}: wanted {sorted(want_names)}, got {got}")


def _assert_connected(spans):
    """Every span belongs to one trace and parents into it."""
    tids = {s["trace_id"] for s in spans}
    assert len(tids) == 1, tids
    ids = {s["span_id"] for s in spans}
    for s in spans:
        assert s.get("parent_id") is None or s["parent_id"] in ids, s


# ---------------------------------------------------------------------------
# E2E: proxy root spans + traceparent interop (cheap deployment)
# ---------------------------------------------------------------------------
def test_inbound_traceparent_joins_external_trace(rt_trace):
    _, serve = rt_trace

    @serve.deployment(ray_actor_options=DEVICE)
    class Echo:
        def __call__(self, body):
            return {"echo": body}

    serve.start(http_port=0)
    serve.run(Echo.bind(), route_prefix="/")
    from ray_tpu.serve import api as serve_api

    url = f"http://127.0.0.1:{serve_api._proxy.port}/"
    ext_trace = "ab" * 16
    hdr = f"00-{ext_trace}-{'12' * 8}-01"
    req = urllib.request.Request(
        url, data=json.dumps({"a": 1}).encode(),
        headers={"Content-Type": "application/json",
                 "traceparent": hdr})
    with urllib.request.urlopen(req, timeout=30) as resp:
        assert json.loads(resp.read()) == {"echo": {"a": 1}}
        # The caller's trace id is honored, not replaced.
        assert resp.headers.get("x-rtpu-trace-id") == ext_trace
    spans = _poll_trace(ext_trace, {"serve.request", "serve.proxy_queue",
                                    "serve.replica"})
    root = next(s for s in spans if s["name"] == "serve.request")
    assert root["trace_id"] == ext_trace
    # The external caller's span is the root's parent.
    assert root["parent_id"] == "12" * 8


def test_batched_requests_carry_batch_spans(rt_trace):
    _, serve = rt_trace

    @serve.deployment(max_ongoing_requests=32,
                      ray_actor_options=DEVICE)
    class Batched:
        @serve.batch(max_batch_size=8, batch_wait_timeout_s=0.2)
        def __call__(self, items):
            return [{"v": i} for i in items]

    serve.start(http_port=0)
    serve.run(Batched.bind(), route_prefix="/")
    from ray_tpu.serve import api as serve_api

    url = f"http://127.0.0.1:{serve_api._proxy.port}/"
    tids: dict = {}

    def worker(i):
        tids[i], frames = _stream_http(url, i, timeout=60)
        assert frames == [{"v": i}]

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert len(tids) == 6 and all(tids.values())

    # Every request's waterfall shows its parked interval; the batch
    # execution span anchors to (at least) the oldest waiter's trace.
    execute_seen = 0
    for tid in tids.values():
        spans = _poll_trace(tid, {"serve.request", "serve.replica",
                                  "serve.batch_wait"})
        _assert_connected(spans)
        for s in spans:
            if s["name"] == "serve.batch_execute":
                execute_seen += 1
                assert s["attributes"]["batch_size"] >= 1
                assert "oldest_wait_ms" in s["attributes"]
    assert execute_seen >= 1


# ---------------------------------------------------------------------------
# E2E: the LLM streaming waterfall + the acceptance demo
# ---------------------------------------------------------------------------
def test_streaming_llm_request_yields_one_connected_trace(rt_trace):
    """The demo walkthrough: a mixed workload with one artificially
    slow streaming request; serve.status()'s quantile row carries an
    exemplar trace id whose waterfall (state.get_trace + `rtpu trace
    show`) shows proxy_queue -> replica -> prefill -> per-decode-step
    spans with a recorded TTFT event."""
    _, serve = rt_trace
    url = _deploy_llm(serve, num_blocks=64, block_size=8, max_batch=4)

    # Mixed workload: short requests plus one slow straggler (6x the
    # output tokens -> 6x the decode steps and root duration).
    tid_slow, frames = _stream_http(
        url, {"prompt": [1, 2, 3], "max_tokens": 24, "seed": 0})
    assert frames[-1]["done"] and frames[-1]["num_tokens"] == 24
    for i in range(3):
        tid, frames = _stream_http(
            url, {"prompt": [5, 6, 7], "max_tokens": 4, "seed": i + 1})
        assert frames[-1]["done"]
    assert tid_slow

    want = {"serve.request", "serve.proxy_queue", "serve.replica",
            "llm.prefill", "llm.decode_step"}
    # Decode-step spans ride the worker's 1s flusher in batches, so the
    # first poll that sees every NAME may still hold a partial
    # waterfall — keep polling until the step count settles.
    deadline = time.monotonic() + 60
    while True:
        spans = _poll_trace(tid_slow, want)
        steps = [s for s in spans if s["name"] == "llm.decode_step"]
        if len(steps) >= 20 or time.monotonic() >= deadline:
            break
        time.sleep(0.5)
    _assert_connected(spans)

    root = next(s for s in spans if s["name"] == "serve.request")
    ev_names = [e["name"] for e in root.get("events", [])]
    assert "ttft" in ev_names and "last_token" in ev_names
    ttft_ev = next(e for e in root["events"] if e["name"] == "ttft")
    assert ttft_ev["ts"] >= root["start"]

    # 24 output tokens -> 23+ decode steps, each slice carrying the
    # batch composition + pool pressure of its step.
    assert len(steps) >= 20
    assert all("kv_util" in s["attributes"] for s in steps)
    prefill = next(s for s in spans if s["name"] == "llm.prefill")
    assert prefill["attributes"]["tokens"] == 3

    # serve.status()'s quantile rows point at a retained exemplar.
    deadline = time.monotonic() + 60
    ex_tid = None
    while time.monotonic() < deadline:
        lat = (serve.status().get("LLMServer") or {}).get("latency") or {}
        row = lat.get("ttft") or {}
        ex_tid = row.get("exemplar_trace_id")
        if ex_tid and row.get("count", 0) >= 4:
            assert row["exemplar_ms"] >= 0.0
            break
        time.sleep(0.5)
    assert ex_tid, "no ttft exemplar surfaced in serve.status()"
    ex_spans = _poll_trace(ex_tid, {"serve.request", "llm.prefill"})

    # p99 -> root cause, rendered: the exemplar's ASCII waterfall.
    text = tracing.render_waterfall(ex_spans)
    assert text.startswith(f"trace {ex_tid}")
    for name in ("serve.proxy_queue", "llm.prefill", "llm.decode_step"):
        assert name in text, text
    assert "` ttft" in text, text


def test_trace_cli_and_chrome_export(rt_trace, capsys, tmp_path):
    _, serve = rt_trace
    url = _deploy_llm(serve, num_blocks=64, block_size=8, max_batch=4)
    tid, frames = _stream_http(
        url, {"prompt": [9, 9, 9], "max_tokens": 6, "seed": 3})
    assert frames[-1]["done"] and tid
    _poll_trace(tid, {"serve.request", "llm.prefill",
                      "llm.decode_step"})
    # `trace list` shows FINALIZED traces only: wait out the linger
    # window (get_trace also serves pending traces, so the poll above
    # can return before the tail sampler has run).
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        if any(r["trace_id"] == tid
               for r in state.list_traces(limit=100)):
            break
        time.sleep(0.3)

    from ray_tpu.scripts.cli import cmd_trace_list, cmd_trace_show

    class ListArgs:
        address = None
        deployment = None
        min_ms = 0.0
        errors_only = False
        limit = 50

    cmd_trace_list(ListArgs())
    out = capsys.readouterr().out
    assert "TRACE" in out and tid in out

    out_file = str(tmp_path / "trace.json")

    class ShowArgs:
        address = None
        id = tid
        output = out_file

    cmd_trace_show(ShowArgs())
    out = capsys.readouterr().out
    assert f"trace {tid}" in out
    assert "llm.decode_step" in out and "` ttft" in out
    assert "chrome trace written" in out

    events = json.load(open(out_file))
    assert events, "per-trace chrome export is empty"
    assert all(e["tid"] == tid[:8] for e in events)
    assert any(e["ph"] == "i" and "ttft" in e["name"] for e in events)
    assert all("dur" in e for e in events if e["ph"] == "X")

    # Unknown id: friendly message, not a traceback.
    class MissingArgs:
        address = None
        id = "0" * 32
        output = None

    cmd_trace_show(MissingArgs())
    assert "not retained" in capsys.readouterr().out


def test_preemption_links_victim_trace(rt_trace):
    """Over-admission on a tiny KV pool: the evicted request's OWN
    waterfall records the preempt and the later resume, so a stalled
    token cadence is explainable from the trace alone."""
    import engine_by_hand

    _, serve = rt_trace
    with engine_by_hand.held() as engines:
        url = _deploy_llm(serve, num_blocks=6, block_size=8, max_batch=4)
    eng, = engines
    tids: dict = {}

    def worker(i):
        # 5 + 30 tokens fill the pool's 5 blocks: any two streams in
        # flight together preempt (tests/test_serve_llm.py has why).
        tids[i], frames = _stream_http(
            url, {"prompt": [3, 1, 4, 1, 5], "max_tokens": 30,
                  "seed": i, "temperature": 0.9})
        assert frames[-1]["done"]

    # One stream decodes alone for four steps, then two join it: the
    # three are in flight together whatever the box is doing, because
    # the test is what steps the engine.
    threads = [threading.Thread(target=worker, args=(i,)) for i in range(3)]
    threads[0].start()
    engine_by_hand.arrived(eng, 1)
    engine_by_hand.drive(eng, steps=4)
    for t in threads[1:]:
        t.start()
    engine_by_hand.arrived(eng, 3)
    engine_by_hand.drive(eng)
    for t in threads:
        t.join(timeout=180)
    assert len(tids) == 3 and all(tids.values())
    from ray_tpu.llm.engine import PREEMPTED

    victims = {rid for _, rid, s in eng.events() if s == PREEMPTED}
    assert victims, "three 35-token streams fitted a 40-token pool"

    # Preempt/resume land on the worker flusher after the streams
    # finish: poll until every preempted trace also shows its resume.
    deadline = time.monotonic() + 90
    preempts: list = []
    resumes: list = []
    while time.monotonic() < deadline:
        preempts, resumes = [], []
        for tid in tids.values():
            for s in state.get_trace(tid) or []:
                if s["name"] == "llm.preempt":
                    assert s["trace_id"] == tid  # the victim's trace
                    preempts.append(s)
                elif s["name"] == "llm.resume":
                    resumes.append(s)
        if len({s["trace_id"] for s in preempts}) == len(victims) and \
                {s["trace_id"] for s in preempts} == \
                {s["trace_id"] for s in resumes}:
            break
        time.sleep(0.5)
    # One victim's trace for each request the engine preempted.
    assert len({s["trace_id"] for s in preempts}) == len(victims)
    for s in preempts:
        assert s["attributes"]["preemptions"] >= 1
        assert "kv_util" in s["attributes"]
    # Every preemption's victim eventually resumed on its own trace.
    assert {s["trace_id"] for s in preempts} == \
        {s["trace_id"] for s in resumes}
