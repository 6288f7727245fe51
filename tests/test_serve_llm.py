"""End-to-end LLM serving on CPU interpret mode: concurrent streaming
HTTP requests through the proxy, continuous-batching composition +
preempt/resume checked at the engine, TTFT/TPOT in serve.status(), and
the engine gauges surfacing as head time-series.

The deployment runs the REAL stack — paged Pallas kernel (interpret),
paged KV pool, continuous-batching engine — on the TINY-class config,
so these are the acceptance tests for the whole ray_tpu.llm subsystem.
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
from ray_tpu.models.gpt import GPTConfig  # noqa: E402
from ray_tpu.serve.replica import REPLY_SENT  # noqa: E402
from ray_tpu.util import state  # noqa: E402

CFG = GPTConfig(vocab_size=512, max_seq=128, d_model=64, n_layer=2,
                n_head=4, dtype=jnp.float32)


@pytest.fixture(autouse=True)
def _restore_global_config():
    from ray_tpu._private.config import get_config

    cfg = get_config()
    saved = dataclasses.asdict(cfg)
    yield
    for k, v in saved.items():
        setattr(cfg, k, v)


@pytest.fixture
def rt_llm():
    ray_tpu.shutdown()
    rt = ray_tpu.init(num_cpus=2, system_config={
        "telemetry_sample_interval_s": 0.05})
    from ray_tpu import serve

    try:
        yield rt, serve
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


def _stream_http(url, payload, timeout=180):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        assert r.headers.get("Content-Type") == "application/x-ndjson"
        return [json.loads(line) for line in r.read().splitlines()
                if line.strip()]


def _deploy(serve, **kw):
    from ray_tpu.serve.llm import build_app

    serve.run(build_app(CFG, **kw), name="llm")
    proxy = serve.start(http_port=0)
    return f"http://127.0.0.1:{proxy.port}/"


def test_concurrent_streams_mixed_lengths_through_proxy(rt_llm):
    """N concurrent streaming HTTP requests with mixed prompt/output
    lengths all complete through the proxy, each seeing one frame per
    token plus a final done frame."""
    _, serve = rt_llm
    url = _deploy(serve, num_blocks=64, block_size=8, max_batch=4)
    cases = [  # (prompt tokens, max_tokens)
        ([1, 2, 3], 4),
        ([5, 6, 7, 8, 9, 10, 11], 9),
        ("hello", 6),
        ([42] * 17, 3),
        ([100, 200, 300, 400], 12),
    ]
    results: dict = {}

    def worker(i, prompt, n):
        results[i] = _stream_http(
            url, {"prompt": prompt, "max_tokens": n, "seed": i,
                  "temperature": 0.8})

    threads = [threading.Thread(target=worker, args=(i, p, n))
               for i, (p, n) in enumerate(cases)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=180)
    assert len(results) == len(cases)
    for i, (_, n) in enumerate(cases):
        frames = results[i]
        toks = [f["token"] for f in frames if "token" in f]
        done = frames[-1]
        assert done["done"] and done["finish_reason"] == "length"
        assert len(toks) == n == done["num_tokens"]


def test_a_first_tokens_way_in_and_out_is_timed_around_the_engine(rt_llm):
    """One streamed request through the real proxy: ``proxy_ttft`` once,
    the handler's arrival to the first frame on the socket, which holds
    the engine's ``ttft``; ``stream_out`` once a reply that the loop
    wrote (as many as ``proxy_flush``); and ``engine_stats`` carries the
    process's CPU by thread and the probe's totals."""
    from ray_tpu.serve import slo

    _, serve = rt_llm
    slo._reset_for_tests()
    url = _deploy(serve, num_blocks=64, block_size=8, max_batch=4)
    frames = _stream_http(url, {"prompt": [7, 8, 9], "max_tokens": 6})
    assert frames[-1]["done"] and len(frames) == 7
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        hist = slo.phase_hist("LLMServer")
        if "proxy_ttft" in hist and hist.get("tpot", {}).get("count"):
            break
        time.sleep(0.05)
    assert hist["proxy_ttft"]["count"] == 1 == hist["ttft"]["count"]
    assert hist["proxy_ttft"]["sum"] >= hist["ttft"]["sum"]
    assert hist["stream_out"]["count"] == hist["proxy_flush"]["count"] >= 1
    assert hist["stream_out"]["sum"] >= hist["proxy_flush"]["sum"]
    assert {"stream_out", "proxy_ttft"} <= set(slo.PHASES)
    stats = serve.get_app_handle("llm").options(
        method_name="engine_stats").remote().result(timeout=60)
    groups = stats["threads"]["by_group"]
    assert {"serve-http", "llm-engine", "MainThread"} <= set(groups)
    assert groups["llm-engine"]["cpu_s"] > 0.0
    assert stats["threads"]["process_cpu_s"] > 0.0
    assert stats["interp"]["n"] >= 1
    assert stats["phase_hist"]["proxy_ttft"]["count"] == 1


def test_ttft_tpot_quantiles_and_llm_timeseries(rt_llm):
    """serve.status() reports TTFT/TPOT quantiles for the deployment
    and state.timeseries() serves tokens/s + KV-utilization series."""
    _, serve = rt_llm
    url = _deploy(serve, num_blocks=64, block_size=8, max_batch=4)
    for i in range(3):
        frames = _stream_http(
            url, {"prompt": [7, 8, 9], "max_tokens": 8, "seed": i})
        assert frames[-1]["done"]

    # Poll until every request's phases have LANDED (records ride
    # periodic replica flushes), not merely until the keys appear.
    deadline = time.monotonic() + 45
    lat = {}
    while time.monotonic() < deadline:
        lat = (serve.status().get("LLMServer") or {}).get("latency") or {}
        if all(lat.get(p, {}).get("count", 0) >= 3
               for p in ("ttft", "tpot")):
            break
        time.sleep(0.5)
    for phase in ("ttft", "tpot"):
        cell = lat.get(phase) or {}
        assert cell.get("count", 0) >= 3, lat
        assert 0.0 <= cell["p50_ms"] <= cell["p95_ms"] <= cell["p99_ms"]

    want = {"llm_tokens_per_s:LLMServer", "llm_kv_util:LLMServer",
            "llm_batch_size:LLMServer"}
    deadline = time.monotonic() + 45
    names, best = [], 0.0
    while time.monotonic() < deadline:
        names = state.timeseries_metrics()
        if want <= set(names):
            # Base tier (raw samples): coarser tiers only close their
            # bucket once a later sample lands, which can lag under load.
            series = state.timeseries("llm_tokens_per_s:LLMServer",
                                      resolution=0.05)["series"]
            by_node = series.get("llm_tokens_per_s:LLMServer", {})
            pts = [p for node_pts in by_node.values() for p in node_pts]
            if pts:
                best = max(max(v, hi) for _, v, hi in pts)
                if best > 0.0:
                    break
        time.sleep(0.5)
    assert want <= set(names), names
    assert best > 0.0


def test_late_join_and_preemption_through_serve(rt_llm):
    """The engine behind the deployment recomposes its batch mid-stream
    and survives over-admission: a tiny pool forces preempt+resume and
    the streamed tokens still match a run with a roomy pool."""
    import engine_by_hand
    from ray_tpu.llm.engine import PREEMPTED
    from ray_tpu.serve.llm import LLMServer

    _, serve = rt_llm
    seeds = range(3)

    def collect(url, eng):
        """Stream 0 decodes alone for four steps, then 1 and 2 join it
        mid-decode; the test steps the engine, so the three are in
        flight together whatever the box is doing."""
        out = {}

        def worker(i):
            # 5 + 30 tokens are all 5 blocks of the tight pool, so ANY
            # two streams in flight together force a preemption.
            out[i] = _stream_http(
                url, {"prompt": [3, 1, 4, 1, 5], "max_tokens": 30,
                      "seed": i, "temperature": 0.9})

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in seeds]
        threads[0].start()
        engine_by_hand.arrived(eng, 1)
        engine_by_hand.drive(eng, steps=4)
        for t in threads[1:]:
            t.start()
        engine_by_hand.arrived(eng, len(seeds))
        engine_by_hand.drive(eng)
        for t in threads:
            t.join(timeout=180)
        return {i: [f["token"] for f in fr if "token" in f]
                for i, fr in out.items()}, out

    with engine_by_hand.held(expect=2) as engines:
        url = _deploy(serve, num_blocks=64, block_size=8, max_batch=4)
        # Second app, tiny pool, side by side at its own route prefix:
        # capacity 5 blocks = 40 tokens < 2 sequences x (5 prompt + 30
        # out).
        serve.run(LLMServer.options(name="LLMTight").bind(
            CFG, num_blocks=6, block_size=8, max_batch=4),
            name="llm-tight", route_prefix="/tight")
    tight_eng, roomy_eng = sorted(engines, key=lambda e: e.kv.capacity)
    assert tight_eng.kv.capacity == 5

    roomy, _ = collect(url, roomy_eng)
    tight, frames = collect(url + "tight", tight_eng)
    assert all(len(toks) == 30 for toks in roomy.values()), roomy
    assert tight == roomy
    assert not [s for _, _, s in roomy_eng.events() if s == PREEMPTED]
    h = serve.get_app_handle("llm-tight")
    st = h.options(method_name="engine_stats").remote().result(
        timeout=60)
    assert st["finished"] == 3
    # The done frames carry the preemption count: over-admission must
    # have preempted at least once, and output still matched exactly.
    total_preempt = sum(fr[-1]["preemptions"] for fr in frames.values())
    assert total_preempt > 0, frames
    assert total_preempt == len(
        [s for _, _, s in tight_eng.events() if s == PREEMPTED])


_DETACHED_DRIVER = """
import json, os, urllib.request
import ray_tpu
from ray_tpu import serve
from ray_tpu.models import gpt
from ray_tpu.serve.llm import build_app
from ray_tpu.util import state

ray_tpu.init(address=os.environ["RT_ADDRESS"])
h = serve.run(build_app(gpt.TINY, num_blocks=64, block_size=8,
                        max_batch=4), name="llm")
proxy = serve.start(http_port=0)
st = h.options(method_name="engine_stats").remote().result(timeout=120)
req = urllib.request.Request(
    f"http://127.0.0.1:{proxy.port}/",
    data=json.dumps({"prompt": [1, 2, 3, 4], "max_tokens": 4}).encode(),
    headers={"Content-Type": "application/json"})
with urllib.request.urlopen(req, timeout=120) as r:
    frames = [json.loads(x) for x in r.read().splitlines() if x.strip()]
print("RESULT " + json.dumps({
    "platform": st["platform"], "paged_kernel": st["paged_kernel"],
    "frames": frames, "actors": state.list_actors(),
    "nodes": state.list_nodes()}, default=str))
serve.shutdown()
ray_tpu.shutdown()
"""


def test_readme_recipe_on_detached_cluster_lands_on_the_node_daemon(
        tmp_path):
    """`serve.run(build_app(...))` from a driver attached to an
    `rtpu start --head` cluster: the attached driver hosts no device
    lane, so the replica must be placed on the node daemon's (the
    process that owns the host's chips), construct there from a class
    the daemon never saw, and answer `engine_stats` and a stream."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    temp = str(tmp_path / "rtpu")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=repo + os.pathsep + os.environ.get(
                   "PYTHONPATH", ""))
    for k in ("RT_SESSION_TOKEN", "RT_ADDRESS"):
        env.pop(k, None)
    cli = [sys.executable, "-m", "ray_tpu.scripts.cli", "--temp-dir", temp]
    subprocess.run(cli + ["start", "--head", "--num-cpus", "2"], env=env,
                   check=True, timeout=90, capture_output=True)
    try:
        with open(os.path.join(temp, "head_address")) as f:
            addr = f.read().strip()
        out = subprocess.run(
            [sys.executable, "-u", "-c", _DETACHED_DRIVER],
            env=dict(env, RT_ADDRESS=addr,
                     RT_TOKEN_FILE=os.path.join(temp, "session_token")),
            capture_output=True, text=True, timeout=240)
        with open(os.path.join(temp, "node.log")) as f:
            node_log = f.read()
    finally:
        subprocess.run(cli + ["stop"], env=env, timeout=60,
                       capture_output=True)
    assert out.returncode == 0, out.stderr[-3000:]
    got = json.loads(next(line for line in out.stdout.splitlines()
                          if line.startswith("RESULT "))[7:])
    assert got["platform"] == "cpu" and got["paged_kernel"] == "interpret"
    assert [f["token"] for f in got["frames"][:-1]] and \
        got["frames"][-1]["done"] and got["frames"][-1]["num_tokens"] == 4
    daemon = next(n for n in got["nodes"] if not n["is_driver"])
    assert daemon["resources"]["device"] >= 1, node_log[-2000:]
    driver = next(n for n in got["nodes"] if n["is_driver"])
    assert driver["resources"]["device"] == 0
    replica = next(a for a in got["actors"]
                   if a["name"].startswith("SERVE:LLMServer"))
    assert replica["is_device"] and replica["node_id"] == daemon["node_id"]


# ---------------------------------------------------------------------------
# A step's tokens leave in one hand-over to the replica (PR 52): one
# replica by hand, no cluster, its engine stepped by the test.
# ---------------------------------------------------------------------------
import gc  # noqa: E402

import engine_by_hand  # noqa: E402

_POOL = dict(num_blocks=64, block_size=8, max_batch=4)


def _params():
    from ray_tpu.models.gpt import init

    return init(jax.random.PRNGKey(0), CFG)


@pytest.fixture
def by_hand():
    """make(**kw) -> (replica, engine): an ``_LLMServer`` replica whose
    engine's loop was never started."""
    from ray_tpu.serve import slo
    from ray_tpu.serve.llm import _LLMServer
    from ray_tpu.serve.replica import Replica

    slo._reset_for_tests()
    made = []

    def make(name="by_hand", **kw):
        with engine_by_hand.held() as engines:
            rep = Replica(_LLMServer, (CFG,),
                          dict(params=_params(), **_POOL, **kw),
                          deployment_name=name)
        made.append(rep)
        return rep, engines[0]

    yield make
    for rep in made:
        rep.instance.engine.stop()
    slo._reset_for_tests()


def _ask(rep, prompt, **kw):
    from ray_tpu.serve.replica import STREAM_MARKER

    return rep.handle_request(
        "__call__", ({"prompt": prompt, **kw},), {})[STREAM_MARKER]


def _read_all(rep, sids):
    """{sid: frames}: every stream drained through stream_poll."""
    for sid in sids:
        rep.stream_grant(sid, 16, "me")
    frames, open_sids = {sid: [] for sid in sids}, set(sids)
    while open_sids:
        reply = rep.stream_poll("me")
        assert reply, "streams that ended have their ends ready"
        assert reply.pop(REPLY_SENT) <= time.time()
        for sid, (chunks, done, error) in reply.items():
            assert error is None
            frames[sid] += chunks
            if done:
                open_sids.discard(sid)
    return frames


def test_a_steps_tokens_take_the_stream_condition_once_and_no_thread(
        by_hand):
    """(a, b) Four live streams: no ``serve-feed-*`` thread is started,
    one decode step takes ``_stream_cond`` once and notifies once, its
    ring entry counts one hand-over of four tokens, and the next
    stream_poll reply carries all four streams' frames."""
    from ray_tpu.util import perfmodel

    class Counted(threading.Condition):
        entered = notified = 0

        def __enter__(self):
            Counted.entered += 1
            return super().__enter__()

        def notify_all(self):
            Counted.notified += 1
            super().notify_all()

    def feeders():
        return {t.name for t in threading.enumerate()
                if t.name.startswith("serve-feed-")}

    rep, eng = by_hand()
    rep._stream_cond = Counted()
    before = feeders()
    sids = [_ask(rep, [3, 1, 4, 1, 5, i], max_tokens=12) for i in range(4)]
    assert feeders() == before
    for sid in sids:
        rep.stream_grant(sid, 16, "me")
    engine_by_hand.drive(eng, steps=2)       # prompts in, lanes taken
    assert len([r for r in eng._active if r.lane is not None]) == 4
    rep.stream_poll("me")                    # take what the prefill left
    perfmodel.clear_device_steps()
    Counted.entered = Counted.notified = 0
    eng.step()
    assert (Counted.entered, Counted.notified) == (1, 1)
    (entry,) = [e for e in perfmodel.device_step_events()
                if e.get("deployment") == eng.name]
    assert (entry["handovers"], entry["tokens_handed"]) == (1, 4)
    reply = rep.stream_poll("me")
    del reply[REPLY_SENT]
    assert sorted(reply) == sids
    assert all(len(chunks) == 1 and "token" in chunks[0] and not done
               for chunks, done, _ in reply.values())
    engine_by_hand.drive(eng)
    assert feeders() == before
    perfmodel.clear_device_steps()


@pytest.mark.parametrize("speculative", [None, {"mode": "ngram", "k": 3}],
                         ids=["plain", "proposer"])
@pytest.mark.parametrize("temperature", [0.0, 0.8], ids=["greedy", "sampled"])
def test_a_pushed_stream_delivers_what_tokens_delivers(
        by_hand, temperature, speculative):
    """(c) Frame for frame: the same prompts and seeds through a bare
    engine's ``Request.tokens()`` and through the replica's streams give
    the same tokens in the same order, the final frame last."""
    from ray_tpu.llm.engine import LLMEngine
    from ray_tpu.serve.llm import decode

    asks = [dict(prompt=[5, 6, 7, 5, 6, 7, 5, 6], max_tokens=14, seed=1),
            dict(prompt=[9, 8, 7, 6], max_tokens=9, seed=2),
            dict(prompt=[5, 6, 7, 5, 6, 7, 5, 6], max_tokens=3, seed=3)]
    bare = LLMEngine(_params(), CFG, **_POOL, prefill_chunk_tokens=32,
                     speculative=speculative)
    reqs = [bare.add_request(a["prompt"], a["max_tokens"], seed=a["seed"],
                             temperature=temperature) for a in asks]
    engine_by_hand.drive(bare)
    want = [list(r.tokens()) for r in reqs]
    assert [len(w) for w in want] == [14, 9, 3]

    rep, eng = by_hand(speculative=speculative)
    sids = [_ask(rep, temperature=temperature, **a) for a in asks]
    engine_by_hand.drive(eng)
    frames = _read_all(rep, sids)
    for sid, toks, req in zip(sids, want, reqs):
        assert frames[sid][:-1] == [{"token": t} for t in toks]
        assert frames[sid][-1] == {
            "done": True, "finish_reason": "length",
            "num_tokens": len(toks), "preemptions": 0,
            "cached_tokens": req.cached_tokens, "text": decode(toks)}
    if speculative is not None and not temperature:
        # A greedy loop repeats itself: several tokens left in a step.
        assert eng.stats()["spec"]["accepted"] > 0


def _alive(refs):
    gc.collect()
    return [r().rid for r in refs if r() is not None]


def test_phases_are_recorded_once_a_request_and_finished_requests_go(
        by_hand):
    """(e, f) ``ttft``, ``engine_queue`` and ``tpot`` once a request;
    when a request's last frame has been handed over nothing of the
    engine or the replica holds the request (it has no ``_requests``),
    and ``stats()`` still answers."""
    import weakref

    rep, eng = by_hand()
    assert not hasattr(eng, "_requests")
    answers = (9, 2, 12)
    sids = [_ask(rep, [1, 2, 3, i + 4], max_tokens=n)
            for i, n in enumerate(answers)]
    refs = [weakref.ref(r) for r in eng._waiting]
    assert _alive(refs) == [1, 2, 3]
    engine_by_hand.drive(eng, steps=2)       # the two-token answer is out
    assert _alive(refs) == [1, 3]
    engine_by_hand.drive(eng)
    assert _alive(refs) == []
    stats = rep.instance.engine_stats()
    assert stats["finished"] == 3 and stats["in_flight"] == 0
    frames = _read_all(rep, sids)
    assert [len(frames[sid]) for sid in sids] == [n + 1 for n in answers]
    for phase in ("ttft", "engine_queue", "tpot"):
        assert stats["phase_hist"][phase]["count"] == 3, phase


def test_a_request_without_a_sink_leaves_by_its_queue_and_is_forgotten():
    """(f) The engine outside Serve: tokens on ``out_q``, no hand-over
    counted, a finished request held by its caller alone, and no
    consumer without a sink."""
    import weakref

    from ray_tpu.llm.engine import LLMEngine
    from ray_tpu.util import perfmodel

    perfmodel.clear_device_steps()
    eng = LLMEngine(_params(), CFG, **_POOL, name="no_sink")
    with pytest.raises(ValueError, match="sink"):
        eng.add_request([1, 2, 3], max_tokens=2, consumer=object())
    short = eng.add_request([1, 2, 3], max_tokens=2)
    long = eng.add_request([4, 5, 6], max_tokens=9)
    refs = [weakref.ref(short), weakref.ref(long)]
    engine_by_hand.drive(eng, steps=3)
    assert len(list(short.tokens())) == 2
    del short
    assert _alive(refs) == [long.rid]
    engine_by_hand.drive(eng)
    assert eng.stats()["finished"] == 2
    assert len(list(long.tokens())) == 9
    del long
    assert _alive(refs) == []
    ring = [e for e in perfmodel.device_step_events()
            if e.get("deployment") == "no_sink"]
    assert ring and all(e["handovers"] == 0 and e["tokens_handed"] == 0
                        for e in ring)
    perfmodel.clear_device_steps()


def test_a_serving_process_hands_the_interpreter_on_every_millisecond(
        by_hand):
    """A replica's engine thread and the serving threads share one
    interpreter: building a deployment brings the process's switch
    interval down to ``SWITCH_INTERVAL_S`` (PR 61: at CPython's 5 ms a
    shorter device step cost a first token 6-10 ms), never up; a bare
    engine leaves the process as it was."""
    import sys

    from ray_tpu.llm.engine import LLMEngine
    from ray_tpu.serve import llm as serve_llm

    before = sys.getswitchinterval()
    try:
        sys.setswitchinterval(0.005)
        LLMEngine(_params(), CFG, **_POOL)
        near = lambda s: pytest.approx(s, abs=2e-6)   # kept in whole us
        assert sys.getswitchinterval() == near(0.005)
        by_hand("interval_a")
        assert sys.getswitchinterval() == near(serve_llm.SWITCH_INTERVAL_S)
        sys.setswitchinterval(0.0002)
        by_hand("interval_b")
        assert sys.getswitchinterval() == near(0.0002)
    finally:
        sys.setswitchinterval(before)


def test_the_replica_freezes_once_a_process_and_an_engine_never(
        by_hand, monkeypatch):
    """(g) The set-up's heap leaves the collector's sight once, when the
    process's first replica is warm: as many of its requests as its
    engine has lanes (four here) have been handed over whole since the
    process last built a program. A bare engine's process is left
    alone."""
    from ray_tpu.llm.engine import LLMEngine
    from ray_tpu.serve import llm as serve_llm

    calls = []
    monkeypatch.setattr(gc, "collect", lambda *a: calls.append("collect"))
    monkeypatch.setattr(gc, "freeze", lambda: calls.append("freeze"))
    monkeypatch.setattr(serve_llm, "_frozen", False)

    def answer(rep, eng, n):
        """``n`` requests one after another, each run to its end."""
        for _ in range(n):
            sid = _ask(rep, [1, 2, 3], max_tokens=6)
            engine_by_hand.drive(eng)
        return sid

    bare = LLMEngine(_params(), CFG, **_POOL)
    for _ in range(6):
        req = bare.add_request([1, 2, 3], max_tokens=3)
        engine_by_hand.drive(bare)
    assert len(list(req.tokens())) == 3 and calls == []

    first, eng1 = by_hand("freeze_a")
    second, eng2 = by_hand("freeze_b")
    # Three, whether or not the first of them built the step programs.
    answer(first, eng1, 3)
    assert calls == []
    # A program built now (a warm-up of another shape) starts the count
    # again: the fourth request in a row is not the fourth since.
    built = serve_llm._programs_built
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)).block_until_ready()
    assert serve_llm._programs_built > built
    answer(first, eng1, 3)
    assert calls == []
    sid = answer(first, eng1, 1)
    assert calls == ["collect", "freeze"]
    # Its last frame was handed over before the process stood still.
    assert _read_all(first, [sid])[sid][-1]["done"]
    answer(first, eng1, 5)
    answer(second, eng2, 5)
    assert calls == ["collect", "freeze"]
