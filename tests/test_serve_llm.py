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
