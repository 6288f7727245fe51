"""The serving cells' callers run in generator subprocesses
(``benchmark/drivers/callers.py``, PR 56): what a generator sends is
what the parent commit's in-process callers sent for the same cell and
``--seed``, every frame comes back stamped on the one clock both
processes read, the close cuts what is in flight, no generator loads
the program or jax, and ``callers_cpu_pct`` reads the busiest one."""

import http.server
import itertools
import json
import os
import threading
import time

import pytest

from benchmark import harness, traffic
from benchmark.drivers import callers

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = "callers_cpu_pct"
SERVING = ["gpt2s-serve-chat", "laguna-xs2-serve-repo",
           "kimi-k25-serve-docs"]
with open(os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                       "callers_parent_requests.json")) as _f:
    PARENT = json.load(_f)["cases"]
META = ("caller", "index", "prefix", "body", "prompt_len", "max_tokens")


@pytest.mark.parametrize("case", range(len(PARENT)))
def test_a_callers_requests_are_the_parent_commits(case):
    """Field for field, the first twelve of each pinned caller: built
    from the plan by caller index, as a generator builds them."""
    pinned = PARENT[case]
    plan = traffic.closed_loop_plan(pinned["traffic"], pinned["seed"],
                                    pinned["vocab"])
    for c, theirs in pinned["callers"].items():
        mine = itertools.islice(callers.requests_of(int(c), plan), 12)
        assert [{"meta": m, "payload": p} for m, p in mine] == theirs


class _Stub(http.server.ThreadingHTTPServer):
    """Streams ``max_tokens`` token frames and a done frame, as the
    Serve proxy does; ``slow`` makes every further frame wait, so that
    whatever is in flight then is still in flight at the close."""

    daemon_threads = True

    def __init__(self):
        super().__init__(("127.0.0.1", 0), _Handler)
        self.got, self.wrote, self.slow = [], {}, threading.Event()


class _Handler(http.server.BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"

    def log_message(self, *_):
        pass

    def _chunk(self, obj):
        data = (json.dumps(obj) + "\n").encode()
        self.wfile.write(b"%x\r\n%s\r\n" % (len(data), data))
        self.wfile.flush()

    def do_POST(self):
        payload = json.loads(self.rfile.read(
            int(self.headers["Content-Length"])))
        self.server.got.append((time.perf_counter(), payload))
        self.send_response(200)
        # Keep-alive, as the proxy answers: under ``Connection: close``
        # http.client lets go of the socket that a cut needs.
        self.send_header("Transfer-Encoding", "chunked")
        self.end_headers()
        try:
            for i in range(payload["max_tokens"]):
                if self.server.slow.is_set():
                    time.sleep(5.0)
                if i == 0:
                    self.server.wrote[tuple(payload["prompt"])] = \
                        time.perf_counter()
                self._chunk({"token": i})
                time.sleep(0.002)
            self._chunk({"done": True, "finish_reason": "length",
                         "num_tokens": payload["max_tokens"],
                         "cached_tokens": 0})
            self.wfile.write(b"0\r\n\r\n")
        except OSError:
            pass        # the generator cut the connection
        self.close_connection = True


@pytest.fixture(scope="module")
def served():
    """One run of real generators against the stub: what they sent,
    what they handed back, and the instants around it."""
    pinned = PARENT[0]
    server = _Stub()
    threading.Thread(target=server.serve_forever, daemon=True).start()
    logged = []
    fleet = callers.Fleet("127.0.0.1", server.server_address[1],
                          pinned["traffic"], pinned["seed"], pinned["vocab"],
                          logged.append)
    try:
        clocks = fleet.ready()
        t_start = time.perf_counter() + 0.05
        fleet.start(t_start, 0.3)
        time.sleep(1.5)
        t_open = t_start + 0.4
        server.slow.set()
        time.sleep(0.1)
        t_close = time.perf_counter()
        closed = fleet.close(t_open, t_close)
        t_after = time.perf_counter()
    finally:
        fleet.kill()
        server.shutdown()
        server.server_close()
    return dict(closed, pinned=pinned, server=server, fleet=fleet,
                clocks=clocks, logged=logged, t_start=t_start,
                t_open=t_open, t_close=t_close, t_after=t_after)


def test_a_generator_sends_the_pinned_requests_and_stamps_every_frame(served):
    records, server = served["records"], served["server"]
    sent = [p for _, p in server.got]
    whole = [r for r in records if not r["failed"]]
    assert len(whole) >= 30
    seen = set()
    for r in whole:
        # every frame stamped, in order, between send and end
        assert len(r["t_tokens"]) == len(r["tokens"]) == r["max_tokens"] \
            == r["done"]["num_tokens"]
        assert r["tokens"] == list(range(r["max_tokens"]))
        stamps = [r["t_send"], *r["t_tokens"], r["t_done"], r["t_end"]]
        assert stamps == sorted(stamps)
        assert served["t_start"] - 1e-3 <= r["t_send"] \
            and r["t_end"] <= served["t_after"]
        seen.add((r["caller"], r["index"]))
    # a closed loop: each caller's indices run on from 0 without a gap
    for c in range(6):
        mine = sorted(i for cc, i in seen if cc == c)
        assert mine and mine == list(range(len(mine)))
    # the parent's requests, by the record's fields and by what arrived
    checked = 0
    for c, theirs in served["pinned"]["callers"].items():
        for r in whole:
            if r["caller"] == int(c) and r["index"] < 12:
                want = theirs[r["index"]]
                assert {k: r[k] for k in META} == want["meta"]
                assert want["payload"] in sent
                checked += 1
    assert checked >= 12
    # callers start staggered: caller c is due c / 6 of 0.3 s in
    for c in range(6):
        first = min(r["t_send"] for r in records if r["caller"] == c)
        assert 0.0 <= first - (served["t_start"] + 0.3 * c / 6) < 0.25


def test_parent_and_child_stamp_one_event_within_a_millisecond(served):
    # the ping: a generator's reading between the driver's two
    assert served["clocks"] and all(ok for ok, _ in served["clocks"])
    # a frame's write in this process and its read in the generator,
    # and a request's send there and its arrival here, in that order
    wrote, arrived = served["server"].wrote, {
        tuple(p["prompt"]): t for t, p in served["server"].got}
    plan = traffic.closed_loop_plan(*(served["pinned"][k] for k in (
        "traffic", "seed", "vocab")))
    lags = []
    for r in served["records"]:
        if r["failed"]:
            continue
        key = tuple(next(itertools.islice(
            callers.requests_of(r["caller"], plan), r["index"],
            None))[1]["prompt"])
        assert arrived[key] >= r["t_send"] - 1e-3
        lags.append(r["t_tokens"][0] - wrote[key])
    assert min(lags) >= -1e-3         # never read before it was written
    assert sorted(lags)[len(lags) // 2] < 0.05


def test_the_close_cuts_what_is_in_flight_and_marks_it_as_before(served):
    cut = [r for r in served["records"] if r["t_end"] > served["t_close"]]
    # every caller had a request in flight behind the slowed server
    assert sorted(r["caller"] for r in cut) == list(range(6))
    for r in cut:
        assert r["failed"] and r["error"] and r["done"] is None
        assert r["t_end"] - served["t_close"] < 2.0     # cut, not run out
        assert len(r["t_tokens"]) == len(r["tokens"]) < r["max_tokens"]
    assert served["in_flight"] == 0
    # nothing else failed: a failed record ended after the close
    assert all(r["t_end"] > served["t_close"]
               for r in served["records"] if r["failed"])


def test_no_generator_loads_jax_or_the_program_and_each_reports(served):
    reports, fleet = served["reports"], served["fleet"]
    assert all(ok for ok, _ in fleet.checks(reports))
    assert [r["loaded"] for r in reports] == [[]] * len(reports)
    # main() asserts ``"jax" not in sys.modules`` as it ends
    assert [p.returncode for p in fleet.procs] == [0] * len(reports)
    assert len(reports) == callers.generators_for(6, os.cpu_count())
    assert sorted(c for r in reports for c in r["callers"]) == list(range(6))
    assert sum(r["requests"] for r in reports) == len(served["records"])
    for r in reports:
        assert 0.0 < r["cpu_window_s"] <= r["cpu_s"]
        assert r["cpu_window_s"] < served["t_close"] - served["t_open"]
    # and the served process's own check finds no caller thread here
    ok, what = callers.server_threads(lambda _: None)
    assert ok, what


def test_generators_by_cores_and_the_cpu_clock_between_samples():
    assert callers.generators_for(64, 13) == 4
    assert callers.generators_for(64, 6) == 4
    assert callers.generators_for(64, 5) == 2
    assert callers.generators_for(64, None) == 2
    assert callers.generators_for(3, 30) == 3
    clock = callers._CpuClock()
    clock.samples = [(10.0, 1.0), (11.0, 1.5), (12.0, 1.5), (13.0, 3.5)]
    assert clock.at(9.0) == 1.0 and clock.at(14.0) == 3.5
    assert clock.at(10.5) == pytest.approx(1.25)
    assert clock.at(12.25) - clock.at(10.5) == pytest.approx(0.75)


def test_both_drivers_take_stream_from_the_callers_file():
    base = harness.load_module("drivers", "serve_closed_loop")
    assert base.stream is callers.stream and base.Fleet is callers.Fleet
    assert not hasattr(base, "_Caller")
    with open(os.path.join(ROOT, "benchmark", "drivers",
                           "serve_closed_loop_ref.py")) as f:
        source = f.read()
    assert "base.Fleet(" in source and "_Caller" not in source \
        and "threading.Thread(target=self._run" not in source


def test_the_reader_takes_the_busiest_generator():
    read = harness.load_module("layer_metrics", NAME).read
    c = {"window_s": 50.0, "callers": [
        {"generator": 0, "cpu_window_s": 5.0},
        {"generator": 1, "cpu_window_s": 12.5},
        {"generator": 2, "cpu_window_s": 7.0}]}
    assert read(c) == pytest.approx(25.0)
    assert read({"window_s": 50.0, "callers": []}) is None
    assert read({"window_s": 50.0}) is None         # the train cell


def test_the_manifest_lists_it_once_for_the_three_serving_cells():
    """Membership, not position: the next PR appends behind it."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    (entry,) = [m for m in manifest["per_layer"] if m["name"] == NAME]
    assert entry == {
        "name": NAME, "unit": "%", "better": "lower",
        "source": "host_clock", "layer": "Entry points",
        "moves": "serve_tokens_per_s", "workloads": SERVING}
    (chat,) = [w for w in manifest["workloads"] if w["name"] == SERVING[0]]
    assert "generator processes" in chat["why"] \
        and "pulls" not in chat["why"] and len(chat["why"]) <= 200
