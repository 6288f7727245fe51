"""Streaming responses: generator deployments drain chunk-at-a-time
through the handle (iter_stream) and as chunked HTTP (ndjson frames).

Parity: /root/reference/python/ray/serve/_private/proxy.py:761 streaming
HTTP responses + handle.py DeploymentResponseGenerator.
"""

import json
import urllib.request

import pytest

import ray_tpu
from ray_tpu import serve


@pytest.fixture
def rt():
    ray_tpu.init(num_cpus=2)
    try:
        yield
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


@serve.deployment
class Streamer:
    def __call__(self, req):
        n = int(req.get("n", 4)) if isinstance(req, dict) else 4

        def gen():
            for i in range(n):
                yield {"i": i, "sq": i * i}

        return gen()

    def plain(self, req):
        return {"ok": True}


def test_handle_iter_stream(rt):
    serve.run(Streamer.bind(), name="default")
    h = serve.get_app_handle("default")
    chunks = list(h.remote({"n": 5}).iter_stream(timeout=60))
    assert chunks == [{"i": i, "sq": i * i} for i in range(5)]
    # Non-streaming results come through iter_stream as a single item.
    one = list(h.options(method_name="plain").remote({}).iter_stream(
        timeout=60))
    assert one == [{"ok": True}]


def test_handle_iter_stream_early_exit_frees_generator(rt):
    serve.run(Streamer.bind(), name="default")
    h = serve.get_app_handle("default")
    it = h.remote({"n": 1000}).iter_stream(timeout=60, chunk_batch=2)
    assert next(it) == {"i": 0, "sq": 0}
    it.close()  # early exit: replica-side generator must be cancelled
    import time

    from ray_tpu.serve.deployment import _router_for

    time.sleep(0.5)
    actor = _router_for("Streamer").replica(0)
    # The stream registry is empty again (cancel landed).
    for _ in range(20):
        stats = ray_tpu.get(actor.stats.remote(), timeout=30)
        break
    # No direct registry accessor: issuing a bogus stream_next proves the
    # slot is gone (returns done immediately).
    chunks, done = ray_tpu.get(actor.stream_next.remote(1), timeout=30)
    assert done and not chunks


def test_http_streaming_chunked(rt):
    serve.run(Streamer.bind(), name="default")
    proxy = serve.start(http_port=0)
    url = f"http://127.0.0.1:{proxy.port}/"
    req = urllib.request.Request(
        url, data=json.dumps({"n": 6}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=60) as r:
        assert r.headers.get("Content-Type") == "application/x-ndjson"
        lines = [json.loads(l) for l in r.read().splitlines() if l.strip()]
    assert lines == [{"i": i, "sq": i * i} for i in range(6)]


def test_http_plain_json_still_works(rt):
    serve.run(Streamer.bind(), name="default")
    proxy = serve.start(http_port=0)
    url = f"http://127.0.0.1:{proxy.port}/"
    req = urllib.request.Request(
        url, data=json.dumps({"n": 2}).encode(), method="POST")
    with urllib.request.urlopen(req, timeout=60) as r:
        body = r.read()
    assert json.loads(body.splitlines()[0]) == {"i": 0, "sq": 0}


# ---------------------------------------------------------------------------
# A chunk leaves the replica when it is yielded (PR 35): feeders, one
# poll a handle, run-ahead bounded by what the reader has consumed.
# ---------------------------------------------------------------------------
import http.client
import threading
import time

PERIOD = 0.05


@serve.deployment
class Ticker:
    """Generators that yield on a clock: alone every PERIOD, or all
    together on a shared tick (as an engine's step emits for every
    lane)."""

    def __init__(self):
        self.tick = 0
        self.cond = threading.Condition()
        self.yielded = 0
        self.closed = 0
        threading.Thread(target=self._clock, daemon=True).start()

    def _clock(self):
        while True:
            time.sleep(PERIOD)
            with self.cond:
                self.tick += 1
                self.cond.notify_all()

    def __call__(self, req):
        n, tag = int(req["n"]), req.get("tag")
        together, fail_at = req.get("together"), req.get("fail_at")

        def gen():
            try:
                for i in range(n):
                    if i == fail_at:
                        raise ValueError(f"boom at {i}")
                    if i == req.get("stall_at"):
                        time.sleep(5.0)
                    if together:
                        with self.cond:
                            seen = self.tick
                            self.cond.wait_for(lambda: self.tick > seen)
                    elif req.get("period", PERIOD):
                        time.sleep(req.get("period", PERIOD))
                    self.yielded += 1
                    if req.get("pad"):
                        yield {"tag": tag, "i": i, "pad": "x" * req["pad"]}
                        continue
                    yield {"tag": tag, "i": i, "t": time.time()}
            finally:
                self.closed += 1

        return gen()

    def state(self, req):
        return {"yielded": self.yielded, "closed": self.closed,
                "feeders": sum(t.name.startswith("serve-feed")
                               for t in threading.enumerate())}


def _ticker():
    serve.run(Ticker.bind(), name="default")
    return serve.get_app_handle("default")


def _state(h):
    return h.options(method_name="state").remote({}).result(timeout=30)


def _replica_hist(name="Ticker"):
    from ray_tpu.serve.deployment import _router_for

    actor = _router_for(name).replica(0)
    hist = ray_tpu.get(actor.stats.remote(), timeout=30)["phase_hist"]
    return {p: hist.get(p, {"count": 0})["count"]
            for p in ("stream_pull", "stream_hold")}


def _wait(cond, what, timeout=10.0):
    deadline = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < deadline, f"timed out waiting for {what}"
        time.sleep(0.05)


def _pollers():
    return [t for t in threading.enumerate()
            if t.name == "serve-stream-poll"]


def test_chunks_arrive_as_yielded(rt):
    """(a) A chunk every PERIOD reaches the consumer every PERIOD: no gap
    of three periods, and the first sixteen are not handed over
    together."""
    h = _ticker()
    arrived = []
    for chunk in h.remote({"n": 40}).iter_stream(timeout=60):
        arrived.append((time.time(), chunk))
    assert [c["i"] for _, c in arrived] == list(range(40))
    times = [t for t, _ in arrived]
    yields = [c["t"] for _, c in arrived]
    # A gap at the consumer, less what the generator itself was late by
    # (a loaded box stalls its sleep too).
    gaps = [(b - a) - max(0.0, (d - c) - PERIOD) for a, b, c, d in
            zip(times, times[1:], yields, yields[1:])]
    assert max(gaps) < 3 * PERIOD, sorted(gaps)[-5:]
    assert times[15] - times[0] > 10 * PERIOD
    assert max(t - y for t, y in zip(times, yields)) < 3 * PERIOD


def test_64_http_streams_none_waits_for_a_slot(rt):
    """(b) 64 streams at once through the proxy, twice the replica's 32
    call slots: every chunk of every stream reaches its client within a
    few periods of its yield, once, in order."""
    _ticker()
    proxy = serve.start(http_port=0)
    n_streams, n_chunks = 64, 20
    got = [None] * n_streams
    start = threading.Barrier(n_streams)

    def client(k):
        conn = http.client.HTTPConnection("127.0.0.1", proxy.port,
                                          timeout=60)
        start.wait(30)
        conn.request("POST", "/", body=json.dumps(
            {"n": n_chunks, "tag": k}),
            headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        frames = []
        while line := resp.readline():
            frames.append((time.time(), json.loads(line)))
        conn.close()
        got[k] = frames

    threads = [threading.Thread(target=client, args=(k,))
               for k in range(n_streams)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(90)
    assert not any(t.is_alive() for t in threads)
    late = []
    for k, frames in enumerate(got):
        assert [(f["tag"], f["i"]) for _, f in frames] == \
            [(k, i) for i in range(n_chunks)]
        late += [t - f["t"] for t, f in frames]
    late.sort()
    # With pulls of sixteen in two shifts of 32 a chunk waited up to
    # sixteen periods and four as a rule; handed on as yielded, a
    # fraction of one, and a few at the very worst on a loaded box.
    assert late[len(late) // 2] < PERIOD
    assert late[len(late) * 99 // 100] < 5 * PERIOD, late[-20:]
    assert late[-1] < 10 * PERIOD, late[-5:]


def _drain_all(h, requests, timeout=60):
    """Read ``requests`` streams at once, one thread each; returns each
    stream's chunks."""
    out = [None] * len(requests)

    def read(k):
        out[k] = list(h.remote(requests[k]).iter_stream(timeout=timeout))

    threads = [threading.Thread(target=read, args=(k,))
               for k in range(len(requests))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout + 30)
    assert not any(t.is_alive() for t in threads)
    return out


def test_one_reply_carries_every_streams_chunks(rt):
    """(c) Streams that yield together share their replies; a stream
    alone gets a reply a chunk."""
    h = _ticker()
    out = _drain_all(h, [{"n": 20, "tag": k, "together": True}
                         for k in range(16)])
    assert all(len(chunks) == 20 for chunks in out)
    shared = _replica_hist()
    assert shared["stream_hold"] == 16 * 20
    assert shared["stream_pull"] * 4 <= shared["stream_hold"], shared
    assert len(list(h.remote({"n": 20}).iter_stream(timeout=60))) == 20
    alone = _replica_hist()
    chunks = alone["stream_hold"] - shared["stream_hold"]
    replies = alone["stream_pull"] - shared["stream_pull"]
    # One reply a chunk and at most one more for the stream's end (on a
    # loaded box a reply may be slow enough to carry two).
    assert chunks == 20 and 15 <= replies <= 22, (shared, alone)


def test_a_reader_that_stops_stops_the_generator(rt):
    """(d) An unbounded generator runs sixteen chunks ahead of what its
    reader has consumed, and no further."""
    h = _ticker()
    it = h.remote({"n": 10 ** 9, "period": 0}).iter_stream(timeout=60)
    assert [next(it)["i"] for _ in range(3)] == [0, 1, 2]
    _wait(lambda: _state(h)["yielded"] >= 19, "the run-ahead to fill")
    time.sleep(0.5)
    assert _state(h)["yielded"] == 3 + 16
    # Reading on lets it run on, still sixteen ahead.
    assert [next(it)["i"] for _ in range(40)] == list(range(3, 43))
    _wait(lambda: _state(h)["yielded"] >= 43 + 16, "the next run-ahead")
    time.sleep(0.3)
    assert _state(h)["yielded"] == 43 + 16
    it.close()
    _wait(lambda: _state(h)["closed"] == 1, "the generator to close")


def test_early_exit_frees_generator_feeder_and_poller(rt):
    """(e) Closing the iterator, or stream_cancel itself, closes the
    generator and ends its feeder; the poller ends with the handle's
    last stream."""
    from ray_tpu.serve.deployment import _router_for
    from ray_tpu.serve.replica import STREAM_MARKER

    h = _ticker()
    first = h.remote({"n": 10 ** 9}).iter_stream(timeout=60)
    second = h.remote({"n": 10 ** 9, "period": 0}).iter_stream(timeout=60)
    assert next(first)["i"] == 0 and next(second)["i"] == 0
    assert _state(h)["feeders"] == 2 and len(_pollers()) == 1
    first.close()  # its feeder is inside next(): ends when that returns
    _wait(lambda: _state(h)["closed"] == 1 and _state(h)["feeders"] == 1,
          "the first stream to close")
    assert len(_pollers()) == 1  # the second stream still needs it
    second.close()  # its feeder waits for its reader: ends at once
    _wait(lambda: _state(h)["closed"] == 2 and not _state(h)["feeders"],
          "the second stream to close")
    _wait(lambda: not _pollers(), "the poller to end")
    # stream_cancel on a stream nobody iterates.
    actor = _router_for("Ticker").replica(0)
    sid = h.remote({"n": 10 ** 9}).result(timeout=30)[STREAM_MARKER]
    _wait(lambda: _state(h)["feeders"] == 1, "the feeder to start")
    ray_tpu.get(actor.stream_cancel.remote(sid), timeout=30)
    _wait(lambda: _state(h)["closed"] == 3 and not _state(h)["feeders"],
          "the cancelled stream to close")
    assert ray_tpu.get(actor.stream_next.remote(sid), timeout=30) == \
        ([], True)


@pytest.mark.parametrize("k", [0, 5])
def test_an_error_arrives_behind_the_chunks_before_it(rt, k):
    """(f) A generator that raises after k chunks delivers those k, then
    the error."""
    h = _ticker()
    it = h.remote({"n": 10, "fail_at": k, "period": 0}).iter_stream(
        timeout=60)
    assert [next(it)["i"] for _ in range(k)] == list(range(k))
    with pytest.raises(ray_tpu.TaskError, match=f"boom at {k}") as ei:
        next(it)
    assert isinstance(ei.value.cause, ValueError)
    _wait(lambda: _state(h)["closed"] == 1 and not _state(h)["feeders"]
          and not _pollers(), "the failed stream to be freed")


@pytest.mark.parametrize("lane", ["worker", "device"])
def test_order_and_exactly_once_over_64_streams(rt, lane):
    """(g) 64 streams that yield as fast as their readers allow, so
    every stream runs into its run-ahead bound again and again: each
    reader gets its own chunks, all of them, once, in order. On the
    device lane feeders, polls, poller and readers are threads of this
    process, switched every 10 us."""
    import sys

    if lane == "device":
        serve.run(Ticker.options(ray_actor_options={
            "scheduling_strategy": "device"}).bind(), name="default")
        h = serve.get_app_handle("default")
    else:
        h = _ticker()
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        out = _drain_all(h, [{"n": 60, "tag": k, "period": 0}
                             for k in range(64)])
    finally:
        sys.setswitchinterval(interval)
    for k, chunks in enumerate(out):
        assert [(c["tag"], c["i"]) for c in chunks] == \
            [(k, i) for i in range(60)]
    hist = _replica_hist()
    assert hist["stream_hold"] == 64 * 60
    _wait(lambda: not _pollers() and not _state(h)["feeders"],
          "pollers and feeders to end")


def test_http_stream_that_stalls_is_aborted_in_band(rt):
    """The per-chunk deadline: a stream that stalls past the request
    timeout gets the in-band error frame and no terminating chunk."""
    h = _ticker()
    assert len(list(h.remote({"n": 1}).iter_stream(timeout=60))) == 1
    proxy = serve.start(http_port=0, request_timeout_s=1.0)
    conn = http.client.HTTPConnection("127.0.0.1", proxy.port, timeout=30)
    conn.request("POST", "/", body=json.dumps({"n": 3, "period": 5.0}),
                 headers={"Content-Type": "application/json"})
    resp = conn.getresponse()
    assert resp.status == 200
    with pytest.raises(http.client.IncompleteRead) as ei:
        resp.read()
    assert json.loads(ei.value.partial.splitlines()[-1]) == \
        {"error": "stream chunk timed out"}
    conn.close()


def test_grpc_drains_a_stream_through_the_same_path(rt):
    """The unary gRPC ingress answers a streaming deployment with the
    list of its chunks, read through iter_stream like any other."""
    import grpc

    h = _ticker()
    proxy = serve.start_grpc()
    ch = grpc.insecure_channel(f"127.0.0.1:{proxy.port}")
    out = ch.unary_unary("/rtpu.serve/PredictJson")(
        json.dumps({"n": 30, "tag": "g", "period": 0}).encode(),
        metadata=(("app", "default"),), timeout=60)
    ch.close()
    assert [(c["tag"], c["i"]) for c in json.loads(out)] == \
        [("g", i) for i in range(30)]
    _wait(lambda: not _pollers() and not _state(h)["feeders"],
          "the poller and the feeder to end")


# ---------------------------------------------------------------------------
# A stream the deployment fills itself (PR 52): user code returns a
# PushedStream in place of a generator and hands chunks to many of them
# in one ``push``; the replica keeps it on the same books with no feeder
# thread, and polls, handle and proxy read it as they read a generator's.
# ---------------------------------------------------------------------------
from ray_tpu.serve.replica import (  # noqa: E402
    REPLY_SENT,
    STREAM_MARKER,
    PushedStream,
    Replica,
)


def _feeders():
    return [t.name for t in threading.enumerate()
            if t.name.startswith("serve-feed-")]


@serve.deployment
class Pusher:
    """A PushedStream a request, filled by ``feed``: what an engine's
    step loop does with a step's tokens for every lane. ``gen`` is the
    other kind of streaming method, on the same replica."""

    def __init__(self):
        self.streams = {}

    def __call__(self, req):
        # Imported here: the class travels to its replica by value, and
        # this module is not importable there.
        from ray_tpu.serve.replica import PushedStream, push

        stream = self.streams[req["tag"]] = PushedStream()
        # Handed over before the replica has the stream on its books.
        push([(stream, [{"tag": req["tag"], "i": i}
                        for i in range(req.get("early", 0))], False, None)])
        return stream

    def gen(self, req):
        return ({"tag": req["tag"], "i": i} for i in range(req["n"]))

    def feed(self, req):
        from ray_tpu.serve.replica import push

        push([(self.streams[tag], [{"tag": tag, "i": i} for i in chunks],
               ended, ValueError(error) if error else None)
              for tag, chunks, ended, error in req["handed"]])
        return {"feeders": sum(t.name.startswith("serve-feed-")
                               for t in threading.enumerate())}


# The class for a Replica built by hand; the name above is the
# deployment (so the class travels to a replica's worker by value).
_Pusher = Pusher.func_or_class


class _Counted(threading.Condition):
    """A stream condition that counts its acquisitions and wake-ups."""

    def __init__(self):
        super().__init__()
        self.entered = self.notified = 0

    def __enter__(self):
        self.entered += 1
        return super().__enter__()

    def notify_all(self):
        self.notified += 1
        super().notify_all()


def _open(rep, tag, early=0):
    return rep.handle_request(
        "__call__", ({"tag": tag, "early": early},), {})[STREAM_MARKER]


def test_a_pushed_stream_has_no_feeder_and_a_generator_still_has_one():
    """Which path a streaming response takes is the type of what user
    code returned: a generator is parked behind a feeder thread of its
    own, a PushedStream goes on the same books with no thread."""
    rep = Replica(_Pusher, (), {}, deployment_name="push_kinds")
    before = set(_feeders())
    pushed = _open(rep, "p")
    assert set(_feeders()) == before
    assert rep._streams[pushed].gen is None
    # Longer than its run-ahead: its feeder waits for a reader.
    parked = rep.handle_request(
        "gen", ({"tag": "g", "n": 40},), {})[STREAM_MARKER]
    assert set(_feeders()) - before == {f"serve-feed-{parked}"}
    assert rep._streams[parked].gen is not None
    assert rep.stream_next(parked, 3) == (
        [{"tag": "g", "i": i} for i in range(3)], False)
    rep.stream_cancel(parked)
    _wait(lambda: set(_feeders()) == before, "the generator's feeder to end")
    rep.instance.feed({"handed": [["p", [0, 1], True, None]]})
    assert rep.stream_next(pushed, 16) == (
        [{"tag": "p", "i": 0}, {"tag": "p", "i": 1}], True)
    assert not rep._streams


def test_one_push_fills_many_streams_under_one_lock_with_one_wake_up():
    """(b) A push into twelve streams takes the stream condition once,
    stamps one time of arrival and wakes the polls once, and the next
    stream_poll reply carries all of it."""
    rep = Replica(_Pusher, (), {}, deployment_name="push_books")
    cond = rep._stream_cond = _Counted()
    sids = [_open(rep, k) for k in range(12)]
    for sid in sids:
        rep.stream_grant(sid, 16, "me")      # the reader's first word
    cond.entered = cond.notified = 0
    rep.instance.feed({"handed": [[k, [0, 1, 2], k % 2 == 0, None]
                                  for k in range(12)]})
    assert (cond.entered, cond.notified) == (1, 1)
    assert len({t for s in rep._streams.values() for _, t in s.ready}) == 1
    reply = rep.stream_poll("me")
    assert reply.pop(REPLY_SENT) <= time.time()
    assert sorted(reply) == sids
    for k, sid in enumerate(sids):
        assert reply[sid] == ([{"tag": k, "i": i} for i in range(3)],
                              k % 2 == 0, None)
    # The ended ones are off the books; the rest are ongoing work.
    assert sorted(rep._streams) == sids[1::2]
    assert rep.stats()["ongoing"] == 6
    hist = rep.stats()["phase_hist"]
    assert hist["stream_hold"]["count"] == 36


def test_what_is_handed_over_before_the_books_and_the_reader_is_kept():
    """Chunks handed over before the replica saw the stream, and before
    its reader said whose polls carry it, wait in it, in order; a grant
    moves nothing of a stream that has no feeder."""
    rep = Replica(_Pusher, (), {}, deployment_name="push_early")
    sid = _open(rep, "a", early=2)
    rep.instance.feed({"handed": [["a", [2, 3], False, None]]})
    stream = rep._streams[sid]
    assert stream.caller is None
    assert [c["i"] for c, _ in stream.ready] == [0, 1, 2, 3]
    assert rep.stream_poll("nobody") == {}   # its limit: nothing is theirs
    limit = stream.limit
    rep.stream_grant(sid, 10 ** 6, "me")
    assert stream.limit == limit
    reply = rep.stream_poll("me")
    del reply[REPLY_SENT]
    assert reply == {
        sid: ([{"tag": "a", "i": i} for i in range(4)], False, None)}


def test_a_pushed_stream_answers_the_one_request_that_made_it():
    with pytest.raises(RuntimeError, match="made by the request"):
        PushedStream()                       # outside any request

    class Again:
        made = None

        def __call__(self, req):
            self.made = self.made or PushedStream()
            return self.made

    rep = Replica(Again, (), {}, deployment_name="push_again")
    sid = rep.handle_request("__call__", ({},), {})[STREAM_MARKER]
    with pytest.raises(TypeError, match="answers the one request"):
        rep.handle_request("__call__", ({},), {})
    assert list(rep._streams) == [sid]
    other = Replica(Again, (), {}, deployment_name="push_other")
    other.instance.made = rep.instance.made
    with pytest.raises(TypeError, match="answers the one request"):
        other.handle_request("__call__", ({},), {})
    assert not other._streams


def test_a_cancelled_or_abandoned_pushed_stream_drops_what_it_is_handed():
    """(d) stream_cancel of a pushed stream leaves no entry in
    ``_streams``, and what is handed to it afterwards, or past its end,
    or after its replica is gone, goes nowhere."""
    rep = Replica(_Pusher, (), {}, deployment_name="push_cancel")
    a, b, c = (_open(rep, tag) for tag in "abc")
    held = {tag: rep.instance.streams[tag] for tag in "abc"}
    rep.stream_cancel(a)
    assert sorted(rep._streams) == [b, c]
    rep.instance.feed({"handed": [["a", [0, 1], True, None],
                                  ["b", [0], True, None]]})
    assert held["a"].ready == [] and not held["a"].ended
    assert rep.stream_next(a, 16) == ([], True)
    rep.instance.feed({"handed": [["b", [1], False, None]]})    # past its end
    assert rep.stream_next(b, 16) == ([{"tag": "b", "i": 0}], True)
    assert sorted(rep._streams) == [c]
    feed = rep.instance.feed
    del rep                                  # the finalizer abandons c
    feed({"handed": [["c", [0], False, None]]})
    assert held["c"].cancelled and held["c"].ready == []


@pytest.mark.parametrize("k", [0, 5])
def test_an_error_handed_to_a_pushed_stream_arrives_behind_its_chunks(
        rt, k):
    """(d) Through the handle, as a generator's: the k chunks handed
    over before the error, then the error, and the stream is freed."""
    serve.run(Pusher.bind(), name="default")
    h = serve.get_app_handle("default")
    response = h.remote({"tag": "e", "early": k})
    assert STREAM_MARKER in response.result(timeout=30)    # it is there
    it = response.iter_stream(timeout=60)
    fed = h.options(method_name="feed").remote(
        {"handed": [["e", [], True, f"boom at {k}"]]}).result(timeout=30)
    assert fed["feeders"] == 0
    assert [next(it)["i"] for _ in range(k)] == list(range(k))
    with pytest.raises(ray_tpu.TaskError, match=f"boom at {k}") as ei:
        next(it)
    assert isinstance(ei.value.cause, ValueError)
    _wait(lambda: not _pollers(), "the failed stream to be freed")


# ---------------------------------------------------------------------------
# A loop's consumer is a sink (PR 59): a poll reply is ONE callback on
# the proxy's loop, which writes every stream's share to its socket; no
# task switch, future or timer a frame. What ``await write`` and
# ``wait_for`` were there for holds without them.
# ---------------------------------------------------------------------------
import asyncio  # noqa: E402
import socket  # noqa: E402

from ray_tpu.serve import slo  # noqa: E402
from ray_tpu.serve.deployment import Router, _StreamEnd  # noqa: E402


def _flushes(name):
    return slo.phase_hist(name).get("proxy_flush", {"count": 0})["count"]


def _post(port, body, rcvbuf=None):
    """An HTTP connection with the request sent and the response's head
    read; the body is the caller's to read, or not."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    if rcvbuf:
        sock = socket.socket()
        sock.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, rcvbuf)
        sock.settimeout(60)
        sock.connect(("127.0.0.1", port))
        conn.sock = sock
    conn.request("POST", "/", body=json.dumps(body),
                 headers={"Content-Type": "application/json"})
    return conn, conn.getresponse()


def test_a_reply_with_64_streams_frames_is_one_wake_of_the_loop(rt):
    """Every push fills 64 streams, one stream_poll reply carries them,
    and the proxy's loop wakes ONCE for the reply: ``proxy_flush``
    counts a wake a round where ``stream_hold`` counts 64 chunks. What
    was handed over before the writer attached arrives first."""
    serve.run(Pusher.bind(), name="default")
    h = serve.get_app_handle("default")
    proxy = serve.start(http_port=0)
    n, rounds = 64, 12
    opened = [_post(proxy.port, {"tag": k, "early": 2}) for k in range(n)]
    # Every reader has its early frames: its stream is on the books,
    # its writer attached and its caller known to the replica.
    early = [[json.loads(resp.readline()) for _ in range(2)]
             for _, resp in opened]
    assert early == [[{"tag": k, "i": i} for i in range(2)]
                     for k in range(n)]
    before, held = _flushes("Pusher"), _replica_hist("Pusher")
    feed = h.options(method_name="feed")
    for r in range(rounds):
        feed.remote({"handed": [[k, [2 + r], r == rounds - 1, None]
                                for k in range(n)]}).result(timeout=30)
    for k, (conn, resp) in enumerate(opened):
        rest = [json.loads(line) for line in resp.read().splitlines()]
        assert rest == [{"tag": k, "i": 2 + r} for r in range(rounds)]
        conn.close()
    frames = _replica_hist("Pusher")["stream_hold"] - held["stream_hold"]
    wakes = _flushes("Pusher") - before
    assert frames == n * rounds
    # A round is one reply and one wake; two rounds may share one.
    assert 1 <= wakes <= rounds + 1, (wakes, rounds)
    _wait(lambda: not _pollers(), "the poller to end")


def test_a_reader_that_stops_holds_its_frames_and_the_generator(rt):
    """Backpressure without ``await write``: a client that stops reading
    fills the transport's buffer, the sink stops taking, the frames wait
    in the stream's end with ``_consumed`` standing still, and the
    generator stops sixteen chunks past it; reading on drains the
    buffer and a drain waiter resumes the stream, every chunk in order."""
    from ray_tpu.serve.deployment import _router_for

    h = _ticker()
    proxy = serve.start(http_port=0)
    total, pad = 1500, 16384
    conn, resp = _post(proxy.port, {"n": total, "tag": "slow", "period": 0,
                                    "pad": pad}, rcvbuf=8192)
    assert json.loads(resp.readline())["i"] == 0
    (end,) = [e for ends in _router_for("Ticker")._stream_ends.values()
              for e in ends.values()]
    _wait(lambda: not end._taking, "the sink to stop taking", timeout=60)
    seen = [-1, time.monotonic()]

    def settled():
        y = _state(h)["yielded"]
        if y != seen[0]:
            seen[:] = [y, time.monotonic()]
        return time.monotonic() - seen[1] > 1.0

    _wait(settled, "the generator to stop", timeout=60)
    stopped = seen[0]
    assert stopped < total
    assert not end._taking and end._consumed < stopped
    # The generator at its run-ahead bound, and what it yielded past
    # what the sink took held here (or on its way here).
    assert stopped == end._consumed + 16
    _wait(lambda: len(end._chunks) == 16, "the held frames")
    rest = [json.loads(line)["i"] for line in resp.read().splitlines()]
    assert rest == list(range(1, total))
    conn.close()
    _wait(lambda: _state(h)["closed"] == 1 and not _pollers(),
          "the stream to be freed")


def test_frames_then_a_stall_one_timer_a_stream(rt):
    """The per-chunk deadline is one timer a stream: frames that keep
    coming re-arm nothing, the timer re-arms itself from the time of the
    last frame when it fires, and a stall past the request timeout ends
    the stream with the frames it had, the in-band error frame and an
    aborted connection."""
    h = _ticker()
    assert len(list(h.remote({"n": 1}).iter_stream(timeout=60))) == 1
    proxy = serve.start(http_port=0, request_timeout_s=1.0)
    armed = []
    call_later = proxy._loop.call_later

    def counting(delay, callback, *args):
        if getattr(callback, "__name__", "") == "_deadline":
            armed.append(delay)
        return call_later(delay, callback, *args)

    proxy._loop.call_later = counting
    frames = 30
    conn, resp = _post(proxy.port, {"n": frames + 1, "period": PERIOD,
                                    "fail_at": None, "stall_at": frames})
    assert resp.status == 200
    with pytest.raises(http.client.IncompleteRead) as ei:
        resp.read()
    lines = [json.loads(line) for line in ei.value.partial.splitlines()]
    assert [f["i"] for f in lines[:-1]] == list(range(frames))
    assert lines[-1] == {"error": "stream chunk timed out"}
    conn.close()
    # Armed at the attach and once a firing: 30 frames over 1.5 s (more
    # on a loaded box) and a stall of 1 s are three or four firings,
    # not thirty timers.
    assert 2 <= len(armed) <= 12, armed
    assert all(0 < d <= 1.0 for d in armed)
    _wait(lambda: _state(h)["closed"] >= 2 and not _pollers(),
          "the stalled stream to be freed", timeout=20)


def test_a_client_that_disconnects_cancels_the_replicas_stream(rt):
    h = _ticker()
    proxy = serve.start(http_port=0)
    conn, resp = _post(proxy.port, {"n": 10 ** 9, "tag": "gone"})
    assert [json.loads(resp.readline())["i"] for _ in range(3)] == [0, 1, 2]
    assert _state(h)["feeders"] == 1 and len(_pollers()) == 1
    conn.sock.shutdown(socket.SHUT_RDWR)
    conn.close()
    _wait(lambda: _state(h)["closed"] == 1 and not _state(h)["feeders"],
          "the generator to close")
    _wait(lambda: not _pollers(), "the poller to end")


def test_order_and_exactly_once_over_64_sinks(rt):
    """64 generator streams through the proxy, yielding as fast as
    their sinks' grants allow, with every thread of this process (the
    device lane's feeders and polls, the poller, the loop) switched
    every 10 us: each client gets its own chunks, all of them, once, in
    order."""
    import sys

    serve.run(Ticker.options(ray_actor_options={
        "scheduling_strategy": "device"}).bind(), name="default")
    h = serve.get_app_handle("default")
    proxy = serve.start(http_port=0)
    got = [None] * 64
    # This process's own histogram: the replica is a thread of it.
    held = _replica_hist()["stream_hold"]

    def client(k):
        conn, resp = _post(proxy.port, {"n": 60, "tag": k, "period": 0})
        got[k] = [json.loads(line) for line in resp.read().splitlines()]
        conn.close()

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=client, args=(k,))
                   for k in range(64)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    for k, frames in enumerate(got):
        assert [(f["tag"], f["i"]) for f in frames] == \
            [(k, i) for i in range(60)]
    assert _replica_hist()["stream_hold"] - held == 64 * 60
    _wait(lambda: not _pollers() and not _state(h)["feeders"],
          "pollers and feeders to end")


class _Remote:
    def __init__(self, log, name):
        self.log, self.name = log, name

    def remote(self, *args):
        self.log.append((self.name, *args))


class _Actor:
    """Records the actor calls a stream's end makes."""

    def __init__(self):
        self.calls = []
        self.stream_grant = _Remote(self.calls, "grant")
        self.stream_cancel = _Remote(self.calls, "cancel")


def test_a_sink_gets_what_was_dealt_before_it_first_and_can_pause():
    """The end alone, no cluster: shares dealt before the sink attached
    wait for it and come first; a reply for many sinks is one callback
    of their loop; a sink that returns False is given nothing more and
    counts nothing as consumed until it resumes."""
    router, actor, loop = Router("sinks"), _Actor(), asyncio.new_event_loop()
    ends = [_StreamEnd(router, "k", actor, sid, 4) for sid in (1, 2, 3)]
    got = {e.sid: [] for e in ends}
    taking = {e.sid: True for e in ends}

    def sink(sid):
        def take(chunks, ended, error):
            got[sid].append((list(chunks), ended, error))
            return taking[sid]
        return take

    a, b, c = ends
    assert a.deal(["a0", "a1"], False, None) is None   # nobody's loop yet
    scheduled = []
    loop.call_soon_threadsafe = lambda fn, *args: scheduled.append((fn, args))
    before = _flushes("sinks")
    for e in ends:
        e.attach(loop, sink(e.sid))
    assert got == {1: [(["a0", "a1"], False, None)], 2: [], 3: []}
    assert a._consumed == 2 and not scheduled
    boom = ValueError("boom")
    router._deal([(a, (["a2"], False, None)), (b, (["b0", "b1"], True, None)),
                  (c, (["c0"], True, boom))])
    (call,) = scheduled                     # ONE callback for three streams
    call[0](*call[1])
    assert _flushes("sinks") == before + 1
    assert got[1][1:] == [(["a2"], False, None)]
    assert got[2] == [(["b0", "b1"], True, None)]
    assert got[3] == [(["c0"], True, boom)]  # the error behind its chunk
    # Paused: the share waits in the end, uncounted, and asks no grant.
    taking[1] = False
    a.deal(["a3"], False, None)
    a.pump()
    assert a._consumed == 4 and not a._taking
    a.deal(["a4", "a5"], False, None)
    a.pump()
    assert len(got[1]) == 3 and list(a._chunks) == ["a4", "a5"]
    assert actor.calls[-1] == ("grant", 1, 8) and a.grant() is None
    taking[1] = True
    a.resume()
    assert got[1][3] == (["a4", "a5"], False, None) and a._consumed == 6
    # Half a run-ahead behind the replica's word: the consumer says so.
    grants = [call for call in actor.calls if call[0] == "grant"]
    assert grants[-1] == ("grant", 1, 10)
    a.close()
    assert ("cancel", 1) in actor.calls
    a.deal(["late"], False, None)
    a.pump()                                 # closed: goes nowhere
    assert len(got[1]) == 4
    loop.close()
