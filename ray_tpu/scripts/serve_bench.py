"""Serve latency/throughput benchmark — recorded numbers for the ingress.

Parity target: the reference treats serve performance as a release suite
(/root/reference/release/release_tests.yaml serve microbenchmarks:
p50/p99 latency + RPS). ``python -m ray_tpu.scripts.serve_bench``
measures three paths (VERDICT r4 item 4):

  * ``handle``    — in-process DeploymentHandle calls (no HTTP);
  * ``http_local``— the local aiohttp ingress with KEEP-ALIVE clients
    (per-request TCP setup belongs to the client, not the ingress; the
    reference's serve microbenchmarks use persistent connections too);
  * ``fleet``     — the per-node ProxyActor fleet on a REAL second
    node: per-proxy latency through a non-driver node's proxy, plus
    aggregate RPS with clients spread across >=2 proxies.

Ingress overhead = http p50 - handle p50.
"""

from __future__ import annotations

import http.client
import json
import os
import statistics
import threading
import time


def _percentiles(xs):
    xs = sorted(xs)

    def pct(p):
        if not xs:
            return 0.0
        i = min(len(xs) - 1, int(round(p / 100 * (len(xs) - 1))))
        return xs[i]

    return {"p50_ms": round(pct(50) * 1000, 2),
            "p90_ms": round(pct(90) * 1000, 2),
            "p95_ms": round(pct(95) * 1000, 2),
            "p99_ms": round(pct(99) * 1000, 2),
            "mean_ms": round(statistics.fmean(xs) * 1000, 2)}


def _http_closed_loop(host: str, port: int, duration_s: float,
                      clients: int, path: str = "/") -> tuple:
    """Closed-loop keep-alive clients; returns (latencies, elapsed)."""
    lat: list = []
    lock = threading.Lock()
    stop_at = time.perf_counter() + duration_s
    body = json.dumps({"scale": 2.0})
    headers = {"Content-Type": "application/json"}

    def client():
        conn = http.client.HTTPConnection(host, port, timeout=30)
        mine = []
        try:
            while time.perf_counter() < stop_at:
                t0 = time.perf_counter()
                conn.request("POST", path, body=body, headers=headers)
                resp = conn.getresponse()
                resp.read()
                if resp.status != 200:
                    raise RuntimeError(f"HTTP {resp.status}")
                mine.append(time.perf_counter() - t0)
        finally:
            conn.close()
        with lock:
            lat.extend(mine)

    threads = [threading.Thread(target=client) for _ in range(clients)]
    t_start = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return lat, time.perf_counter() - t_start


def _deploy(serve):
    @serve.deployment
    class Model:
        def __init__(self):
            import jax
            import jax.numpy as jnp

            w = jax.random.normal(jax.random.key(0), (64, 64))
            self._fwd = jax.jit(lambda x: (x @ w).sum())
            float(self._fwd(jnp.ones((8, 64))))  # compile

        def __call__(self, req):
            import jax.numpy as jnp

            x = jnp.ones((8, 64)) * float(
                req.get("scale", 1.0) if isinstance(req, dict) else 1.0)
            return {"y": float(self._fwd(x))}

    serve.run(Model.bind(), name="default")
    return serve.get_app_handle("default")


def run(duration_s: float = 3.0, clients: int = 4) -> dict:
    from ray_tpu import serve

    handle = _deploy(serve)
    proxy = serve.start(http_port=0)

    # Warm: replica startup + jit compile must not pollute latency.
    for _ in range(5):
        handle.remote({"scale": 1.0}).result(timeout=120)

    # -- handle path (no HTTP) --------------------------------------------
    lat_handle: list = []
    deadline = time.perf_counter() + duration_s
    while time.perf_counter() < deadline:
        t0 = time.perf_counter()
        handle.remote({"scale": 2.0}).result(timeout=30)
        lat_handle.append(time.perf_counter() - t0)

    # -- HTTP path, keep-alive. Latency and throughput are measured
    # SEPARATELY: a closed loop with N clients on a 1-core box measures
    # queueing (p50 -> N/throughput), not the ingress. 1 client = true
    # request latency; N clients = sustained RPS.
    _http_closed_loop("127.0.0.1", proxy.port, 0.3, clients)  # warm
    lat_http1, _ = _http_closed_loop(
        "127.0.0.1", proxy.port, duration_s, 1)
    lat_http, elapsed = _http_closed_loop(
        "127.0.0.1", proxy.port, duration_s, clients)

    serve.shutdown()
    return {
        "handle": {**_percentiles(lat_handle),
                   "rps": round(len(lat_handle) / duration_s, 1)},
        "http_local": {**_percentiles(lat_http1),
                       "rps": round(len(lat_http) / elapsed, 1),
                       "saturated_p50_ms": _percentiles(lat_http)["p50_ms"],
                       "note": "latency percentiles at 1 client; rps + "
                               "saturated_p50 with N closed-loop clients"},
    }


def run_fleet(duration_s: float = 3.0, clients: int = 4) -> dict:
    """The per-node ProxyActor fleet on a 2-node cluster: latency via
    the NON-DRIVER node's proxy and aggregate RPS across both."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.cluster_utils import Cluster

    cluster = Cluster(init_args={"num_cpus": 2})
    try:
        cluster.add_node(num_cpus=1)
        cluster.wait_for_nodes(2)
        handle = _deploy(serve)
        serve.start(proxy_location="every_node", http_port=0)
        for _ in range(5):
            handle.remote({"scale": 1.0}).result(timeout=120)
        deadline = time.time() + 30
        proxies = serve.status_proxies()
        while len(proxies) < 2 and time.time() < deadline:
            time.sleep(0.25)
            proxies = serve.status_proxies()
        assert len(proxies) >= 2, f"fleet never reached 2 proxies: {proxies}"
        head_node = ray_tpu.get_runtime_context().node_id.hex()
        out = {"proxies": len(proxies)}
        per = {}
        for p in proxies:
            where = ("driver_node" if p["node_id"] == head_node
                     else "worker_node")
            _http_closed_loop("127.0.0.1", p["port"], 0.3, 2)  # warm
            lat1, _ = _http_closed_loop(
                "127.0.0.1", p["port"], duration_s, 1)
            lat, elapsed = _http_closed_loop(
                "127.0.0.1", p["port"], duration_s, clients)
            per[where] = {**_percentiles(lat1),
                          "rps": round(len(lat) / elapsed, 1),
                          "saturated_p50_ms": _percentiles(lat)["p50_ms"]}
        out.update(per)
        # Aggregate: clients split across BOTH proxies simultaneously.
        agg: dict = {}
        lock = threading.Lock()

        def drive(port):
            lat, elapsed = _http_closed_loop(
                "127.0.0.1", port, duration_s, max(1, clients // 2))
            with lock:
                agg[port] = (len(lat), elapsed)

        ts = [threading.Thread(target=drive, args=(p["port"],))
              for p in proxies[:2]]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        total = sum(n for n, _ in agg.values())
        longest = max(e for _, e in agg.values())
        out["combined_2proxy_rps"] = round(total / longest, 1)
        serve.shutdown()
        return out
    finally:
        cluster.shutdown()


def _llm_stream(conn, prompt, max_tokens, seed, temperature=0.8):
    """One streaming generation over a keep-alive connection.
    Returns (ttft_s, [inter-token gap_s...], n_tokens)."""
    body = json.dumps({"prompt": list(prompt), "max_tokens": max_tokens,
                       "seed": seed, "temperature": temperature})
    t0 = time.perf_counter()
    conn.request("POST", "/", body=body,
                 headers={"Content-Type": "application/json"})
    resp = conn.getresponse()
    ttft = None
    stamps = []
    while True:
        line = resp.readline()
        if not line:
            break
        if not line.strip():
            continue
        frame = json.loads(line)
        if "token" in frame:
            now = time.perf_counter()
            if ttft is None:
                ttft = now - t0
            stamps.append(now)
    gaps = [b - a for a, b in zip(stamps, stamps[1:])]
    return ttft, gaps, len(stamps)


def run_serve_llm(duration_s: float = 6.0, clients: int = 6,
                  max_tokens: int = 24) -> dict:
    """Generation-path bench (``bench.py --serve-llm``): closed-loop
    streaming clients against the continuous-batching LLM deployment
    (serve/llm.py). Reported numbers are the LLM serving SLO pair —
    TTFT and TPOT p50/p95 per request, measured at the CLIENT off the
    ndjson frame arrivals — plus aggregate tokens/s and the engine's
    own view (KV utilization, batch size) at the end of the run."""
    from ray_tpu import serve
    from ray_tpu.models.gpt import TINY
    from ray_tpu.serve.llm import build_app

    serve.run(build_app(TINY, num_blocks=64, block_size=16,
                        max_batch=clients + 2), name="llm")
    proxy = serve.start(http_port=0)
    h = serve.get_app_handle("llm")

    def one_stream(conn, seed):
        """Returns (ttft_s, [gap_s...], n_tokens)."""
        body = json.dumps({"prompt": [seed % 200 + 1] * (4 + seed % 9),
                           "max_tokens": max_tokens, "seed": seed,
                           "temperature": 0.8})
        t0 = time.perf_counter()
        conn.request("POST", "/", body=body,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        ttft = None
        stamps = []
        while True:
            line = resp.readline()
            if not line:
                break
            if not line.strip():
                continue
            frame = json.loads(line)
            if "token" in frame:
                now = time.perf_counter()
                if ttft is None:
                    ttft = now - t0
                stamps.append(now)
        gaps = [b - a for a, b in zip(stamps, stamps[1:])]
        return ttft, gaps, len(stamps)

    # Warm: first request pays prefill+decode compiles.
    warm = http.client.HTTPConnection("127.0.0.1", proxy.port,
                                      timeout=300)
    one_stream(warm, 0)
    warm.close()

    ttfts: list = []
    gaps_all: list = []
    tokens = [0]
    lock = threading.Lock()
    stop_at = time.perf_counter() + duration_s

    def client(cid):
        conn = http.client.HTTPConnection("127.0.0.1", proxy.port,
                                          timeout=300)
        seed = cid
        try:
            while time.perf_counter() < stop_at:
                ttft, gaps, n = one_stream(conn, seed)
                seed += clients
                with lock:
                    if ttft is not None:
                        ttfts.append(ttft)
                    gaps_all.extend(gaps)
                    tokens[0] += n
        finally:
            conn.close()

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(clients)]
    t_start = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    elapsed = time.perf_counter() - t_start
    eng = h.options(method_name="engine_stats").remote().result(
        timeout=60)
    serve.shutdown()
    return {
        "clients": clients,
        "max_tokens": max_tokens,
        "requests": len(ttfts),
        "tokens_per_s": round(tokens[0] / elapsed, 1),
        "ttft": _percentiles(ttfts),
        "tpot": _percentiles(gaps_all),
        # kv_utilization is the END-OF-RUN sample — ~0 once the last
        # request drains. kv_util_peak is the in-step high water, the
        # number that actually says how full the pool ran.
        "engine": {"kv_utilization": round(eng["kv_utilization"], 3),
                   "kv_util_peak": round(eng.get("kv_util_peak", 0.0), 3),
                   "kv_cache_hit_rate": round(
                       eng.get("kv_cache_hit_rate", 0.0), 3),
                   "prefill_chunks": eng.get("prefill_chunks", 0),
                   "steps": eng["steps"],
                   "finished": eng["finished"]},
        "note": "TTFT/TPOT measured at the client off ndjson frame "
                "arrivals; CPU interpret-mode kernel (TINY config)",
    }


def run_serve_llm_prefix(rounds: int = 2, clients: int = 4,
                         max_tokens: int = 12,
                         prefix_tokens: int = 256) -> dict:
    """Shared-system-prompt workload (the prefix-cache acceptance
    shape): every request carries a common ``prefix_tokens`` system
    prompt via the deployment-wide hint, with per-request tails of
    8/16/32/64 tokens. A/B runs prefix_cache off then on in the same
    process — with the cache on, every request after the first skips
    the prefix prefill entirely, so TTFT should be roughly FLAT in
    total prompt length (p50 per tail within ~2x of the shortest)."""
    from ray_tpu import serve
    from ray_tpu.models.gpt import GPTConfig
    from ray_tpu.serve.llm import build_app

    cfg = GPTConfig(vocab_size=512, max_seq=384, d_model=128,
                    n_layer=2, n_head=4)
    tails = (8, 16, 32, 64)
    system = [(7 * i) % 200 + 1 for i in range(prefix_tokens)]

    def one_pass(prefix_cache: bool, nrounds: int = rounds) -> dict:
        serve.run(build_app(cfg, num_blocks=96, block_size=16,
                            max_batch=clients + 2,
                            prefix_cache=prefix_cache,
                            system_prompt=system), name="llm")
        proxy = serve.start(http_port=0)
        h = serve.get_app_handle("llm")
        # Warm every tail-length shape (jit compiles) — with the cache
        # on this also computes+registers the shared prefix once.
        warm = http.client.HTTPConnection("127.0.0.1", proxy.port,
                                          timeout=600)
        for n in tails:
            _llm_stream(warm, [(3 * i) % 200 + 1 for i in range(n)],
                        4, seed=0)
        warm.close()

        by_tail = {n: [] for n in tails}
        tokens = [0]
        lock = threading.Lock()

        def client(cid):
            conn = http.client.HTTPConnection("127.0.0.1", proxy.port,
                                              timeout=600)
            try:
                for r in range(nrounds):
                    # Rotate the tail order per client+round: without
                    # this every client issues the same bucket at the
                    # same moment and the buckets measure lockstep
                    # queueing phases, not prompt-length scaling.
                    k = (cid + r) % len(tails)
                    for n in tails[k:] + tails[:k]:
                        tail = [(cid * 31 + r * 7 + i) % 200 + 1
                                for i in range(n)]
                        ttft, _, nt = _llm_stream(
                            conn, tail, max_tokens,
                            seed=cid * 1000 + r)
                        with lock:
                            if ttft is not None:
                                by_tail[n].append(ttft)
                            tokens[0] += nt
            finally:
                conn.close()

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(clients)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        elapsed = time.perf_counter() - t0
        eng = h.options(method_name="engine_stats").remote().result(
            timeout=60)
        serve.shutdown()
        all_ttft = [x for xs in by_tail.values() for x in xs]
        return {
            "requests": len(all_ttft),
            "tokens_per_s": round(tokens[0] / elapsed, 1),
            "ttft": _percentiles(all_ttft),
            "ttft_by_prompt_tokens": {
                str(prefix_tokens + n): _percentiles(xs)
                for n, xs in by_tail.items()},
            "kv_cache_hit_rate": round(
                eng.get("kv_cache_hit_rate", 0.0), 3),
            "kv_util_peak": round(eng.get("kv_util_peak", 0.0), 3),
            "prefill_chunks": eng.get("prefill_chunks", 0),
        }

    out = {
        "clients": clients,
        "prefix_tokens": prefix_tokens,
        "tails": list(tails),
        "max_tokens": max_tokens,
        # The flatness check reads the ON buckets' medians — give them
        # 2x the samples (the off arm is ~25x slower per request; its
        # magnitude doesn't need tight buckets).
        "prefix_cache_off": one_pass(False),
        "prefix_cache_on": one_pass(True, nrounds=rounds * 2),
        "note": "common system prompt via the deployment hint; A/B in "
                "one process (same box, same compile cache)",
    }
    # Flatness acceptance: every bucket's p50 within 2x of the
    # one-block-uncached-span bucket (the shortest tail) — with the
    # prefix cached, TTFT must not scale with TOTAL prompt length.
    on = out["prefix_cache_on"]["ttft_by_prompt_tokens"]
    ref = max(on[str(prefix_tokens + tails[0])]["p50_ms"], 1e-3)
    out["cache_hit_ttft_flat"] = bool(
        max(v["p50_ms"] for v in on.values()) <= 2.0 * ref)
    return out


def run_serve_llm_spec(requests_per_client: int = 3, clients: int = 3,
                       max_tokens: int = 48) -> dict:
    """Speculative-decoding A/B (``bench.py --serve-llm``): the same
    deployment serving a DECODE-BOUND repetitive-text workload with
    speculation off, then the n-gram proposer, then the small-draft
    proposer. Prompts are short and loopy and generation is long and
    greedy, so decode steps dominate wall time and the n-gram suffix
    match keeps its accept rate high — the shape speculation exists
    for. Outputs are bit-identical across all three arms (llm/spec.py
    keyed-draw verification), so tokens/s is the only thing that moves;
    TTFT/TPOT ride along to show latency does not regress."""
    from ray_tpu import serve
    from ray_tpu.models.gpt import TINY
    from ray_tpu.serve.llm import build_app

    def one_pass(speculative) -> dict:
        serve.run(build_app(TINY, num_blocks=64, block_size=16,
                            max_batch=clients + 2,
                            speculative=speculative), name="llm")
        proxy = serve.start(http_port=0)
        h = serve.get_app_handle("llm")
        # Warm prefill+decode(/verify) compiles out of the timed window.
        warm = http.client.HTTPConnection("127.0.0.1", proxy.port,
                                          timeout=600)
        _llm_stream(warm, [3, 4] + [3] * 10, 8, seed=0, temperature=0.0)
        warm.close()

        ttfts: list = []
        tpots: list = []
        tokens = [0]
        lock = threading.Lock()

        def client(cid):
            conn = http.client.HTTPConnection("127.0.0.1", proxy.port,
                                              timeout=600)
            try:
                for r in range(requests_per_client):
                    # Short loopy prompt, long greedy generation: greedy
                    # decode settles into a cycle the n-gram proposer
                    # replays from the sequence's own history.
                    p = (cid + r) % 7 + 3
                    prompt = [p, p + 1] + [p] * 10
                    ttft, gaps, n = _llm_stream(
                        conn, prompt, max_tokens, seed=cid,
                        temperature=0.0)
                    with lock:
                        if ttft is not None:
                            ttfts.append(ttft)
                        if gaps:
                            tpots.append(sum(gaps) / len(gaps))
                        tokens[0] += n
            finally:
                conn.close()

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(clients)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        elapsed = time.perf_counter() - t0
        eng = h.options(method_name="engine_stats").remote().result(
            timeout=60)
        serve.shutdown()
        row = {"requests": len(ttfts),
               "tokens_per_s": round(tokens[0] / elapsed, 1),
               "ttft": _percentiles(ttfts),
               "tpot": _percentiles(tpots),
               "engine_steps": eng["steps"]}
        if "spec_accept_rate" in eng:
            row["accept_rate"] = round(eng["spec_accept_rate"], 3)
            row["spec_tokens_per_step"] = round(
                eng["spec_tokens_per_step"], 2)
        return row

    out = {
        "clients": clients,
        "requests_per_client": requests_per_client,
        "max_tokens": max_tokens,
        "spec_off": one_pass(None),
        "ngram": one_pass({"mode": "ngram", "k": 4}),
        "draft": one_pass({"mode": "draft", "k": 4}),
    }
    base = max(out["spec_off"]["tokens_per_s"], 1e-9)
    out["ngram_speedup"] = round(out["ngram"]["tokens_per_s"] / base, 2)
    out["draft_speedup"] = round(out["draft"]["tokens_per_s"] / base, 2)
    out["note"] = ("A/B/C in one process; greedy decode, outputs "
                   "bit-identical across arms. draft = self-draft "
                   "(no-KV re-forward per proposed token) — on the "
                   "CPU interpret path its proposal cost usually eats "
                   "the step savings; it is the exactness/plumbing "
                   "demo, n-gram is the throughput arm.")
    return out


def _mux_llm_clients(port: int, duration_s: float, plans: list) -> dict:
    """Closed-loop streaming clients multiplexed on ONE thread with
    ``selectors`` — thread-per-client measurement on a 2-core box
    starves readers for several engine steps and then drains a burst,
    so per-token gap percentiles measure the GIL, not the server.
    One reader timestamps each frame at real socket arrival.

    ``plans``: per-client ``(next_prompt, max_tokens)`` where
    ``next_prompt()`` yields ``(prompt, seed)`` for the next request.
    Returns {"ttfts": [...], "gaps": [...], "tokens": n, "elapsed": s}.
    """
    import selectors
    import socket

    sel = selectors.DefaultSelector()
    ttfts: list = []
    tpots: list = []       # per-request mean inter-token time
    tokens = [0]
    stop_at = time.perf_counter() + duration_s

    class Stream:
        def __init__(self, next_prompt, max_tokens):
            self.next_prompt = next_prompt
            self.max_tokens = max_tokens
            self.sock = socket.create_connection(("127.0.0.1", port),
                                                 timeout=600)
            self.sock.setblocking(False)
            sel.register(self.sock, selectors.EVENT_READ, self)
            self.buf = b""
            self.in_body = False
            self.t0 = 0.0
            self.ttft = None
            self.last = None
            self.n = 0
            self.send()

        def send(self):
            prompt, seed = self.next_prompt()
            body = json.dumps({"prompt": prompt,
                               "max_tokens": self.max_tokens,
                               "seed": seed,
                               "temperature": 0.8}).encode()
            req = (b"POST / HTTP/1.1\r\nHost: x\r\n"
                   b"Content-Type: application/json\r\n"
                   b"Content-Length: %d\r\n\r\n%s" % (len(body), body))
            self.buf = b""
            self.in_body = False
            self.ttft = None
            self.last = None
            self.n = 0
            self.t0 = time.perf_counter()
            self.sock.sendall(req)

        def feed(self, data: bytes, now: float) -> bool:
            """Returns True when the response finished."""
            self.buf += data
            if not self.in_body:
                i = self.buf.find(b"\r\n\r\n")
                if i < 0:
                    return False
                self.buf = self.buf[i + 4:]
                self.in_body = True
            # ndjson frames ride chunked transfer encoding; frames are
            # the lines that parse as JSON objects (chunk-size markers
            # and blank lines don't). The 0-length chunk ends the
            # response.
            done = b"\r\n0\r\n\r\n" in self.buf or \
                self.buf.startswith(b"0\r\n\r\n")
            *lines, self.buf = self.buf.split(b"\n")
            for ln in lines:
                ln = ln.strip()
                if not ln.startswith(b"{"):
                    continue
                try:
                    frame = json.loads(ln)
                except ValueError:
                    continue
                if "token" in frame:
                    if self.ttft is None:
                        self.ttft = now - self.t0
                        self.first_t = now
                    self.last = now
                    self.n += 1
                    tokens[0] += 1
            if done:
                if self.ttft is not None:
                    ttfts.append(self.ttft)
                    if self.n > 1:
                        # The standard streaming TPOT: per-request mean
                        # inter-token time, percentiles ACROSS requests
                        # (per-gap percentiles here would measure frame
                        # coalescing in the replica->proxy->socket hops,
                        # not decode cadence).
                        tpots.append((self.last - self.first_t)
                                     / (self.n - 1))
                return True
            return False

    streams = [Stream(np_, mt) for np_, mt in plans]
    t_start = time.perf_counter()
    live = len(streams)
    while live and time.perf_counter() < max(stop_at, t_start) + 30:
        for key, _ in sel.select(timeout=0.5):
            st = key.data
            try:
                data = st.sock.recv(65536)
            except BlockingIOError:
                continue
            now = time.perf_counter()
            if data and st.feed(data, now):
                if time.perf_counter() < stop_at:
                    st.send()
                else:
                    sel.unregister(st.sock)
                    st.sock.close()
                    live -= 1
    elapsed = time.perf_counter() - t_start
    for key in list(sel.get_map().values()):
        key.data.sock.close()
    sel.close()
    return {"ttfts": ttfts, "tpots": tpots, "tokens": tokens[0],
            "elapsed": elapsed}


def run_serve_llm_mixed(duration_s: float = 8.0, stream_clients: int = 3,
                        long_clients: int = 3,
                        max_tokens: int = 24) -> dict:
    """Mixed streaming + long-prefill workload, A/B chunked prefill +
    prefix cache OFF vs ON in one process. The off arm reproduces the
    old admission behavior — a 96-token prompt prefills whole,
    stalling every live decode stream for that whole step, and every
    repeat of a recurring long prompt re-prefills its shared prefix.
    The on arm bounds per-step prefill work to 32 tokens and reuses
    the cached prefix, which is where the TTFT/TPOT p90 reduction
    comes from."""
    from ray_tpu import serve
    from ray_tpu.models.gpt import TINY
    from ray_tpu.serve.llm import build_app

    shared = [(11 * i) % 400 + 1 for i in range(64)]
    # Realistic request mix: a handful of recurring prompts (few-shot
    # templates, retry storms), not a fresh prompt per request — this
    # is the population the prefix cache exists for. The off arm pays
    # the full prefill for every repeat.
    long_tails = [[(t * 13 + i) % 400 + 1 for i in range(40)]
                  for t in range(3)]
    short_prompts = [[p * 7 % 400 + 1] * (4 + p % 9) for p in range(8)]

    def one_pass(on: bool) -> dict:
        # 96 blocks: enough headroom that parking every finished chain
        # for reuse doesn't force an eviction per admission (the on arm
        # retains ~5 hot chains of ~8 blocks plus in-flight tables).
        serve.run(build_app(
            TINY, num_blocks=96, block_size=16,
            max_batch=stream_clients + long_clients + 2,
            prefill_chunk_tokens=(32 if on else None),
            prefix_cache=on), name="llm")
        proxy = serve.start(http_port=0)
        h = serve.get_app_handle("llm")
        # Warm the compile shapes AND the recurring-prompt population:
        # steady-state serving is what the SLO pair measures, so the
        # one-time cold prefill of each template stays out of the
        # timed window (the off arm re-pays it per request anyway).
        warm = http.client.HTTPConnection("127.0.0.1", proxy.port,
                                          timeout=600)
        for tail in long_tails:
            _llm_stream(warm, shared + tail, 4, seed=0)
        for p in short_prompts:
            _llm_stream(warm, p, 4, seed=0)
        warm.close()

        def plan(cid, long_prompts):
            state = {"seed": cid}

            def next_prompt():
                seed = state["seed"]
                state["seed"] += 64
                if long_prompts:
                    return shared + long_tails[seed % 3], seed
                return short_prompts[seed % 8], seed

            # Long-prompt clients turn around faster (shorter outputs)
            # so the off arm keeps paying whole-prompt prefills.
            return next_prompt, (max_tokens // 2 if long_prompts
                                 else max_tokens)

        plans = [plan(i, False) for i in range(stream_clients)]
        plans += [plan(100 + i, True) for i in range(long_clients)]
        res = _mux_llm_clients(proxy.port, duration_s, plans)
        eng = h.options(method_name="engine_stats").remote().result(
            timeout=60)
        serve.shutdown()
        return {
            "requests": len(res["ttfts"]),
            "tokens_per_s": round(res["tokens"] / res["elapsed"], 1),
            "ttft": _percentiles(res["ttfts"]),
            "tpot": _percentiles(res["tpots"]),
            "kv_cache_hit_rate": round(
                eng.get("kv_cache_hit_rate", 0.0), 3),
            "kv_util_peak": round(eng.get("kv_util_peak", 0.0), 3),
            "prefill_chunks": eng.get("prefill_chunks", 0),
        }

    return {
        "stream_clients": stream_clients,
        "long_clients": long_clients,
        "long_prompt_tokens": 104,
        "max_tokens": max_tokens,
        "chunking_off": one_pass(False),
        "chunking_on": one_pass(True),
        "note": "A/B in one process: off = whole-prompt prefill, no "
                "prefix reuse; on = 32-token chunked admission + "
                "prefix cache (the serving defaults)",
    }


def main():
    import ray_tpu

    duration = float(os.environ.get("RT_SERVE_BENCH_S", "3"))
    clients = int(os.environ.get("RT_SERVE_BENCH_CLIENTS", "4"))
    ray_tpu.init(num_cpus=2)
    try:
        doc = run(duration_s=duration, clients=clients)
    finally:
        ray_tpu.shutdown()
    doc_fleet = run_fleet(duration_s=duration, clients=clients)
    import jax

    doc = {
        "ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "device": {"platform": jax.devices()[0].platform,
                   "kind": jax.devices()[0].device_kind},
        "duration_s": duration,
        "clients": clients,
        **doc,
        "fleet": doc_fleet,
        "ingress_overhead_ms": round(
            doc["http_local"]["p50_ms"] - doc["handle"]["p50_ms"], 2),
    }
    out = os.environ.get("RT_SERVE_BENCH_OUT", "SERVE_BENCH.json")
    with open(out, "w") as f:
        json.dump(doc, f, indent=2)
        f.write("\n")
    print(json.dumps(doc))


if __name__ == "__main__":
    main()
