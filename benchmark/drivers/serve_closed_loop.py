"""Driver ``serve_closed_loop``: N callers over HTTP streaming through
the Serve proxy, each sending its next request when the last one's done
frame arrives, no think time. The callers are threads of generator
subprocesses (``drivers/callers.py``; four processes for 64 callers),
not of this process, which is the server's: they share with it the
loopback socket and the monotonic clock. The set-up requests below are
sent from this process, before the window.

Reads from the configuration file ``model`` and ``serve`` (the app's
builder and its keyword arguments: pool, block size, ``max_batch``,
chunk size) and from the cell's file ``traffic`` (callers, prefixes,
body and answer sizes, stagger and ramp) and ``reference_request``.

Set-up, in this order: deploy, and wait for the replica's first answer
(``engine_stats``); one unshared reference request, which decodes
beside the warm-ups and the ramp; one request a prefix, which registers it;
one request for every chunk length the cell's prompts can give (the
engine compiles a chunk's forward pass, its cache slice and its pool
write once a length; bodies come in multiples of ``multiple_of``, so
the lengths are its multiples up to the chunk budget: a deployment
that has been up for a while has them all, so the window must too);
the callers, staggered (each generator starts its callers at the
instants it is handed); the ramp. Then the window. Requests still in
flight when it closes are cut off by closing their sockets: the
generators are told so at the close, and hand their records over then.

After the window, outside every timed span, ``correct`` is decided:
the reference request and, for every prefix, the longest answer a
sharer of it got entirely inside the window (a prefix-cache hit,
decoded in a full batch) are rebuilt from the plan and compared token
by token with plain greedy decoding over ``gpt.forward``.

Collects: one record a request (send time, every token frame's time,
the done frame, stamped by the generator that sent it), the
generators' reports (``callers``: their CPU seconds inside the window),
``engine_stats`` at both edges of the window, the engine's ``llm.step``
ring entries in between, and the device's memory peaks as the window
closes.
"""

from __future__ import annotations

import importlib
import threading
import time

from benchmark.drivers.callers import Fleet, server_threads, stream


# Token streams of the set-up requests: "callers" no real caller has.
_REFERENCE, _WARM_PREFIX, _FIRST_OF_PREFIX, _TAIL = (
    1_000_001, 1_000_002, 1_000_003, 1_000_004)


def _must(rec: dict, what: str) -> dict:
    if rec["failed"]:
        raise SystemExit(f"benchmark: {what} failed: {rec['error']}")
    return rec


def run(ctx) -> dict:
    import jax

    from benchmark import reference, traffic
    from benchmark.harness import Tracer, log, memory_peaks, sized
    from ray_tpu import serve
    from ray_tpu.util import perfmodel

    deploy = sized(ctx.config["serve"], ctx.rehearse)
    spec = sized(ctx.workload["traffic"], ctx.rehearse)
    ref = sized(ctx.workload["reference_request"], ctx.rehearse)
    vocab = ctx.model_fields["vocab_size"]
    seed31 = traffic.seed31(ctx.seed)
    plan = traffic.closed_loop_plan(spec, ctx.seed, vocab)
    engine = dict(deploy["kwargs"])
    chunk, block = engine["prefill_chunk_tokens"], engine["block_size"]

    builder = getattr(importlib.import_module(deploy["module"]),
                      deploy["builder"])
    t0 = time.perf_counter()
    handle = serve.run(builder(ctx.model_cfg, seed=seed31, **engine),
                       name="llm")
    proxy = serve.start(http_port=0)
    host, port = "127.0.0.1", proxy.port
    stats = handle.options(method_name="engine_stats")
    # ``serve.run`` returns before the replica is built: the first
    # answer to anything says it is up, so that no request's timeout
    # (the proxy's 504 after 60 s) covers the start.
    stats.remote().result(timeout=900)
    log(f"deployed in {time.perf_counter() - t0:.1f} s on port {port}")
    # The generators start their interpreters beside the warm-ups; the
    # harness ends them whatever becomes of this run.
    fleet = Fleet(host, port, spec, ctx.seed, vocab, log)
    ctx.cleanup.append(fleet.kill)

    # -- the reference request: unshared, decoding beside the warm-ups
    ref_prompt = plan["tokens"](_REFERENCE, 0, ref["prompt_tokens"])
    ref_got = {}
    ref_thread = threading.Thread(
        target=lambda: ref_got.update(stream(host, port, {
            "prompt": ref_prompt, "max_tokens": ref["max_tokens"]})),
        name="reference-request")
    ref_thread.start()

    # -- register the prefixes; warm every chunk length the prompts give
    prefixes = plan["prefixes"]
    every = int(spec["body_tokens"].get("multiple_of", 1))
    warm_prefix = prefixes[0] if prefixes else \
        plan["tokens"](_WARM_PREFIX, 0, 2 * block)
    first = [_must(stream(host, port, {
        "prompt": p + plan["tokens"](_FIRST_OF_PREFIX, i, max(every, block)),
        "max_tokens": 1}), "a prefix's first request")
        for i, p in enumerate(prefixes or [warm_prefix])]
    checks = []
    tails = list(range(every, chunk + 1, every))
    for r in tails:
        rec = _must(stream(host, port, {
            "prompt": warm_prefix + plan["tokens"](_TAIL, r, r),
            "max_tokens": 1}), f"the warm-up request of tail length {r}")
        if rec["done"]["cached_tokens"] != len(warm_prefix):
            checks.append((False, f"warm-up tail {r}: cached_tokens "
                           f"{rec['done']['cached_tokens']} is not the "
                           f"prefix's {len(warm_prefix)}"))
    log(f"{len(first)} prefix request(s) and {len(tails)} chunk lengths "
        f"warmed {time.perf_counter() - t0:.1f} s after deploy began")

    # -- the callers, in their generators
    checks += fleet.ready()
    t_first_caller = time.perf_counter() + 0.05
    fleet.start(t_first_caller, spec["stagger_s"])
    time.sleep(max(0.0, t_first_caller + spec["ramp_s"]
                   - time.perf_counter()))
    ref_thread.join(timeout=600)
    _must(ref_got or {"failed": True, "error": "never answered"},
          "the reference request")

    # -- the window
    tracer = Tracer(ctx.trace)
    shape = sized(ctx.workload.get("window", {}), ctx.rehearse)
    stats_open = stats.remote().result(timeout=60)
    t_open, t_open_wall = time.perf_counter(), time.time()
    if ctx.trace:
        time.sleep(shape.get("trace_after_s", 2.0))
        tracer.start()
        time.sleep(shape.get("trace_seconds", 3.0))
        tracer.stop()
    time.sleep(max(0.0, t_open + ctx.seconds - time.perf_counter()))
    t_close, t_close_wall = time.perf_counter(), time.time()
    stats_close = stats.remote().result(timeout=60)
    memory = memory_peaks()
    checks.append(server_threads(log))
    steps = [e for e in perfmodel.device_step_events(since=t_open_wall)
             if e["name"] == "llm.step" and e["t_wall"] <= t_close_wall]
    closed = fleet.close(t_open, t_close)
    records, in_flight = closed["records"], closed["in_flight"]
    checks += fleet.checks(closed["reports"])
    serve.shutdown()

    ended = [r for r in records if t_open <= r["t_end"] <= t_close]
    done = [r for r in ended if not r["failed"]]
    cut = [r for r in records if r["t_end"] > t_close]
    log(f"window {t_close - t_open:.3f} s: {len(done)} requests completed, "
        f"{len(ended) - len(done)} failed, {len(cut)} cut off at the close, "
        f"{len(steps)} engine steps, "
        f"{sum(len(r['t_tokens']) for r in records)} token frames in all")
    # Counts a person reads to see whether the lanes were still filling:
    # token frames and first tokens in each sixth of the window.
    sixth = (t_close - t_open) / 6
    for k in range(6):
        a, b = t_open + k * sixth, t_open + (k + 1) * sixth
        log(f"  sixth {k + 1}: "
            f"{sum(a <= t < b for r in records for t in r['t_tokens'])} "
            f"token frames, "
            f"{sum(a <= r['t_tokens'][0] < b for r in records if r['t_tokens'])}"
            f" first tokens, {sum(a <= r['t_end'] < b for r in done)} "
            f"completions")
    for r in ended:
        if r["failed"]:
            log(f"  failed: caller {r['caller']} request {r['index']}: "
                f"{r['error']}")
    checks += [
        (in_flight == 0, "every caller thread ended after the close"),
        (len(done) > 0, f"{len(done)} requests completed inside the window"),
        (all(len(r["tokens"]) == r["max_tokens"] == r["done"]["num_tokens"]
             for r in done),
         "every completed request streamed exactly its max_tokens"),
        (stats_close["platform"] == ctx.device["platform"],
         f"engine_stats reports platform {stats_close['platform']}"),
    ]
    if ctx.device["platform"] == "tpu":
        checks.append((stats_close["paged_kernel"] == "compiled",
                       f"the paged kernel is "
                       f"{stats_close['paged_kernel']}"))
    sharers = [r for r in done if r["prefix"] is not None]
    if prefixes:
        checks.append((bool(sharers) and all(
            r["done"]["cached_tokens"] > 0 for r in sharers),
            f"all {len(sharers)} completed prefix sharers report "
            f"cached_tokens > 0"))
    preempted = sum(r["done"].get("preemptions", 0) for r in done)
    log(f"engine: kv_util_peak {stats_close['kv_util_peak']:.3f}, "
        f"{preempted} preemptions among the completed requests, prefix "
        f"{stats_close.get('prefix')}")

    # -- `correct`: answers of the window against plain greedy decoding
    compare = [("the reference request", ref_prompt, ref_got["tokens"])]
    for i, pre in enumerate(prefixes):
        mine = [r for r in sharers if r["prefix"] == i]
        inside = [r for r in mine if r["t_send"] >= t_open] or mine
        if not inside:
            checks.append((False, f"no sharer of prefix {i} completed "
                           f"inside the window"))
            continue
        r = max(inside, key=lambda r: (len(r["tokens"]), r["t_send"]))
        compare.append((
            f"caller {r['caller']} request {r['index']} (prefix {i}, "
            f"{r['prompt_len']}-token prompt, {r['done']['cached_tokens']} "
            f"cached, sent {r['t_send'] - t_open:.1f} s into the window)",
            pre + plan["tokens"](r["caller"], r["index"], r["body"]),
            r["tokens"]))
    model = importlib.import_module(ctx.config["model"]["module"])
    params = model.init(jax.random.PRNGKey(seed31), ctx.model_cfg)
    for what, prompt, got in compare:
        checks += reference.serve_checks(
            what, *reference.greedy_check(params, ctx.model_cfg, prompt, got),
            len(got))
    del params
    return {
        "kind": "serve",
        "checks": checks,
        "attempted": len(ended), "failed": len(ended) - len(done),
        "t_open": t_open, "t_close": t_close,
        "window_s": t_close - t_open,
        # A request cut off at the close was still running: its frames
        # inside the window count, its end does not.
        "records": [r for r in records
                    if not (r["failed"] and r["t_end"] <= t_close)],
        "failed_records": [r for r in ended if r["failed"]],
        "callers": closed["reports"],
        "engine_stats": (stats_open, stats_close),
        "engine_steps": steps,
        "max_batch": engine["max_batch"],
        "memory": memory,
        "device_extra": {"kv_live_peak_share": stats_close["kv_util_peak"]},
        "tracer": tracer if ctx.trace else None,
    }
