"""Driver ``serve_closed_loop_ref``: what ``serve_closed_loop`` does (N
closed-loop callers over HTTP streaming through the Serve proxy, the
same set-up order, window, records and collected keys), with the plain
reference that decides ``correct`` NAMED BY THE CONFIGURATION's file
(``reference.module``) instead of ``benchmark/reference.py``, which is
wired to ``gpt.forward``. A file of its own because no existing file of
the benchmark may change; ``stream``, ``_must`` and the set-up's
token-stream ids are ``serve_closed_loop``'s, by import, the callers
are ``drivers/callers.py``'s generator subprocesses as there, and the
plan is ``traffic.closed_loop_plan``'s.

What differs, and why:

* Two short unshared requests come first, so that each of the
  programs' first compilations falls into a request of its own: an
  8,192-token prefix sent cold would wait out all of them inside the
  proxy's 60 s request timeout.
* A shared prefix is registered by sending it ALONE (one answer token),
  not with a first body behind it: a model with window layers can take
  a cached prefix up only where the window behind its end is still
  held, and the engine parks that tail when a sequence is released at
  the prefix's end (ray_tpu/llm/kv_cache.py ``WindowPool``).
* Before the reference runs, every device array of the finished
  deployment is deleted: the served parameters and pools fill most of
  the chip, and the reference needs the room (it makes the same
  parameters again from ``--seed`` and raises them to float32 a layer
  at a time).
* ``correct`` compares the reference request and, for the first
  ``compare_prefixes`` prefixes of the cell's file, the longest answer
  a sharer completed inside the window: their tokens, pooled, against
  the reference's full forward pass, and the served router against the
  reference's on those sequences' router inputs (the reference module
  says why both).
* With ``BENCH_LAGUNA_CONTROLS`` set in the environment the same
  answers are read again against the reference one precision lower and
  with faults planted in it (a window one block short, one expert
  fewer a token, routed weights that sum to 1), and the readings are
  logged: how the limits' other readings are made again. They decide
  nothing.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib
import os
import threading
import time


def run(ctx) -> dict:
    import jax

    from benchmark import harness, traffic
    from benchmark.harness import Tracer, log, memory_peaks, sized
    from ray_tpu import serve
    from ray_tpu.util import perfmodel

    base = harness.load_module("drivers", "serve_closed_loop")
    stream, must = base.stream, base._must
    reference = importlib.import_module(ctx.config["reference"]["module"])

    deploy = sized(ctx.config["serve"], ctx.rehearse)
    spec = sized(ctx.workload["traffic"], ctx.rehearse)
    ref = sized(ctx.workload["reference_request"], ctx.rehearse)
    vocab = ctx.model_fields["vocab_size"]
    seed31 = traffic.seed31(ctx.seed)
    plan = traffic.closed_loop_plan(spec, ctx.seed, vocab)
    engine = dict(deploy["kwargs"])
    chunk = engine["prefill_chunk_tokens"]

    builder = getattr(importlib.import_module(deploy["module"]),
                      deploy["builder"])
    t0 = time.perf_counter()
    handle = serve.run(builder(ctx.model_cfg, seed=seed31, **engine),
                       name="llm")
    proxy = serve.start(http_port=0)
    host, port = "127.0.0.1", proxy.port
    stats = handle.options(method_name="engine_stats")
    # ``serve.run`` returns before the replica has made its weights
    # (3.9 B parameters take a while): the first answer to anything
    # says it is up, so that no request's timeout covers the start.
    stats.remote().result(timeout=900)
    log(f"deployed in {time.perf_counter() - t0:.1f} s on port {port}")
    # The generators start their interpreters beside the warm-ups; the
    # harness ends them whatever becomes of this run.
    fleet = base.Fleet(host, port, spec, ctx.seed, vocab, log)
    ctx.cleanup.append(fleet.kill)

    # -- the programs' first compilations, one short request each, so
    # that no later request waits out more than one inside the proxy's
    # request timeout: a span from a prompt's start (one chunk, no
    # table), then a span behind one (two chunks)
    for n in (chunk, 2 * chunk):
        must(stream(host, port, {
            "prompt": plan["tokens"](base._WARM_PREFIX, n, n),
            "max_tokens": 2}), f"the {n}-token compile warm-up")
    log(f"programs compiled {time.perf_counter() - t0:.1f} s after deploy "
        f"began")

    # -- the reference request: unshared, decoding beside the warm-ups
    ref_prompt = plan["tokens"](base._REFERENCE, 0, ref["prompt_tokens"])
    ref_got = {}
    ref_thread = threading.Thread(
        target=lambda: ref_got.update(stream(host, port, {
            "prompt": ref_prompt, "max_tokens": ref["max_tokens"]})),
        name="reference-request")
    ref_thread.start()

    # -- register the prefixes (each alone); warm every chunk length
    prefixes = plan["prefixes"]
    every = int(spec["body_tokens"].get("multiple_of", 1))
    for i, p in enumerate(prefixes):
        must(stream(host, port, {"prompt": p, "max_tokens": 1}),
             f"the request that registers prefix {i}")
    checks = []
    tails = list(range(every, chunk + 1, every))
    for r in tails:
        rec = must(stream(host, port, {
            "prompt": prefixes[0] + plan["tokens"](base._TAIL, r, r),
            "max_tokens": 1}), f"the warm-up request of tail length {r}")
        if rec["done"]["cached_tokens"] != len(prefixes[0]):
            checks.append((False, f"warm-up tail {r}: cached_tokens "
                           f"{rec['done']['cached_tokens']} is not the "
                           f"prefix's {len(prefixes[0])}"))
    log(f"{len(prefixes)} prefixes registered and {len(tails)} chunk "
        f"lengths warmed {time.perf_counter() - t0:.1f} s after deploy "
        f"began")

    # -- the callers, in their generators
    checks += fleet.ready()
    t_first_caller = time.perf_counter() + 0.05
    fleet.start(t_first_caller, spec["stagger_s"])
    time.sleep(max(0.0, t_first_caller + spec["ramp_s"]
                   - time.perf_counter()))
    ref_thread.join(timeout=600)
    must(ref_got or {"failed": True, "error": "never answered"},
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
    checks.append(base.server_threads(log))
    steps = [e for e in perfmodel.device_step_events(since=t_open_wall)
             if e["name"] == "llm.step" and e["t_wall"] <= t_close_wall]
    closed = fleet.close(t_open, t_close)
    records, in_flight = closed["records"], closed["in_flight"]
    checks += fleet.checks(closed["reports"])
    # Requests cut off at the close still decode in the engine: let it
    # drain, so that it is idle when its arrays go (below).
    for _ in range(120):
        now = stats.remote().result(timeout=60)
        if not now["in_flight"] and not now["waiting"]:
            break
        time.sleep(0.5)
    serve.shutdown()

    ended = [r for r in records if t_open <= r["t_end"] <= t_close]
    done = [r for r in ended if not r["failed"]]
    cut = [r for r in records if r["t_end"] > t_close]
    log(f"window {t_close - t_open:.3f} s: {len(done)} requests completed, "
        f"{len(ended) - len(done)} failed, {len(cut)} cut off at the close, "
        f"{len(steps)} engine steps, "
        f"{sum(len(r['t_tokens']) for r in records)} token frames in all")
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
    sharers = [r for r in done if r["prefix"] is not None]
    checks += [
        (in_flight == 0, "every caller thread ended after the close"),
        (len(done) > 0, f"{len(done)} requests completed inside the window"),
        (all(len(r["tokens"]) == r["max_tokens"] == r["done"]["num_tokens"]
             for r in done),
         "every completed request streamed exactly its max_tokens"),
        (stats_close["platform"] == ctx.device["platform"],
         f"engine_stats reports platform {stats_close['platform']}"),
        # A hit through BOTH kinds of cache: the whole prefix, which the
        # window kind grants only where its tail is still parked.
        (bool(sharers) and all(
            r["done"]["cached_tokens"] == len(prefixes[r["prefix"]])
            for r in sharers),
         f"all {len(sharers)} completed prefix sharers report their whole "
         f"prefix cached"),
    ]
    if ctx.device["platform"] == "tpu":
        checks.append((stats_close["paged_kernel"] == "compiled",
                       f"the paged kernel is "
                       f"{stats_close['paged_kernel']}"))
    preempted = sum(r["done"].get("preemptions", 0) for r in done)
    log(f"engine: kv_util_peak {stats_close['kv_util_peak']:.3f}, window "
        f"kind {stats_close.get('kv_window_util_peak')}, {preempted} "
        f"preemptions among the completed requests, prefix "
        f"{stats_close.get('prefix')}")

    # -- `correct`: answers of the window against the plain reference
    compare = [("the reference request", ref_prompt, ref_got["tokens"])]
    for i in range(min(len(prefixes),
                       int(ctx.workload.get("compare_prefixes", 4)))):
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
            prefixes[i] + plan["tokens"](r["caller"], r["index"], r["body"]),
            r["tokens"]))
    # The deployment is down; what it left on the device goes, so that
    # the reference fits.
    del handle, stats
    gc.collect()
    for a in jax.live_arrays():
        a.delete()
    model = importlib.import_module(ctx.config["model"]["module"])
    params = model.init(jax.random.PRNGKey(seed31), ctx.model_cfg)
    t_ref = time.perf_counter()
    cfg, route = ctx.model_cfg, reference.served_router_of(ctx.config)
    read = reference.compare(params, cfg, route, compare)
    for line in read["lines"]:
        log(f"  {line}")
    checks += reference.token_checks(read) + reference.router_checks(read)
    if os.environ.get("BENCH_LAGUNA_CONTROLS"):
        controls = {
            "one precision lower": (cfg, True),
            "a window one block short": (dataclasses.replace(
                cfg, sliding_window=cfg.sliding_window
                - engine["block_size"]), False),
            "one expert fewer a token": (dataclasses.replace(
                cfg, num_experts_per_tok=cfg.num_experts_per_tok - 1),
                False),
            "routed weights sum to 1": (dataclasses.replace(
                cfg, moe_routed_scaling_factor=1.0), False)}
        for name, (faulty, lower) in controls.items():
            r = reference.compare(params, faulty, route, compare, lower)
            for ok, text in (reference.token_checks(r)
                             + reference.router_checks(r)):
                log(f"control, {name}: {'PASSES' if ok else 'fails'}: "
                    f"{text}")
    log(f"{len(compare)} comparisons with the reference in "
        f"{time.perf_counter() - t_ref:.1f} s")
    del params
    return {
        "kind": "serve",
        "checks": checks,
        "attempted": len(ended), "failed": len(ended) - len(done),
        "t_open": t_open, "t_close": t_close,
        "window_s": t_close - t_open,
        "records": [r for r in records
                    if not (r["failed"] and r["t_end"] <= t_close)],
        "failed_records": [r for r in ended if r["failed"]],
        "callers": closed["reports"],
        "engine_stats": (stats_open, stats_close),
        "engine_steps": steps,
        "max_batch": engine["max_batch"],
        "memory": memory,
        "device_extra": {
            "kv_live_peak_share": stats_close["kv_util_peak"],
            "kv_window_live_peak_share":
                stats_close.get("kv_window_util_peak")},
        "tracer": tracer if ctx.trace else None,
    }
