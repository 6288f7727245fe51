"""Proof that ray_tpu's two TPU paths start and are right on the chip.

    python chip_smoke.py             one chip: a JaxTrainer run, then an
                                     LLMEngine behind Serve, then the
                                     same Serve recipe from a driver
                                     attached to an `rtpu start --head`
                                     cluster; GPT-2-small at full width
                                     (12 layers, d_model 768, 12 heads
                                     of 64, vocab 50,304, seq 1,024),
                                     weights from --seed
    python chip_smoke.py --chips 4   four chips: the sharded JaxTrainer
                                     run and its one-chip comparison,
                                     and no other phase

A chip belongs to one process at a time, so this parent never imports
jax: every phase runs in its own child, one after the other (in the
detached phase the chip's owner is the cluster's node daemon, and the
child only attaches to it), and the chip owners share one persistent
compile cache (JAX_COMPILATION_CACHE_DIR
when it is set, else <repo>/.jax_cache). A phase that fails, finds no
TPU or ran on another platform makes the script exit non-zero with no
result line. The last line of stdout is the one JSON object the
driver reads; the figures printed before it are smoke figures from a
handful of steps, not benchmark results.

--rehearse runs the same phases at TINY size on whatever backend jax
has (the CPU rehearsal of /opt/skills/guides/on-chip-measurement §2). It
checks control flow only and always exits 3, without a result line.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
RESULT_TAG = "PHASE_RESULT "
# Per-phase wall limits, compilation included. The serve phase took
# 597 s with two Kimi comparisons (my chip run, PR 34), ~510 s with one.
PHASE_TIMEOUT_S = {"train": 360, "serve": 600, "detached": 280,
                   "train4": 900}

# Stated tolerances (bf16 activations, f32 softmax statistics and loss).
# The loss moves ~0.006 over three steps, so a loss tolerance has to sit
# well under that to tell an update from no update; the figures in
# brackets are what the chip showed (CHANGES.md, PR 21).
LOSS_START_TOL = 0.5        # first loss vs ln(vocab): random init
LOSS_TOL = 1e-3             # flash vs dense loss [3e-5]; 4-chip vs 1-chip
                            # loss, step by step [5e-5]
LOSS_FALL_MIN = 2 * LOSS_TOL    # last loss under the first by at least this
ATTN_GRAD_REL_TOL = 0.05    # d loss/d(wq, wk), flash vs dense, relative L2
                            # [0.0138]
MESH_GRAD_REL_TOL = 0.005   # the same gradients' per-head norms, 4 chips
                            # vs 1, over the largest norm [0.00075]
GRAD_ROWS = 8               # rows of batch 0 those gradients are taken on
LOGIT_MARGIN_EPS = 0.05     # top-two margin under which bf16 may flip argmax
# Laguna's served bfloat16 logits against its float32 reference, largest
# absolute difference over every compared row (logits have spread ~0.9).
# Not a rounding-sized limit: a top-k router turns bfloat16 rounding into
# swapped experts on about one token-layer in ten, and a swapped expert
# moves a row by 0.1-0.6 (benchmark/reference_laguna.py has the readings;
# the typical row, reported beside it, differs by rounding alone). A
# wrong mask, rotary or expert reads ~4.
LAGUNA_LOGIT_TOL = 1.5
# Kimi-K2.5's served share against its float32 non-absorbed reference,
# the same way: the largest difference read 0.14 and the median row's
# 0.11 (my chip run, PR 34; logits have spread ~1.7), the bfloat16
# residual's rounding through five layers. The router swaps less here
# (a token's 8 experts fall on the 12 held ones a quarter of the time);
# a missing rope term or softmax scale moves a row by 2.5-10
# (benchmark/reference_kimi_k2.py has the readings).
KIMI_LOGIT_TOL = 0.6


_T0 = time.perf_counter()


def log(msg: str) -> None:
    print(f"[{time.perf_counter() - _T0:6.1f}s] {msg}", flush=True)


# ---------------------------------------------------------------------------
# Shared by the children (each child is the one process that owns the chip)
# ---------------------------------------------------------------------------

def _open_device(rehearse: bool) -> dict:
    """First jax touch of a child: name the device, refuse anything but a
    TPU (unless rehearsing), turn the shared compile cache on."""
    import jax

    d = jax.devices()[0]
    info = {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}
    log(f"  device: {info}")
    if d.platform != "tpu" and not rehearse:
        raise SystemExit(
            f"chip_smoke: jax found no TPU (devices: {info}; JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS')!r}) - this script proves the "
            f"chip path and does not run on another backend")
    from ray_tpu._private.backend_probe import enable_compile_cache

    placed = ("placed by JAX_COMPILATION_CACHE_DIR"
              if os.environ.get("JAX_COMPILATION_CACHE_DIR")
              else "fixed in-checkout default")
    log(f"  compile cache: {enable_compile_cache()} ({placed})")
    hits = {"hit": 0, "miss": 0}

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            hits["hit"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            hits["miss"] += 1

    jax.monitoring.register_event_listener(on_event)
    info["_cache"] = hits
    return info


def _finish(info: dict, **figures) -> None:
    cache = info.pop("_cache", None)
    if cache is not None:
        log(f"  compile cache this phase: {cache['hit']} hits, "
            f"{cache['miss']} misses")
    print(RESULT_TAG + json.dumps({"device": info, "cache": cache,
                                   **figures}), flush=True)


def _check(ok: bool, what: str) -> None:
    log(f"  [{'ok' if ok else 'FAIL'}] {what}")
    if not ok:
        raise SystemExit(f"chip_smoke: check failed: {what}")


def _attention_grads(params, tokens, cfg, mesh):
    """d loss / d (wq, wk) on a few rows. These two weights reach the
    loss only through the attention scores, so their gradient is the
    kernel's backward (dq, dk) and nothing else: a random-init loss
    barely depends on attention, this does."""
    import jax

    from ray_tpu.models import gpt

    def attn_grads(p, t):
        g = jax.grad(gpt.loss_fn)(p, t, cfg, mesh)["blocks"]
        return g["wq"], g["wk"]

    return jax.jit(attn_grads)(params, tokens[:GRAD_ROWS])


def _train_loop(config):
    """The train loop of the benchmark's train cell (Data ingest -> device_put per step ->
    the tuned GPT-2 step), instrumented: AOT-compiles the step so the
    compiled text can be inspected, and reads the loss on the host after
    every step."""
    import time as _t

    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu import train as rt_train
    from ray_tpu.models import gpt
    from ray_tpu.parallel import MeshSpec

    cfg = config["cfg"]
    devices = jax.devices()[:config["n_devices"]]
    mesh = MeshSpec.auto(len(devices)).build(devices)
    opt = optax.adamw(3e-4, b1=0.9, b2=0.95, weight_decay=0.1,
                      mu_dtype=jnp.bfloat16)
    params = gpt.init(jax.random.key(config["seed"]), cfg)
    state = {"params": params, "opt_state": opt.init(params), "step": 0}
    state = gpt.shard_state(state, mesh, cfg)
    step_fn = gpt.make_train_step(cfg, opt, mesh)
    sharding = NamedSharding(mesh, P(("dp", "fsdp")))
    shard = rt_train.get_dataset_shard("train")

    out = {"losses": [], "step_ms": []}
    compiled = None
    for b in shard.iter_batches(batch_size=config["batch"],
                                batch_format="jax", sharding=sharding,
                                drop_last=True):
        tokens = b["tokens"]
        if compiled is None:
            if config["dense_reference"]:
                # The plain reference for the kernel: the same loss on
                # the same params and batch through dense jnp attention.
                import dataclasses

                dense = dataclasses.replace(cfg, use_flash=False)
                out["dense_loss"] = float(jax.jit(
                    lambda p, t: gpt.loss_fn(p, t, dense, mesh))(
                        state["params"], tokens))
                want = _attention_grads(state["params"], tokens, dense, mesh)
            got = _attention_grads(state["params"], tokens, cfg, mesh)
            # [layer, head] norms: what the 4-chip run is compared on.
            out["attn_grad_norms"] = [
                jnp.sqrt((g * g).sum(axis=(1, 3))).tolist() for g in got]
            if config["dense_reference"]:
                out["attn_grad_rel_err"] = [
                    float(jnp.linalg.norm((a - b).ravel())
                          / jnp.linalg.norm(b.ravel()))
                    for a, b in zip(got, want)]
                del want
            del got     # the step needs nearly the whole chip
            t0 = _t.perf_counter()
            compiled = step_fn.lower(state, tokens).compile()
            out["compile_s"] = _t.perf_counter() - t0
            text = compiled.as_text()
            out["kernel_calls"] = text.count("tpu_custom_call")
            out["collectives"] = sum(
                text.count(k) for k in ("all-gather", "all-reduce",
                                        "reduce-scatter"))
        t0 = _t.perf_counter()
        state, metrics = compiled(state, tokens)
        out["losses"].append(float(metrics["loss"]))   # host read = fence
        out["step_ms"].append((_t.perf_counter() - t0) * 1e3)
        if len(out["losses"]) >= config["steps"]:
            break
    leaves = jax.tree_util.tree_leaves(state)
    out["platforms"] = sorted({d.platform for x in leaves
                               for d in x.devices()})
    per_dev: dict = {}
    for x in leaves:
        for s in x.addressable_shards:
            per_dev[s.device.id] = per_dev.get(s.device.id, 0) \
                + s.data.nbytes
    out["bytes_per_device"] = per_dev
    out["state_bytes"] = sum(x.nbytes for x in leaves)
    rt_train.report(out)


def _fit(cfg, *, n_devices: int, batch: int, steps: int, seed: int,
         dense_reference: bool, name: str) -> dict:
    """One JaxTrainer.fit on the device lane over the first n_devices."""
    import numpy as np

    from ray_tpu import data as rt_data
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    rng = np.random.default_rng(seed)
    rows = [{"tokens": rng.integers(0, cfg.vocab_size, cfg.max_seq,
                                    dtype=np.int32)}
            for _ in range(batch * steps)]
    trainer = JaxTrainer(
        _train_loop,
        train_loop_config={"cfg": cfg, "batch": batch, "steps": steps,
                           "seed": seed, "n_devices": n_devices,
                           "dense_reference": dense_reference},
        scaling_config=ScalingConfig(num_workers=1, use_tpu=True),
        run_config=RunConfig(name=name),
        datasets={"train": rt_data.from_items(rows)})
    result = trainer.fit()
    if result.error is not None:
        raise SystemExit(f"chip_smoke: JaxTrainer.fit failed: {result.error}")
    return result.metrics


def _train_cfg(tiny: bool):
    import dataclasses

    from ray_tpu.models import gpt

    base = gpt.TINY if tiny else gpt.GPT2_SMALL
    return dataclasses.replace(base, remat=True, use_flash=True)


# ---------------------------------------------------------------------------
# Phase: train (one chip)
# ---------------------------------------------------------------------------

def phase_train(args) -> None:
    info = _open_device(args.rehearse)
    on_tpu = info["platform"] == "tpu"
    import ray_tpu

    cfg = _train_cfg(args.rehearse)
    batch, steps = (4, 3) if args.rehearse else (24, 5)
    rt = ray_tpu.init(num_cpus=2)
    try:
        log(f"  object store: {type(rt.shm).__name__}")
        _check(type(rt.shm).__name__ == "NativeObjectStore",
               "the native (C++) object store is in use")
        m = _fit(cfg, n_devices=1, batch=batch, steps=steps, seed=args.seed,
                 dense_reference=True, name="chip_smoke_train")
        if on_tpu:
            # The profiler session works on this jax only from the
            # process that holds the chip — which is this one.
            from ray_tpu._private import profiler

            trace = profiler.device_profile(duration_s=0.5)["jax_trace"]
            _check("events" in trace,
                   f"jax.profiler trace session exports "
                   f"({len(trace.get('events', []))} events; "
                   f"{trace.get('error', 'no error')})")
    finally:
        ray_tpu.shutdown()
    losses = m["losses"]
    log(f"  losses: {[round(x, 4) for x in losses]}  "
        f"dense-attention reference on batch 0: {m['dense_loss']:.4f}")
    _check(len(losses) == steps and all(math.isfinite(x) for x in losses),
           f"{steps} steps, every loss finite")
    want = math.log(cfg.vocab_size)
    _check(abs(losses[0] - want) < LOSS_START_TOL,
           f"first loss {losses[0]:.3f} within {LOSS_START_TOL} of "
           f"ln(vocab) = {want:.3f}")
    _check(losses[-1] < losses[0] - LOSS_FALL_MIN,
           f"the loss fell by more than {LOSS_FALL_MIN} over the steps "
           f"({losses[0]:.4f} -> {losses[-1]:.4f})")
    _check(abs(losses[0] - m["dense_loss"]) < LOSS_TOL,
           f"flash loss equals the dense jnp reference within "
           f"{LOSS_TOL} (diff {abs(losses[0] - m['dense_loss']):.5f})")
    rel = m["attn_grad_rel_err"]
    _check(max(rel) < ATTN_GRAD_REL_TOL,
           f"d loss/d(wq, wk) through the flash backward equals the dense "
           f"jnp reference within {ATTN_GRAD_REL_TOL} relative L2 on "
           f"{GRAD_ROWS} rows (wq {rel[0]:.5f}, wk {rel[1]:.5f})")
    _check(m["platforms"] == [info["platform"]],
           f"every leaf of the train state lives on {m['platforms']}")
    if on_tpu:
        _check(m["kernel_calls"] > 0,
               f"compiled step contains the flash kernel "
               f"(tpu_custom_call x{m['kernel_calls']})")
    warm = m["step_ms"][1:]
    log(f"  SMOKE train: compile {m['compile_s']:.1f} s, step "
        f"{sum(warm) / len(warm):.1f} ms (batch {batch} x seq {cfg.max_seq}, "
        f"{len(warm)} steps after 1 warm-up, timed to a host read of the "
        f"loss) - smoke figures, not results")
    _finish(info, compile_s=m["compile_s"], step_ms=warm)


# ---------------------------------------------------------------------------
# Phase: train4 (four chips, --chips 4 only)
# ---------------------------------------------------------------------------

def phase_train4(args) -> None:
    info = _open_device(args.rehearse)
    on_tpu = info["platform"] == "tpu"
    _check(info["count"] >= 4, f"four devices visible ({info['count']})")
    import ray_tpu

    cfg = _train_cfg(args.rehearse)
    batch, steps = (8, 3) if args.rehearse else (24, 3)
    ray_tpu.init(num_cpus=2)
    try:
        # The one-chip step needs 15.2 of the chip's 15.75 GiB, so it runs
        # first, before anything else has lived on that chip.
        one = _fit(cfg, n_devices=1, batch=batch, steps=steps,
                   seed=args.seed, dense_reference=False,
                   name="chip_smoke_train4_ref")
        four = _fit(cfg, n_devices=4, batch=batch, steps=steps,
                    seed=args.seed, dense_reference=False,
                    name="chip_smoke_train4")
    finally:
        ray_tpu.shutdown()
    log(f"  4-chip losses: {[round(x, 4) for x in four['losses']]}")
    log(f"  1-chip losses: {[round(x, 4) for x in one['losses']]}")
    _check(all(math.isfinite(x) for x in four["losses"] + one["losses"]),
           "every loss finite")
    diffs = [abs(a - b) for a, b in zip(four["losses"], one["losses"])]
    _check(len(diffs) == steps and max(diffs) < LOSS_TOL,
           f"4-chip and 1-chip losses agree step by step within "
           f"{LOSS_TOL} (max diff {max(diffs):.5f})")
    _check(four["losses"][-1] < four["losses"][0] - LOSS_FALL_MIN,
           f"the 4-chip loss fell by more than {LOSS_FALL_MIN}: the "
           f"agreement above is between runs that both learn")
    rel = []
    for a, b in zip(four["attn_grad_norms"], one["attn_grad_norms"]):
        top = max(max(row) for row in b)
        rel.append(max(abs(x - y) for ra, rb in zip(a, b)
                       for x, y in zip(ra, rb)) / top)
    _check(max(rel) < MESH_GRAD_REL_TOL,
           f"per-(layer, head) norms of d loss/d(wq, wk) through the "
           f"shard_mapped flash backward agree between 4 chips and 1 "
           f"within {MESH_GRAD_REL_TOL} of the largest (wq {rel[0]:.5f}, "
           f"wk {rel[1]:.5f})")
    share = {int(k): v / four["state_bytes"]
             for k, v in four["bytes_per_device"].items()}
    log(f"  share of state bytes per device: "
        f"{ {k: round(v, 3) for k, v in sorted(share.items())} }")
    _check(len(share) == 4 and all(0.15 < v < 0.35 for v in share.values()),
           "the train state is spread over 4 devices, about a quarter each")
    _check(len(one["bytes_per_device"]) == 1,
           "the comparison run lives on one device")
    _check(four["collectives"] > 0,
           f"compiled 4-chip step contains collectives "
           f"(x{four['collectives']})")
    if on_tpu:
        _check(four["kernel_calls"] > 0 and one["kernel_calls"] > 0,
               f"both compiled steps contain the flash kernel "
               f"(tpu_custom_call x{four['kernel_calls']} / "
               f"x{one['kernel_calls']})")
    for name, m in (("4-chip", four), ("1-chip", one)):
        warm = m["step_ms"][1:]
        log(f"  SMOKE train {name}: compile {m['compile_s']:.1f} s, step "
            f"{sum(warm) / len(warm):.1f} ms (global batch {batch}) - smoke "
            f"figures, not results")
    _finish(info, compile_s=four["compile_s"], step_ms=four["step_ms"][1:])


# ---------------------------------------------------------------------------
# Phase: serve (one chip)
# ---------------------------------------------------------------------------

def _stream(url: str, payload: dict) -> dict:
    """One streaming HTTP request; returns tokens, the done frame and
    client-side time to first token."""
    import urllib.request

    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    t0 = time.perf_counter()
    tokens, done, ttft = [], None, None
    with urllib.request.urlopen(req, timeout=300) as r:
        for line in r:
            if not line.strip():
                continue
            frame = json.loads(line)
            if "token" in frame:
                if ttft is None:
                    ttft = time.perf_counter() - t0
                tokens.append(frame["token"])
            elif frame.get("done"):
                done = frame
    return {"tokens": tokens, "done": done, "ttft": ttft,
            "wall": time.perf_counter() - t0}


def _reference_check(params, cfg, prompt, got) -> tuple:
    """The plain reference: greedy decoding over gpt.forward (dense jnp
    attention, no cache), teacher-forced with the engine's own tokens so
    every position is compared. Returns (exact matches, tolerated flips,
    worst margin among mismatches)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import gpt

    pad = -(len(prompt) + len(got)) % 128 + len(prompt) + len(got)
    fwd = jax.jit(lambda p, t: gpt.forward(p, t, cfg))
    seq = list(prompt)
    exact = flips = 0
    worst = 0.0
    for tok in got:
        buf = np.zeros((1, pad), np.int32)
        buf[0, :len(seq)] = seq
        row = np.asarray(fwd(params, jnp.asarray(buf))[0, len(seq) - 1],
                         np.float32)
        if int(row.argmax()) == tok:
            exact += 1
        else:
            # A flip is tolerated only where bf16 cannot tell the
            # reference's best token from the engine's.
            margin = float(row.max() - row[tok])
            worst = max(worst, margin)
            flips += 1
        seq.append(tok)     # teacher-force: follow the engine's stream
    return exact, flips, worst


def _serve_size(rehearse: bool) -> tuple:
    """(cfg, pool, prompt length range, shared prefix length, tokens out)"""
    from ray_tpu.models import gpt

    if rehearse:
        return (gpt.TINY, dict(num_blocks=64, block_size=16, max_batch=8),
                (16, 48), 32, 16)
    # The pool rehearsal 3 proved compiles next to its ~1.9x
    # temporaries: 131,072 token slots, 4.5 GiB of KV.
    return (gpt.GPT2_SMALL,
            dict(num_blocks=8192, block_size=16, max_batch=32),
            (64, 512), 256, 64)


def phase_serve(args) -> None:
    info = _open_device(args.rehearse)
    on_tpu = info["platform"] == "tpu"
    import threading

    import jax
    import numpy as np

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.models import gpt
    from ray_tpu.serve.llm import build_app

    cfg, pool, lens, shared_len, n_out = _serve_size(args.rehearse)
    rng = np.random.default_rng(args.seed)
    shared = rng.integers(0, cfg.vocab_size, shared_len).tolist()
    prompts = []
    for i in range(8):
        n = int(rng.integers(lens[0], lens[1] + 1))
        body = rng.integers(0, cfg.vocab_size, n).tolist()
        # Requests 0-3 share a prefix; 4-7 do not.
        prompts.append((shared + body)[:max(n, shared_len + 8)]
                       if i < 4 else body)
    ref_idx = min(range(4, 8), key=lambda i: len(prompts[i]))

    ray_tpu.init(num_cpus=2)
    try:
        # -- deployment 1: serving defaults (prefix cache, chunked prefill)
        t0 = time.perf_counter()
        handle = serve.run(build_app(cfg, seed=args.seed, **pool),
                           name="llm")
        proxy = serve.start(http_port=0)
        url = f"http://127.0.0.1:{proxy.port}/"
        log(f"  deployed in {time.perf_counter() - t0:.1f} s at {url}")
        t0 = time.perf_counter()
        warm = _stream(url, {"prompt": prompts[ref_idx][:lens[0]],
                             "max_tokens": 4})
        cold_s = time.perf_counter() - t0
        _check(warm["done"] is not None, "warm-up request completed")

        results: dict = {}

        def client(i):
            results[i] = _stream(url, {"prompt": prompts[i],
                                       "max_tokens": n_out, "seed": i})

        # Request 0 (a sharer) goes first; once its prefill has
        # registered the shared prefix the other seven join it, so eight
        # streams are in flight together and the later sharers can hit.
        stats = handle.options(method_name="engine_stats")
        registered = stats.remote().result(timeout=60)["prefix"][
            "registrations"]
        t_all = time.perf_counter()
        threads = [threading.Thread(target=client, args=(i,), daemon=True)
                   for i in range(8)]
        threads[0].start()
        deadline = time.monotonic() + 300
        while time.monotonic() < deadline:
            st = stats.remote().result(timeout=60)
            if st["prefix"]["registrations"] > registered or 0 in results:
                break
            time.sleep(0.01)
        for t in threads[1:]:
            t.start()
        for t in threads:
            t.join(timeout=400)
        wall = time.perf_counter() - t_all
        _check(len(results) == 8 and all(
            r["done"] and r["done"]["num_tokens"] == n_out
            and len(r["tokens"]) == n_out for r in results.values()),
            f"8 concurrent streams each ended in a done frame with "
            f"{n_out} tokens")
        cached = [results[i]["done"]["cached_tokens"] for i in range(8)]
        log(f"  prompt lengths {[len(p) for p in prompts]}, "
            f"cached_tokens {cached}")
        _check(all(c > 0 for c in cached[1:4]),
               "the later prefix-sharing requests were served from cache "
               "(cached_tokens > 0)")
        st = stats.remote().result(timeout=60)
        log(f"  engine: platform {st['platform']}, device_kind "
            f"{st['device_kind']!r}, paged kernel {st['paged_kernel']}, "
            f"{st['steps']} steps, prefill chunks {st['prefill_chunks']}, "
            f"kv_util_peak {st['kv_util_peak']:.3f}")
        last = st.get("last_step", {})
        log(f"  engine's last step: "
            + ", ".join(f"{k} {last[k]:.2f}" if isinstance(last[k], float)
                        else f"{k} {last[k]}" for k in sorted(last)))
        _check(st["platform"] == info["platform"],
               f"engine_stats reports platform {st['platform']}")
        if on_tpu:
            _check(st["paged_kernel"] == "compiled",
                   "the paged kernel is compiled, not interpreted")
        params = gpt.init(jax.random.PRNGKey(args.seed), cfg)
        exact, flips, worst = _reference_check(
            params, cfg, prompts[ref_idx], results[ref_idx]["tokens"])
        _check(worst < LOGIT_MARGIN_EPS,
               f"request {ref_idx} ({len(prompts[ref_idx])}-token prompt) "
               f"matches the plain greedy reference over gpt.forward: "
               f"{exact}/{n_out} tokens equal, {flips} flips, all where the "
               f"reference's margin is under {LOGIT_MARGIN_EPS} "
               f"(worst {worst:.4f})")
        ttfts = sorted(r["ttft"] for r in results.values())
        log(f"  SMOKE serve: first request incl. compiles {cold_s:.1f} s; "
            f"time to first token median {ttfts[4] * 1e3:.0f} ms, max "
            f"{ttfts[-1] * 1e3:.0f} ms; {8 * n_out / wall:.1f} tokens/s over "
            f"8 streams - smoke figures, not results")

        # -- deployment 2, after the first is shut down: n-gram
        # speculation, so the q_len > 1 verify kernel runs.
        serve.shutdown()
        import gc

        gc.collect()
        live = sum(x.nbytes for x in jax.live_arrays())
        log(f"  first deployment shut down; {live / 2**20:.0f} MiB of "
            f"arrays still live")
        handle = serve.run(build_app(
            cfg, seed=args.seed, speculative={"mode": "ngram", "k": 4},
            **pool), name="llm-spec")
        proxy = serve.start(http_port=0)
        url = f"http://127.0.0.1:{proxy.port}/"
        pattern = rng.integers(0, cfg.vocab_size, 8).tolist()
        spec_results: dict = {}

        def spec_client(i):
            # A repeating prompt gives the n-gram proposer matches.
            spec_results[i] = _stream(
                url, {"prompt": (pattern * 16)[:lens[0] + 8 * i],
                      "max_tokens": n_out, "seed": i})

        threads = [threading.Thread(target=spec_client, args=(i,),
                                    daemon=True) for i in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=400)
        _check(len(spec_results) == 2 and all(
            r["done"] and r["done"]["num_tokens"] == n_out
            for r in spec_results.values()),
            f"2 speculative streams each ended in a done frame with "
            f"{n_out} tokens")
        st = handle.options(method_name="engine_stats").remote().result(
            timeout=60)
        log(f"  speculative engine: paged kernel {st['paged_kernel']}, "
            f"spec {st['spec']}")
        _check(st["spec"]["verify_steps"] > 0 and st["platform"]
               == info["platform"],
               f"the q_len 5 verify kernel ran on {st['platform']} "
               f"({st['spec']['verify_steps']} verify steps)")
        serve.shutdown()
    finally:
        ray_tpu.shutdown()
    # -- Laguna-XS.2: logits through both pools against the reference
    import gc

    gc.collect()
    worst, typical = _laguna_logits(args.rehearse, args.seed)
    _check(worst < LAGUNA_LOGIT_TOL,
           f"laguna: served logits (chunked prefill, then decode through "
           f"the full and the window pool) equal the plain float32 "
           f"reference's within {LAGUNA_LOGIT_TOL} (largest difference "
           f"{worst:.5f}, the median row's {typical:.5f})")
    # -- Kimi-K2.5's share: logits through the latent pool, both paths
    gc.collect()
    for a in jax.live_arrays():
        a.delete()
    k_worst, k_typical = _kimi_logits(args.rehearse, args.seed)
    _check(k_worst < KIMI_LOGIT_TOL,
           f"kimi: served logits (a prefix hit, a chunked body, then decode "
           f"through the latent pool) equal the plain float32 non-absorbed "
           f"reference's within {KIMI_LOGIT_TOL} (largest difference "
           f"{k_worst:.5f}, the median row's {k_typical:.5f})")
    _finish(info, ttft_s=ttfts, tokens_per_s=8 * n_out / wall,
            laguna_logit_diff=worst, kimi_logit_diff=k_worst)


def _record_logits(eng) -> dict:
    """Record every logits row ``eng`` decides a token from, {rid:
    [row, ...]}: the row its last prefill chunk's program hands back,
    then one a decode step."""
    import jax
    import numpy as np

    rows, last = {}, []
    settle, fetch, chunk = (eng._settle, eng._fetch_decisions,
                            eng._prefill_chunk)

    def on_chunk(*args):
        out = chunk(*args)
        last.append(out[0])     # the row the chunk program hands back
        return out

    def on_settle():
        # The chunks not yet settled are the last ones dispatched.
        for ch, row in zip(eng._pending, last[-len(eng._pending):]):
            if ch.done and ch.req is not None:
                rows.setdefault(ch.req.rid, []).append(
                    np.asarray(jax.device_get(row), np.float32))
        last.clear()
        return settle()

    def on_fetch(logits, ids, all_greedy):
        got = np.asarray(jax.device_get(logits), np.float32)
        for r in eng._active:
            if r.state == "RUNNING":    # holds a lane of the kept array
                rows.setdefault(r.rid, []).append(got[r.lane, 0])
        return fetch(logits, ids, all_greedy)

    eng._settle, eng._fetch_decisions = on_settle, on_fetch
    eng._prefill_chunk = on_chunk
    return rows


def _logit_diffs(reqs, rows, forward, what: str) -> tuple:
    """(largest, median) over rows of |served logits - reference's|."""
    import numpy as np

    by_row = []
    for r in reqs:
        want = np.asarray(forward(r.prompt + r.output))[
            len(r.prompt) - 1:len(r.prompt) - 1 + len(r.output)]
        got = np.stack(rows[r.rid])
        _check(got.shape == want.shape, f"{what}: {got.shape[0]} logits "
               f"rows compared for a {len(r.prompt)}-token prompt")
        by_row += np.abs(got - want).max(axis=1).tolist()
    return max(by_row), float(np.median(by_row))


def _laguna_logits(rehearse: bool, seed: int) -> tuple:
    """Laguna-XS.2 at the benchmark cell's cut (published widths, layer
    0 and one period; tiny when rehearsing) through LLMEngine alone: a
    prompt prefilled in chunks across window boundaries, then decode
    steps through both pools, beside a second lane. Every logits row
    the engine decides a token from is compared with the plain
    reference's full forward pass (models/laguna_ref.py). Returns the
    largest absolute difference and the median row's."""
    import jax
    import numpy as np

    from ray_tpu.llm.engine import LLMEngine
    from ray_tpu.models import laguna, laguna_ref

    kinds = (laguna.FULL,) + (laguna.SLIDING,) * 3 + (laguna.FULL,)
    mlps = (laguna.DENSE,) + (laguna.SPARSE,) * 4
    if rehearse:
        cfg = laguna.LagunaConfig(
            vocab_size=512, hidden_size=64, intermediate_size=128,
            num_hidden_layers=5, num_key_value_heads=2, head_dim=16,
            num_attention_heads_per_layer=(4, 8, 8, 8, 4), num_experts=8,
            num_experts_per_tok=2, moe_intermediate_size=32,
            shared_expert_intermediate_size=32, sliding_window=32,
            layer_types=kinds, mlp_layer_types=mlps, max_seq=256,
            dtype="float32")
        pool, n_prompt, n_out = dict(num_blocks=64, block_size=8,
                                     prefill_chunk_tokens=32), 90, 6
    else:
        cfg = laguna.LagunaConfig(
            num_hidden_layers=5, layer_types=kinds, mlp_layer_types=mlps,
            num_attention_heads_per_layer=(48, 64, 64, 64, 48),
            max_seq=2048)
        pool, n_prompt, n_out = dict(num_blocks=1024, block_size=16,
                                     prefill_chunk_tokens=512), 1300, 12
    params = laguna.init(jax.random.PRNGKey(seed), cfg)
    eng = LLMEngine(params, cfg, max_batch=8, **pool)
    rows = _record_logits(eng)
    rng = np.random.default_rng(seed + 7)
    reqs = [eng.add_request(rng.integers(0, cfg.vocab_size, n).tolist(),
                            max_tokens=n_out)
            for n in (n_prompt, n_prompt // 3)]
    while eng.step():
        pass
    st = eng.stats()
    log(f"  laguna: paged kernel {st['paged_kernel']}, chunk attention "
        f"{st['chunk_attention']}, window kind peak "
        f"{st['kv_window_util_peak']:.3f}, "
        f"{st['kv_window_blocks_slid']} blocks slid out")
    # The router alone, on identical inputs: the served route() against
    # plain float32 softmax + top-k. Tokens cannot show this (a top-k
    # router turns rounding into swapped experts whatever computes the
    # scores; benchmark/reference_laguna.py), the router itself can.
    from ray_tpu.ops import moe

    import jax.numpy as jnp

    routed = next(p for p in params["layers"] if "router" in p)
    h = jax.random.normal(jax.random.PRNGKey(seed + 1),
                          (2048, cfg.hidden_size)).astype(cfg.dtype)
    k = cfg.num_experts_per_tok
    _, served, _ = moe.route(h, routed["router"], k)
    with jax.default_matmul_precision("highest"):
        scores = h.astype(jnp.float32) @ routed["router"].astype(jnp.float32)
    plain = jax.lax.top_k(jax.nn.softmax(scores, -1), k)[1]
    low = jax.lax.top_k(jax.nn.softmax(
        (h @ routed["router"]).astype(jnp.bfloat16), -1), k)[1]
    same = lambda a, b: float((jnp.sort(a, -1) == jnp.sort(b, -1))
                              .all(-1).mean())
    _check(same(served, plain) >= 0.999,
           f"laguna: route() picks the float32 reference's experts on "
           f"{same(served, plain):.4f} of 2,048 tokens (at least 0.999; "
           f"scores in bfloat16 agree on {same(low, plain):.4f})")
    del eng
    return _logit_diffs(
        reqs, rows, lambda seq: laguna_ref.forward(params, seq, cfg),
        "laguna")


def _kimi_logits(rehearse: bool, seed: int) -> tuple:
    """Kimi-K2.5's served share at the benchmark cell's cut (published
    widths, layer 0 and four routed layers, 12 of 384 experts, a slice
    of the vocabulary; tiny when rehearsing) through LLMEngine alone: a
    prefix sent alone, then the prefix with a body prefilled in chunks
    against the cached latent rows (the up-projecting form), then
    decode steps through the latent pool (the absorbed kernel), beside
    a second lane. Every logits row the engine decides a token from is
    compared with the plain reference's NON-absorbed full forward pass
    (models/kimi_k2_ref.py), given the same share. Returns the largest
    absolute difference and the median row's."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.llm.engine import LLMEngine
    from ray_tpu.models import kimi_k2, kimi_k2_ref
    from ray_tpu.ops import moe

    if rehearse:
        cfg = kimi_k2.KimiK2Config(
            vocab_size=512, hidden_size=64, intermediate_size=128,
            moe_intermediate_size=32, num_hidden_layers=3,
            num_attention_heads=4, q_lora_rank=48, kv_lora_rank=32,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            n_routed_experts=16, num_experts_per_tok=2, experts_held=4,
            max_seq=256, dtype="float32")
        pool, n_prefix, n_body, n_out = dict(
            num_blocks=64, block_size=8, prefill_chunk_tokens=32), 64, 40, 6
    else:
        cfg = kimi_k2.KimiK2Config(num_hidden_layers=5, vocab_size=20480,
                                   experts_held=12, max_seq=2048)
        pool, n_prefix, n_body, n_out = dict(
            num_blocks=1024, block_size=16,
            prefill_chunk_tokens=512), 1024, 384, 12
    params = kimi_k2.init(jax.random.PRNGKey(seed), cfg)
    eng = LLMEngine(params, cfg, max_batch=8, **pool)
    rng = np.random.default_rng(seed + 11)
    draw = lambda n: rng.integers(0, cfg.vocab_size, n).tolist()
    prefix = draw(n_prefix)
    eng.add_request(prefix, max_tokens=1)
    while eng.step():
        pass
    rows = _record_logits(eng)
    reqs = [eng.add_request(prefix + draw(n_body), max_tokens=n_out),
            eng.add_request(draw(n_body), max_tokens=n_out)]
    while eng.step():
        pass
    st = eng.stats()
    _check(reqs[0].cached_tokens == n_prefix,
           f"kimi: the {n_prefix}-token prefix was a cache hit")
    log(f"  kimi: paged kernel {st['paged_kernel']}, chunk attention "
        f"{st['chunk_attention']}, latent pool peak "
        f"{st['kv_util_peak']:.3f}")
    # The router alone, on identical inputs: the served route_sigmoid()
    # against plain float32 sigmoid + bias + top-k.
    routed = next(p for p in params["layers"] if "router" in p)
    h = jax.random.normal(jax.random.PRNGKey(seed + 1),
                          (2048, cfg.hidden_size)).astype(cfg.dtype)
    k, b = cfg.num_experts_per_tok, routed["router_bias"]
    _, served, _ = moe.route_sigmoid(h, routed["router"], b, k)
    with jax.default_matmul_precision("highest"):
        scores = h.astype(jnp.float32) @ routed["router"].astype(jnp.float32)
    plain = jax.lax.top_k(jax.nn.sigmoid(scores) + b, k)[1]
    low = jax.lax.top_k(jax.nn.sigmoid(
        (h @ routed["router"]).astype(jnp.bfloat16)) + b.astype(
            jnp.bfloat16), k)[1]
    same = lambda a, b: float((jnp.sort(a, -1) == jnp.sort(b, -1))
                              .all(-1).mean())
    _check(same(served, plain) >= 0.999,
           f"kimi: route_sigmoid() picks the float32 reference's experts "
           f"on {same(served, plain):.4f} of 2,048 tokens (at least 0.999; "
           f"scores in bfloat16 agree on {same(low, plain):.4f})")
    del eng
    # The request behind the prefix alone: its rows passed all three
    # paths, and a second float32 pass costs the phase ~85 s on the chip.
    return _logit_diffs(
        reqs[:1], rows, lambda seq: kimi_k2_ref.forward(params, seq, cfg),
        "kimi")


# ---------------------------------------------------------------------------
# Phase: detached (one chip, owned by the cluster's node daemon)
# ---------------------------------------------------------------------------

def phase_detached(args) -> None:
    """The README recipe from a driver attached to `rtpu start --head`.
    The node daemon hosts the device lane there, so it is the process
    that opens the chip; this child, the head and every CLI stay off
    it. The attached driver has no device lane: the replica has to be
    placed on the daemon's."""
    import numpy as np

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.llm import build_app
    from ray_tpu.util import state

    cfg, pool, lens, _, n_out = _serve_size(args.rehearse)
    temp = os.environ["CHIP_SMOKE_CLUSTER_DIR"]
    cli = [sys.executable, "-m", "ray_tpu.scripts.cli", "--temp-dir", temp]
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [HERE] + os.environ.get("PYTHONPATH", "").split(os.pathsep)))
    subprocess.run(cli + ["start", "--head", "--num-cpus", "2"], env=env,
                   check=True, timeout=120)
    os.environ["RT_TOKEN_FILE"] = os.path.join(temp, "session_token")
    with open(os.path.join(temp, "head_address")) as f:
        addr = f.read().strip()
    prompt = np.random.default_rng(args.seed).integers(
        0, cfg.vocab_size, lens[0] + 16).tolist()
    ray_tpu.init(address=addr)
    try:
        t0 = time.perf_counter()
        handle = serve.run(build_app(cfg, seed=args.seed, **pool),
                           name="llm")
        proxy = serve.start(http_port=0)
        url = f"http://127.0.0.1:{proxy.port}/"
        stats = handle.options(method_name="engine_stats")
        st = stats.remote().result(timeout=200)
        log(f"  deployed in {time.perf_counter() - t0:.1f} s at {url} "
            f"(daemon start-up and engine build included)")
        first = _stream(url, {"prompt": prompt, "max_tokens": n_out})
        again = _stream(url, {"prompt": prompt, "max_tokens": n_out})
        nodes = state.list_nodes()
        replica = next(a for a in state.list_actors()
                       if str(a["name"]).startswith("SERVE:LLMServer"))
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
        subprocess.run(cli + ["stop"], env=env, timeout=60)
    daemon = next(n for n in nodes if not n["is_driver"])
    driver = next(n for n in nodes if n["is_driver"])
    log(f"  node daemon resources {daemon['resources']}; attached driver "
        f"{driver['resources']}")
    info = {"platform": st["platform"], "kind": st["device_kind"],
            "count": int(daemon["resources"]["TPU"])}
    log(f"  engine: platform {st['platform']}, device_kind "
        f"{st['device_kind']!r}, paged kernel {st['paged_kernel']}")
    if not args.rehearse:
        _check(info["platform"] == "tpu" and info["count"] >= 1
               and st["paged_kernel"] == "compiled",
               "the daemon counted its chip, the engine's pools live on "
               "it and its decode program carries the compiled kernel")
    _check(replica["is_device"] and replica["node_id"] == daemon["node_id"]
           and driver["resources"]["device"] == 0,
           "the replica lives on the node daemon's device lane; the "
           "attached driver hosts none")
    _check(all(r["done"] and r["done"]["num_tokens"] == n_out
               and len(r["tokens"]) == n_out
               and all(0 <= t < cfg.vocab_size for t in r["tokens"])
               for r in (first, again)),
           f"2 streams through the driver's proxy each ended in a done "
           f"frame with {n_out} tokens")
    _check(again["done"]["cached_tokens"] > 0,
           f"the repeated prompt was served from the daemon's prefix cache "
           f"(cached_tokens {again['done']['cached_tokens']})")
    log(f"  SMOKE detached: first stream incl. compiles "
        f"{first['wall']:.1f} s, second {again['wall']:.1f} s - smoke "
        f"figures, not results")
    _finish(info, first_s=first["wall"], second_s=again["wall"])


PHASES = {"train": phase_train, "serve": phase_serve,
          "detached": phase_detached, "train4": phase_train4}


# ---------------------------------------------------------------------------
# Parent: never imports jax
# ---------------------------------------------------------------------------

def _kill_group(pgid: int, overran: str | None) -> None:
    if overran:
        log(f"chip_smoke: phase {overran} overran "
            f"{PHASE_TIMEOUT_S[overran]} s; killing it")
    try:
        os.killpg(pgid, signal.SIGKILL)
    except ProcessLookupError:
        pass


def _stop_cluster(cluster_dir: str) -> None:
    """Kill whatever `rtpu start` recorded and `rtpu stop` did not reach."""
    try:
        with open(os.path.join(cluster_dir, "pids")) as f:
            pids = [int(line) for line in f if line.strip()]
    except FileNotFoundError:
        pids = []
    for pid in pids:
        _kill_group(pid, None)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="TINY size on any backend; exits 3, no result")
    ap.add_argument("--phase", choices=sorted(PHASES),
                    help="(internal) run one phase in this process")
    args = ap.parse_args()
    if args.phase:
        PHASES[args.phase](args)
        return 0

    if "jax" in sys.modules:    # a parent that touched jax holds the chip
        raise SystemExit("chip_smoke: the parent imported jax")
    # The detached phase's daemons run in sessions of their own; their
    # pids are recorded here so they are stopped whatever the phase does.
    cluster_dir = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        return _run_phases(args, cluster_dir)
    finally:
        shutil.rmtree(cluster_dir, ignore_errors=True)


def _run_phases(args, cluster_dir: str) -> int:
    phases = (["train4"] if args.chips == 4
              else ["train", "serve", "detached"])
    device = None
    for name in phases:
        log(f"== phase {name} ==")
        t0 = time.perf_counter()
        cmd = [sys.executable, "-u", os.path.abspath(__file__), "--phase",
               name, "--seed", str(args.seed)]
        if args.rehearse:
            cmd.append("--rehearse")
        # Own session, so a phase that overruns is killed with every
        # process it started (workers, proxies).
        proc = subprocess.Popen(
            cmd, stdout=subprocess.PIPE, text=True, cwd=HERE,
            start_new_session=True,
            env=dict(os.environ, CHIP_SMOKE_CLUSTER_DIR=cluster_dir))
        killer = threading.Timer(PHASE_TIMEOUT_S[name], _kill_group,
                                 args=(proc.pid, name))
        killer.start()
        result = None
        for line in proc.stdout:
            if line.startswith(RESULT_TAG):
                result = json.loads(line[len(RESULT_TAG):])
            else:
                sys.stdout.write(line)
                sys.stdout.flush()
        rc = proc.wait()
        killer.cancel()
        _kill_group(proc.pid, None)     # leave no straggler behind
        _stop_cluster(cluster_dir)
        log(f"== phase {name}: rc={rc}, {time.perf_counter() - t0:.0f} s ==")
        if rc != 0 or result is None:
            log(f"chip_smoke: phase {name} failed (rc={rc}); no result")
            return rc or 1
        if result["device"]["platform"] != "tpu" and not args.rehearse:
            log(f"chip_smoke: phase {name} ran on "
                f"{result['device']['platform']}, not on a TPU; no result")
            return 1
        if args.chips == 4 and result["device"]["count"] != 4:
            log(f"chip_smoke: --chips 4 saw {result['device']['count']} "
                f"devices; no result")
            return 1
        device = device or result["device"]     # as jax reported it
    if args.rehearse:
        log("chip_smoke: rehearsal finished - control flow only, not a "
            "chip run; exiting 3 without a result")
        return 3
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
