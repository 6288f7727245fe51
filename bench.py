"""GPT-2-small training throughput on the local chip(s), one process per
measurement.

    python bench.py            three measurements, each in its own child:
                               dense base, flash, flash through JaxTrainer
    python bench.py --child [--flash | --framework]   one measurement

A chip belongs to one process at a time, so the parent never imports jax
and the children run one after the other. Every result names the device
it ran on, and a measurement that finds no TPU fails: there is no CPU
stand-in, no retry and no supervision. Results go to stdout as JSON
lines, context (step time, config) to stderr. The other flag modes
(--serve-llm, --data-llm, --data-shuffle, --jobs) are host-side runs at
TINY size on whatever platform the environment selects; their rows name
it.

This is not the benchmark ROADMAP A1 asks for; it is what is left of the
old one until that lands.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

A100_GPT2S_TOKENS_PER_SEC = 55_000.0  # reference-stack per-accelerator ballpark


def _device_info() -> dict:
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def _require_tpu() -> dict:
    """The chip-owning child's first call: fail without a TPU, turn the
    shared compile cache on with one."""
    info = _device_info()
    if info["platform"] != "tpu":
        raise SystemExit(
            f"bench.py measures on a TPU; jax found {info} "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r})")
    from ray_tpu._private.backend_probe import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", file=sys.stderr)
    return info


# --------------------------------------------------------------------------
# One measurement (runs in its own child process).
# --------------------------------------------------------------------------

def run_bench(use_flash: bool) -> dict:
    import jax
    import optax

    from ray_tpu.models import gpt
    from ray_tpu.parallel import MeshSpec

    devs = jax.devices()
    n_chips = len(devs)
    on_tpu = devs[0].platform == "tpu"
    print(f"devices: {devs}", file=sys.stderr)

    spec = MeshSpec.auto(n_chips)
    mesh = spec.build()
    data_shards = spec.dp * spec.fsdp

    from jax.sharding import NamedSharding, PartitionSpec as P

    import dataclasses

    if on_tpu:
        # Tuned at r3: remat with the dots_flash policy (save matmul
        # outputs + flash kernel outputs), batch 24/shard, fused single-
        # pass flash backward, bf16 Adam first moment. Sweep provenance:
        # 41.5% (r2) -> 44.6% MFU.
        cfg = dataclasses.replace(gpt.GPT2_SMALL, remat=True,
                                  use_flash=use_flash)
        # The flash config fits 24/shard (O(seq) attention memory); the
        # dense-attention base config only fits 16.
        batch = (24 if use_flash else 16) * data_shards
        warmup, iters = 3, 20
    else:  # CPU correctness run of the same code path (tests)
        cfg = gpt.TINY
        batch = 4 * data_shards
        warmup, iters = 1, 3

    import jax.numpy as jnp

    opt = optax.adamw(3e-4, b1=0.9, b2=0.95, weight_decay=0.1,
                      mu_dtype=jnp.bfloat16)
    params = gpt.init(jax.random.key(0), cfg)
    state = {"params": params, "opt_state": opt.init(params), "step": 0}
    state = gpt.shard_state(state, mesh, cfg)
    step = gpt.make_train_step(cfg, opt, mesh)
    seq = cfg.max_seq
    tokens = jax.device_put(
        jax.random.randint(jax.random.key(1), (batch, seq), 0,
                           cfg.vocab_size),
        NamedSharding(mesh, P(("dp", "fsdp"))),
    )
    t_compile = time.perf_counter()
    for _ in range(warmup):
        state, metrics = step(state, tokens)
    # Fence via a host read: the final loss depends on every prior step.
    float(metrics["loss"])
    print(f"warmup+compile: {time.perf_counter() - t_compile:.1f}s",
          file=sys.stderr)
    t0 = time.perf_counter()
    for _ in range(iters):
        state, metrics = step(state, tokens)
    final_loss = float(metrics["loss"])
    dt = time.perf_counter() - t0
    tokens_per_sec = iters / dt * batch * (seq - 1)
    per_chip = tokens_per_sec / n_chips
    # Shared cost model (util/perfmodel.py): the same peak table and
    # 6N-rule FLOPs the live llm_mfu/train_mfu telemetry series price
    # against. None on the CPU backend: no peak, so no MFU.
    from ray_tpu.util import perfmodel

    hw = perfmodel.detect_hardware()
    print(
        f"cfg: {cfg.num_params()/1e6:.0f}M params flash={cfg.use_flash} "
        f"batch={batch} seq={seq} mesh={spec.shape} "
        f"step={dt/iters*1000:.0f}ms loss={final_loss:.3f}",
        file=sys.stderr)
    if hw is None:
        return _cpu_row("gpt_tiny_train", per_chip)
    mfu = (tokens_per_sec * perfmodel.train_flops_per_token(cfg)
           / (n_chips * hw.flops_per_s))
    return {
        "metric": "gpt2_small_train_tokens_per_sec_per_chip",
        "value": round(per_chip, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(per_chip / A100_GPT2S_TOKENS_PER_SEC, 3),
        "mfu": round(mfu, 4),
        "flash": use_flash,
        "device": _device_info(),
        "per_op_ms": profile_ops(cfg, mesh, batch, step, state, tokens,
                                 dt / iters * 1000.0, opt),
    }


def _cpu_row(name: str, tokens_per_sec: float) -> dict:
    """What a CPU run of a measurement function returns (tests drive
    them at TINY size): a count under a name and a unit no device metric
    uses. main() never prints one — its children require a TPU."""
    return {"metric": f"{name}_cpu_correctness_run",
            "value": round(tokens_per_sec, 1),
            "unit": "tokens/s on the CPU backend (not a device metric)",
            "device": _device_info()}


def profile_ops(cfg, mesh, batch, step, state, tokens,
                step_ms_ref: float, opt=None) -> dict:
    """Per-component wall times at the EXACT bench shapes: attention
    stack vs MLP stack vs embedding/unembed vs optimizer, each timed as
    its own jitted program. Differences from whole-step time reflect
    XLA's cross-op fusion/overlap, so the table brackets (not exactly
    partitions) the step. Emitted into the bench JSON as provenance for
    the MFU ceiling analysis (MFU_ANALYSIS.md)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import gpt

    def timeit(fn, *args, iters=8):
        out = fn(*args)
        jax.tree_util.tree_map(
            lambda x: x.block_until_ready()
            if hasattr(x, "block_until_ready") else x, out)
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        jax.tree_util.tree_map(
            lambda x: x.block_until_ready()
            if hasattr(x, "block_until_ready") else x, out)
        return (time.perf_counter() - t0) / iters * 1000.0

    table = {}
    # Full loss forward / forward+backward on the real sharded state.
    params = state["params"]
    fwd = jax.jit(lambda p, t: gpt.loss_fn(p, t, cfg, mesh))
    table["loss_forward"] = timeit(fwd, params, tokens)
    grad = jax.jit(jax.grad(lambda p, t: gpt.loss_fn(p, t, cfg, mesh)))
    table["loss_fwd_bwd"] = timeit(grad, params, tokens)
    if opt is not None:
        # Measure the optimizer update DIRECTLY, blocked on dispatch.
        # The old derivation (step_ms_ref - loss_fwd_bwd) underflowed
        # to 0.0: step_ms_ref amortizes async dispatch across the step
        # loop while the standalone loss_fwd_bwd timing above is fully
        # blocked, so the subtrahend routinely exceeded the minuend.
        import optax

        grads = grad(params, tokens)

        def opt_step(p, o, g):
            updates, o2 = opt.update(g, o, p)
            return optax.apply_updates(p, updates), o2

        table["optimizer_and_rest"] = timeit(
            jax.jit(opt_step), params, state["opt_state"], grads)
    else:
        table["optimizer_and_rest"] = max(0.0, step_ms_ref
                                          - table["loss_fwd_bwd"])

    # Attention-only and MLP-only stacks at PER-SHARD layer shapes (per
    # layer x n_layer) on one device: a data shard's slice of the step,
    # comparable to whole_step regardless of mesh size (the bench box
    # has one real chip, where per-shard == global).
    n_shards = max(1, mesh.devices.size // max(
        1, int(np.prod([mesh.shape.get(a, 1) for a in ("sp", "tp", "pp")]))
    )) if hasattr(mesh, "shape") else 1
    B = max(1, tokens.shape[0] // n_shards)
    S, D, H = cfg.max_seq, cfg.d_model, cfg.n_head
    hd = D // H
    k1, k2 = jax.random.split(jax.random.key(2))
    q = jax.random.normal(k1, (B, H, S, hd), jnp.bfloat16)
    x = jax.random.normal(k2, (B, S, D), jnp.bfloat16)

    if cfg.use_flash:
        from ray_tpu.ops.flash_attention import flash_attention

        att = jax.jit(lambda q: flash_attention(
            q, q, q, causal=True, block_size=cfg.flash_block,
            layout="bhsd"))
    else:
        def dense_att(q):
            w = jnp.einsum("bhsd,bhtd->bhst", q, q) / (hd ** 0.5)
            mask = jnp.tril(jnp.ones((S, S), bool))
            w = jnp.where(mask, w, -1e9)
            return jnp.einsum("bhst,bhtd->bhsd",
                              jax.nn.softmax(w, axis=-1), q)

        att = jax.jit(dense_att)
    table["attention_fwd_per_layer"] = timeit(att, q)
    att_grad = jax.jit(jax.grad(lambda q: att(q).astype(jnp.float32).sum()))
    table["attention_fwd_bwd_per_layer"] = timeit(att_grad, q)
    table["attention_fwd_bwd_all_layers"] = (
        table["attention_fwd_bwd_per_layer"] * cfg.n_layer)

    w1 = jax.random.normal(k1, (D, 4 * D), jnp.bfloat16)
    w2 = jax.random.normal(k2, (4 * D, D), jnp.bfloat16)
    mlp = jax.jit(lambda x, w1, w2: jax.nn.gelu(x @ w1) @ w2)
    table["mlp_fwd_per_layer"] = timeit(mlp, x, w1, w2)
    mlp_grad = jax.jit(jax.grad(
        lambda x, w1, w2: (jax.nn.gelu(x @ w1) @ w2)
        .astype(jnp.float32).sum()))
    table["mlp_fwd_bwd_per_layer"] = timeit(mlp_grad, x, w1, w2)
    table["mlp_fwd_bwd_all_layers"] = (
        table["mlp_fwd_bwd_per_layer"] * cfg.n_layer)

    # Unembedding projection (the single biggest matmul: D x vocab).
    wv = jax.random.normal(k1, (D, cfg.vocab_size), jnp.bfloat16)
    unemb = jax.jit(lambda x, wv: x @ wv)
    table["unembed_matmul"] = timeit(unemb, x, wv)

    table = {k: round(v, 2) for k, v in table.items()}
    table["whole_step_ms"] = round(step_ms_ref, 2)
    # Roofline verdict at the measured whole-step time, priced by the
    # shared cost model — the same numbers the continuous train_mfu /
    # train_hbm_util series report, so the offline table and the live
    # plane agree by construction.
    from ray_tpu.util import perfmodel

    rl = perfmodel.roofline(
        perfmodel.train_step_cost(cfg, tokens.shape[0], cfg.max_seq),
        step_ms_ref / 1e3, hw=perfmodel.detect_hardware())
    if rl:  # {} on the CPU backend: no peak, no utilization
        table["model_mfu_at_whole_step"] = round(rl["mfu"], 4)
        table["model_hbm_util_at_whole_step"] = round(rl["hbm_util"], 4)
        table["roofline_verdict"] = rl["verdict"]
    print(f"per-op table (ms): {json.dumps(table)}", file=sys.stderr)
    return table


def run_bench_framework() -> dict:
    """End-to-end THROUGH the framework: JaxTrainer.fit drives the same
    tuned GPT-2 step on the device lane with a ray_tpu.data ingest
    pipeline (iter_batches -> device_put per step), tokens/s measured
    inside the worker across the post-warmup steps and delivered via the
    report loop. The gap to run_bench() IS the framework overhead
    (BASELINE.md north star: 'Ray Train tokens/sec', reference
    data_config.py:112 streaming-split ingest)."""
    import dataclasses

    import jax
    import numpy as np

    import ray_tpu
    from ray_tpu import data as rt_data
    from ray_tpu.models import gpt
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    from ray_tpu.parallel import MeshSpec

    devs = jax.devices()
    on_tpu = devs[0].platform == "tpu"
    spec = MeshSpec.auto(len(devs))
    data_shards = spec.dp * spec.fsdp
    if on_tpu:
        cfg = dataclasses.replace(gpt.GPT2_SMALL, remat=True, use_flash=True)
        batch, warmup, iters = 24 * data_shards, 3, 20
    else:
        cfg = gpt.TINY
        batch, warmup, iters = 4 * data_shards, 1, 3
    seq = cfg.max_seq

    rng = np.random.default_rng(0)
    rows = [{"tokens": rng.integers(0, cfg.vocab_size, seq,
                                    dtype=np.int32)}
            for _ in range(batch * 4)]
    ds = rt_data.from_items(rows)

    def loop(config):
        import time as _t

        import jax
        import jax.numpy as jnp
        import numpy as np
        import optax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ray_tpu import train as rt_train
        from ray_tpu.models import gpt
        from ray_tpu.parallel import MeshSpec

        cfg = config["cfg"]
        mesh = MeshSpec.auto(len(jax.devices())).build()
        opt = optax.adamw(3e-4, b1=0.9, b2=0.95, weight_decay=0.1,
                          mu_dtype=jnp.bfloat16)
        params = gpt.init(jax.random.key(0), cfg)
        state = {"params": params, "opt_state": opt.init(params), "step": 0}
        state = gpt.shard_state(state, mesh, cfg)
        step_fn = gpt.make_train_step(cfg, opt, mesh)
        sharding = NamedSharding(mesh, P(("dp", "fsdp")))
        shard = rt_train.get_dataset_shard("train")

        steps, t0, metrics = 0, None, {}
        while steps < config["total"]:
            for b in shard.iter_batches(batch_size=config["batch"],
                                        batch_format="jax",
                                        sharding=sharding, drop_last=True):
                state, metrics = step_fn(state, b["tokens"])
                steps += 1
                if steps == config["warmup"]:
                    float(metrics["loss"])  # fence compile+warmup
                    t0 = _t.perf_counter()
                if steps >= config["total"]:
                    break
        loss = float(metrics["loss"])  # fence the measured window
        rt_train.report({
            "loss": loss,
            "measured_s": _t.perf_counter() - t0,
            "measured_steps": config["total"] - config["warmup"],
        })

    ray_tpu.init(num_cpus=1)
    try:
        trainer = JaxTrainer(
            loop,
            train_loop_config={"cfg": cfg, "batch": batch,
                               "warmup": warmup, "total": warmup + iters},
            scaling_config=ScalingConfig(num_workers=1, use_tpu=on_tpu),
            run_config=RunConfig(name="bench_framework"),
            datasets={"train": ds},
        )
        result = trainer.fit()
    finally:
        ray_tpu.shutdown()
    if result.error is not None:
        raise RuntimeError(f"framework bench failed: {result.error}")
    m = result.metrics
    tps = m["measured_steps"] * batch * (seq - 1) / m["measured_s"]
    n_chips = len(devs)
    print(f"framework path: {tps:,.0f} tokens/s "
          f"(loss={m['loss']:.3f})", file=sys.stderr)
    if not on_tpu:
        return _cpu_row("gpt_tiny_train_framework", tps / n_chips)
    return {
        "metric": "gpt2_small_train_tokens_per_sec_per_chip_framework",
        "value": round(tps / n_chips, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(tps / n_chips / A100_GPT2S_TOKENS_PER_SEC, 3),
        "device": _device_info(),
    }


# --------------------------------------------------------------------------
# Parent: one child per measurement, in sequence; never imports jax.
# --------------------------------------------------------------------------

def run_all() -> int:
    """Dense base, flash, and flash through the framework, each in its
    own child so each owns the chip alone. A child that fails (no TPU
    included) fails the run; nothing is retried or substituted."""
    results = {}
    for name, flags in (("base", []), ("flash", ["--flash"]),
                        ("framework", ["--framework"])):
        proc = subprocess.run(
            [sys.executable, "-u", os.path.abspath(__file__), "--child"]
            + flags, stdout=subprocess.PIPE, text=True)
        sys.stdout.write(proc.stdout)
        if proc.returncode != 0:
            print(f"bench.py: the {name} measurement failed "
                  f"(rc={proc.returncode})", file=sys.stderr)
            return proc.returncode or 1
        results[name] = json.loads(proc.stdout.strip().splitlines()[-1])
    flash, fw = results["flash"], results["framework"]
    print(json.dumps({
        "metric": "gpt2_small_train_framework_overhead",
        "value": round(1.0 - fw["value"] / flash["value"], 4),
        "unit": "fraction of the raw flash step's tokens/s/chip",
        "flash_over_base": round(flash["value"] / results["base"]["value"],
                                 4),
        "device": flash["device"],
    }))
    return 0


def run_data_shuffle(num_blocks: int = 128,
                     rows_per_block: int = 2048) -> dict:
    """Data-exchange throughput: random_shuffle + sort over num_blocks
    blocks through the push-based pipelined exchange (MB/s, blocks/s).
    Rows land in DATA_BENCH.json next to the streaming-ingest numbers."""
    import ray_tpu
    from ray_tpu import data as rd
    from ray_tpu.data import DataContext
    from ray_tpu.data import exchange as X

    ray_tpu.init(num_cpus=4)
    ctx = DataContext.get_current()
    ctx.execution_lane = "device"
    try:
        rows = num_blocks * rows_per_block
        rng = np.random.default_rng(0)

        def source():
            for i in range(num_blocks):
                ids = np.arange(i * rows_per_block,
                                (i + 1) * rows_per_block)
                yield {"id": rng.permutation(ids),
                       "v": rng.random((rows_per_block, 4))}

        ds = rd.Dataset(source)
        total_mb = num_blocks * rows_per_block * (8 + 32) / 1e6
        out = {"blocks": num_blocks, "rows": rows,
               "dataset_mb": round(total_mb, 2),
               "merge_factor": ctx.exchange_merge_factor}
        for op, make in (("shuffle",
                          lambda: ds.random_shuffle(seed=7)),
                         ("sort", lambda: ds.sort("id"))):
            t0 = time.perf_counter()
            n = sum(len(b["id"]) for b in make().iter_blocks())
            dt = time.perf_counter() - t0
            assert n == rows, (n, rows)
            out[op] = {"seconds": round(dt, 3),
                       "mb_per_s": round(total_mb / dt, 1),
                       "blocks_per_s": round(num_blocks / dt, 1)}
        recs = X.list_exchange_stats()
        if recs:
            out["inflight_parts_high_water"] = max(
                r["inflight_parts_high_water"] for r in recs)
            out["inflight_bound"] = max(r["inflight_bound"] for r in recs)
        return out
    finally:
        ray_tpu.shutdown()


def run_serve_llm():
    """LLM serving path: streaming clients vs the continuous-batching
    engine; appends tokens/s + TTFT/TPOT rows to SERVE_BENCH.json."""
    import ray_tpu
    from ray_tpu.scripts.serve_bench import (run_serve_llm as _bench,
                                             run_serve_llm_mixed,
                                             run_serve_llm_prefix,
                                             run_serve_llm_spec)

    duration = float(os.environ.get("RT_SERVE_BENCH_S", "6"))
    clients = int(os.environ.get("RT_SERVE_BENCH_CLIENTS", "6"))
    ts = time.strftime("%Y-%m-%dT%H:%M:%S")
    ray_tpu.init(num_cpus=2)
    try:
        row = _bench(duration_s=duration, clients=clients)
        row["ts"] = ts
        # Prefix-cache acceptance workloads: shared-system-prompt TTFT
        # flatness and the mixed chunked-admission A/B.
        prefix_row = run_serve_llm_prefix()
        prefix_row["ts"] = ts
        mixed_row = run_serve_llm_mixed(duration_s=duration)
        mixed_row["ts"] = ts
        # Speculative decoding A/B/C (off vs n-gram vs small-draft) on
        # the decode-bound repetitive workload speculation targets.
        spec_row = run_serve_llm_spec()
        spec_row["ts"] = ts
    finally:
        ray_tpu.shutdown()
    out = os.environ.get("RT_SERVE_BENCH_OUT", "SERVE_BENCH.json")
    doc = {}
    if os.path.exists(out):
        with open(out) as f:
            doc = json.load(f)
    row["device"] = _device_info()
    doc["llm"] = row
    doc["llm_prefix"] = prefix_row
    doc["llm_mixed"] = mixed_row
    doc["llm_spec"] = spec_row
    with open(out, "w") as f:
        json.dump(doc, f, indent=2)
        f.write("\n")
    return row


def run_data_llm():
    """Offline batch inference (``bench.py --data-llm``): Dataset blocks
    of prompts through the LLMProcessor actor-pool operator
    (ray_tpu/data/llm.py) — same TINY engine as the serve-llm bench but
    throughput-greedy with no HTTP/SLO path, so its tokens/s should meet
    or beat SERVE_BENCH.json's llm row. The row lands in DATA_BENCH.json
    with the locality hit-rate and the store's spilled bytes."""
    import ray_tpu
    from ray_tpu import data as rd
    from ray_tpu.data.execution import last_run_stats
    from ray_tpu.data.llm import build_llm_processor
    from ray_tpu.models.gpt import TINY

    rows = int(os.environ.get("RT_DATA_LLM_ROWS", "96"))
    batch = int(os.environ.get("RT_DATA_LLM_BATCH", "8"))
    max_tokens = int(os.environ.get("RT_DATA_LLM_TOKENS", "24"))
    rt = ray_tpu.init(num_cpus=2)
    try:
        def to_prompts(b):
            # Serve-bench prompt mix: 4-12 token prompts over ids 1..200.
            return {"prompt": np.asarray(
                [[int(i) % 200 + 1] * (4 + int(i) % 9) for i in b["id"]],
                dtype=object),
                "row_id": b["id"]}

        proc = build_llm_processor(
            TINY,
            sampling={"max_tokens": max_tokens, "temperature": 0.8,
                      "seed": 0},
            num_blocks=64, block_size=16, max_batch=batch,
            name="data_llm")
        # One source block per engine batch; the prompt-building map
        # stage rides the locality-aware task router.
        ds = (rd.range(rows, override_num_blocks=max(1, rows // batch))
              .map_batches(to_prompts)
              .map_batches(proc))

        # The first output block pays the prefill+decode compiles (the
        # serve bench warms them with an untimed request); the measured
        # window opens when it lands.
        t_first = None
        tokens = blocks = 0
        t0 = time.perf_counter()
        for blk in ds.iter_blocks():
            now = time.perf_counter()
            if t_first is None:
                t_first = now
                continue
            tokens += int(np.sum(blk["num_generated_tokens"]))
            blocks += 1
        dt = time.perf_counter() - t_first
        st = last_run_stats()
        hits = st.get("locality_hits", 0)
        misses = st.get("locality_misses", 0)
        store = rt.shm.stats()
        row = {
            "rows": rows, "batch": batch, "max_tokens": max_tokens,
            "measured_blocks": blocks,
            "tokens": tokens,
            "seconds": round(dt, 3),
            "tokens_per_s": round(tokens / dt, 1),
            "wall_seconds": round(time.perf_counter() - t0, 3),
            "locality_hit_rate": round(hits / (hits + misses), 4)
            if hits + misses else None,
            "locality_hits": hits, "locality_misses": misses,
            "store_spilled_bytes": store.get("spilled_bytes", 0),
            "note": ("tokens/s over post-compile blocks; comparable to "
                     "SERVE_BENCH.json llm tokens_per_s on the same "
                     "device (same TINY engine, no HTTP path)"),
            "device": _device_info(),
            "ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
        }
    finally:
        ray_tpu.shutdown()
    out = os.environ.get("RT_DATA_BENCH_OUT", "DATA_BENCH.json")
    doc = {}
    if os.path.exists(out):
        with open(out) as f:
            doc = json.load(f)
    doc["data_llm"] = row
    with open(out, "w") as f:
        json.dump(doc, f, indent=2)
        f.write("\n")
    return row


def run_jobs_bench():
    """Multi-tenant job plane under churn: K tenants x M gang jobs on a
    simulated v5e fleet that shrinks mid-run, driven by the real
    scheduler + autoscaler stack in virtual time. Appends makespan,
    Jain fairness, and requeue counts to JOBS_BENCH.json."""
    from ray_tpu.jobs.sim import JobPlaneSim

    tenants = int(os.environ.get("RT_JOBS_BENCH_TENANTS", "4"))
    jobs_per = int(os.environ.get("RT_JOBS_BENCH_JOBS", "8"))
    sim = JobPlaneSim(max_slices_per_type=2, idle_timeout_ticks=4,
                      boot_delay_ticks=1, launch_backoff_ticks=1)
    for k in range(tenants):
        weight = float(k + 1)  # tenant-3 deserves 4x tenant-0's service
        for j in range(jobs_per):
            shape = [{"TPU": 4}, {"TPU": 8}, {"TPU": 16}][j % 3]
            sim.submit(f"tenant-{k}", weight=weight, shape=shape,
                       duration=2 + (j % 3))
    report = sim.run(max_ticks=2000, shrink_at=12, shrink_frac=0.5)
    row = {
        "tenants": tenants, "jobs": report["jobs"],
        "finished": report["finished"],
        "makespan_ticks": report["makespan"],
        "requeues": report["requeues"],
        "lost_gangs": report["lost_gangs"],
        "jain_weighted": round(report["jain_weighted"], 4),
        "ledger_shares": {t: round(s, 4) for t, s
                          in sorted(report["ledger_shares"].items())},
        "slices_killed": report["slices_killed"],
        "ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
    }
    out = os.environ.get("RT_JOBS_BENCH_OUT", "JOBS_BENCH.json")
    doc = {}
    if os.path.exists(out):
        with open(out) as f:
            doc = json.load(f)
    doc["churn"] = row
    with open(out, "w") as f:
        json.dump(doc, f, indent=2)
        f.write("\n")
    return row


def main():
    if "--jobs" in sys.argv:
        print(json.dumps(run_jobs_bench()))
        return 0
    if "--data-llm" in sys.argv:
        print(json.dumps(run_data_llm()))
        return 0
    if "--data-shuffle" in sys.argv:
        print(json.dumps(run_data_shuffle()))
        return 0
    if "--serve-llm" in sys.argv:
        print(json.dumps(run_serve_llm()))
        return 0
    if "--child" in sys.argv:
        _require_tpu()
        if "--framework" in sys.argv:
            print(json.dumps(run_bench_framework()))
        else:
            print(json.dumps(run_bench(use_flash="--flash" in sys.argv)))
        return 0
    return run_all()


if __name__ == "__main__":
    sys.exit(main())
