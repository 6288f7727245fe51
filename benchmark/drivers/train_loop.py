"""Driver ``train_loop``: one ``JaxTrainer.fit`` on the device lane.

The recipe of ``examples/train_gpt.py`` with the Data ingest below:
``ray_tpu.data.from_items`` of seeded token rows, cycled ->
``iter_batches(batch_format="jax", sharding=...)`` ->
``train.wrap_step(make_train_step(...), cfg)`` -> ``train.report`` every
step. Warm-up steps, then steps until the window's seconds have passed,
fenced by a host read of the loss at both ends.

Reads from the configuration file: ``model``, ``train`` (batch_per_chip,
optimizer, warmup_steps, dataset_batches). Collects: every report, the
window's edges, steps and tokens, the dense-attention loss of batch 0.
Logs, on earlier lines of a chip run, where the window's steps spent
their time (``_log_window_profile``).
"""

from __future__ import annotations


def _thread_cpu():
    """CPU seconds of every thread of this process so far, by native
    id, with the interpreter's name for it where it has one."""
    import os
    import threading

    names = {t.native_id: t.name for t in threading.enumerate()}
    tick = os.sysconf("SC_CLK_TCK")
    out = {}
    try:
        tids = os.listdir("/proc/self/task")
    except OSError:
        return out
    for tid in tids:
        try:
            with open(f"/proc/self/task/{tid}/stat") as f:
                rest = f.read().rsplit(")", 1)[1].split()
        except (OSError, IndexError):
            continue
        out[int(tid)] = (names.get(int(tid), "native"),
                         (int(rest[11]) + int(rest[12])) / tick)
    return out


def _loop(config):
    import time

    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmark import reference
    from benchmark.harness import Tracer
    from ray_tpu import train as rt_train
    from ray_tpu.parallel import MeshSpec

    model = __import__(config["model_module"], fromlist=["_"])
    cfg = config["cfg"]
    devices = jax.devices()[:config["chips"]]
    mesh = MeshSpec.auto(len(devices)).build(devices)
    o = config["optimizer"]
    opt = getattr(optax, o["name"])(
        o["learning_rate"], b1=o["b1"], b2=o["b2"],
        weight_decay=o["weight_decay"], mu_dtype=getattr(jnp, o["mu_dtype"]))
    # Weights from the seed, made on the device.
    params = model.init(jax.random.key(config["seed31"]), cfg)
    state = {"params": params, "opt_state": opt.init(params), "step": 0}
    state = model.shard_state(state, mesh, cfg)
    step = rt_train.wrap_step(model.make_train_step(cfg, opt, mesh), cfg)
    sharding = NamedSharding(mesh, P(("dp", "fsdp")))
    shard = rt_train.get_dataset_shard("train")
    tracer = Tracer(config["trace"])

    def batches():
        while True:
            for b in shard.iter_batches(batch_size=config["batch"],
                                        batch_format="jax",
                                        sharding=sharding, drop_last=True):
                yield b["tokens"]

    out = {"dense_loss": None, "steps": 0}
    n, t_open, t_open_wall = 0, None, None
    for tokens in batches():
        if n == 0:
            # The plain reference, before the step's program needs
            # nearly the whole chip.
            out["dense_loss"] = reference.dense_loss(
                state["params"], tokens, cfg, mesh)
        state, metrics = step(state, tokens)
        loss = float(metrics["loss"])       # host read: the fence
        n += 1
        now = time.perf_counter()
        if t_open is None:
            rt_train.report({"step": n, "loss": loss, "warmup": True})
            if n == config["warmup_steps"]:
                cpu_open = _thread_cpu()
                t_open, t_open_wall = time.perf_counter(), time.time()
            continue
        rt_train.report({"step": n, "loss": loss, "t": now})
        out["steps"] += 1
        since = now - t_open
        if since >= config["seconds"]:
            break
        if config["trace"]:
            if tracer.t_start is None and since >= config["trace_after_s"]:
                tracer.start()
            elif tracer.t_start is not None and tracer.t_stop is None and \
                    now - tracer.t_start >= config["trace_seconds"]:
                tracer.stop()
    tracer.stop()
    cpu_close = _thread_cpu()
    out["thread_cpu_s"] = sorted(
        ((name, s - cpu_open.get(tid, ("", 0.0))[1])
         for tid, (name, s) in cpu_close.items()),
        key=lambda x: -x[1])[:12]
    leaves = jax.tree_util.tree_leaves(state)
    out.update(t_open=t_open, t_close=now, t_open_wall=t_open_wall,
               tracer=tracer.state(),
               platforms=sorted({d.platform for x in leaves
                                 for d in x.devices()}))
    rt_train.report({"final": out})


def _log_window_profile(timed, final, epoch, log):
    """Earlier lines, for whoever reads the log of a run that reads
    slow: where the window's steps spent their time, by the host's
    clock, and which threads of this process used the CPU."""
    from benchmark import traffic

    def q(xs):
        return "p10 %.2f p50 %.2f p90 %.2f max %.2f mean %.2f" % (
            traffic.percentile(xs, 10), traffic.percentile(xs, 50),
            traffic.percentile(xs, 90), max(xs), sum(xs) / len(xs))

    ts = [m["t"] for m in timed]
    dts = [(b - a) * 1e3 for a, b in zip(ts, ts[1:])]
    if not dts:
        return
    log(f"step interval ms: {q(dts)}")
    for key in ("train_device_ms", "train_host_gap_ms"):
        xs = [m[key] for m in timed if key in m]
        if xs:
            log(f"{key}: {q(xs)}")
    by_phase = [[] for _ in range(epoch)]
    for m, dt in zip(timed[1:], dts):
        by_phase[m["step"] % epoch].append(dt)
    log("mean step interval ms by step %% %d: %s" % (
        epoch, " ".join("%.1f" % (sum(x) / len(x)) for x in by_phase if x)))
    log("thread CPU s in the window: " + ", ".join(
        f"{name} {s:.2f}" for name, s in final.get("thread_cpu_s", [])))


def run(ctx) -> dict:
    import shutil
    import tempfile

    from benchmark import reference, traffic
    from benchmark.harness import Tracer, log, sized
    from ray_tpu import data as rt_data
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    job = sized(ctx.config["train"], ctx.rehearse)
    chips = ctx.chips
    batch = job["batch_per_chip"] * chips
    seq = ctx.model_fields["max_seq"]
    rows = traffic.token_rows(ctx.seed, batch * job["dataset_batches"], seq,
                              ctx.model_fields["vocab_size"])
    shape = sized(ctx.workload.get("window", {}), ctx.rehearse)
    storage = tempfile.mkdtemp(prefix="benchmark-train-")
    trainer = JaxTrainer(
        _loop,
        train_loop_config={
            "cfg": ctx.model_cfg, "model_module": ctx.config["model"]["module"],
            "chips": chips, "batch": batch, "seed31": traffic.seed31(ctx.seed),
            "optimizer": job["optimizer"], "warmup_steps": job["warmup_steps"],
            "seconds": ctx.seconds, "trace": ctx.trace,
            "trace_after_s": shape.get("trace_after_s", 2.0),
            "trace_seconds": shape.get("trace_seconds", 3.0)},
        scaling_config=ScalingConfig(num_workers=1, use_tpu=True),
        run_config=RunConfig(name=f"benchmark_{ctx.workload_name}",
                             storage_path=storage),
        datasets={"train": rt_data.from_items(rows)})
    try:
        result = trainer.fit()
    finally:
        shutil.rmtree(storage, ignore_errors=True)
    if result.error is not None:
        raise SystemExit(f"benchmark: JaxTrainer.fit failed: {result.error}")
    final = result.metrics["final"]
    reports = [m for m in result.metrics_history if "loss" in m]
    timed = [m for m in reports if not m.get("warmup")]
    losses = [m["loss"] for m in reports]
    checks = reference.train_checks(losses, final["dense_loss"],
                                    ctx.model_fields["vocab_size"])
    checks.append((final["platforms"] == [ctx.device["platform"]],
                   f"every leaf of the train state lives on "
                   f"{final['platforms']}"))
    window_s = final["t_close"] - final["t_open"]
    tokens = final["steps"] * batch * seq
    log(f"window: {final['steps']} steps, {tokens} tokens in "
        f"{window_s:.3f} s")
    if not ctx.rehearse:        # times: never from a CPU run
        _log_window_profile(timed, final, job["dataset_batches"], log)
    return {
        "kind": "train",
        "checks": checks,
        "attempted": final["steps"], "failed": 0,
        "t_open": final["t_open"], "t_close": final["t_close"],
        "window_s": window_s, "steps": final["steps"], "tokens": tokens,
        "batch": batch, "seq": seq, "chips": chips,
        "reports": timed,
        "tracer": Tracer.from_state(final["tracer"]) if ctx.trace else None,
    }
