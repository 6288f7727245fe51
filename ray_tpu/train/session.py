"""Per-worker training session.

Capability parity target: the reference's session plumbing
(/root/reference/python/ray/train/_internal/session.py — `report:393` queues
results that the trainable polls back; `get_context` exposes ranks). Here the
session is a module-global bound inside each TrainWorker; ``report`` enqueues
(metrics, checkpoint) pairs that the trainer's fit-loop drains via actor
polling.

Device-step performance plane: ``wrap_step`` instruments a jitted train
step (dispatch-to-``block_until_ready`` timed apart from the host work
around it, FLOPs/bytes priced by util/perfmodel.py) and ``report``
closes the step, report to report, on the session's
``perfmodel.StepAccounting`` — reported metrics gain
``train_step_ms``/``train_device_ms``/``train_host_gap_ms``/
``train_mfu``/``train_hbm_util`` and the split of the device span
(``train_dispatch_ms`` + ``train_ready_wait_ms``) and of the host's part
(``train_data_wait_ms``), and the step's share of a standstill of the
machine (``train_standstill_ms``), the same values ride the worker metrics
flusher into head telemetry series (``train_mfu:<trial>``, ...), and
every step lands in the perfmodel device-step ring where
``rtpu profile --device`` collects it.
"""

from __future__ import annotations

import queue
import threading
from dataclasses import dataclass, field
from typing import Any, Optional

from .checkpoint import Checkpoint

# Thread-local: several TrainWorkers (e.g. concurrent Tune trials as device
# actors) can coexist in one process, each binding the session on its own
# training-loop thread.
_tls = threading.local()

# Process-wide gang coordinates, written by TrainWorker.__init__ and read
# lazily by the flight recorder (parallel/flightrec.py) when it snapshots:
# kept HERE so CPU-lane workers never import the jax-heavy parallel
# package just to be nameable in a desync verdict.
_worker_identity: dict = {}


@dataclass
class TrainContext:
    world_rank: int = 0
    world_size: int = 1
    local_rank: int = 0
    node_rank: int = 0
    experiment_name: str = ""
    trial_name: str = ""
    trial_id: str = ""
    datasets: dict = field(default_factory=dict)
    mesh: Any = None
    loaded_checkpoint: Optional[Checkpoint] = None

    def get_world_rank(self) -> int:
        return self.world_rank

    def get_world_size(self) -> int:
        return self.world_size

    def get_local_rank(self) -> int:
        return self.local_rank

    def get_trial_name(self) -> str:
        return self.trial_name

    def get_experiment_name(self) -> str:
        return self.experiment_name


class _TrainSession:
    def __init__(self, ctx: TrainContext):
        self.ctx = ctx
        self.reports: queue.Queue = queue.Queue()
        self.stop_event = threading.Event()
        # One step runs from report() to report(): wrap_step()'s device
        # spans, the batch iterator's waits and report() itself land on
        # this accounting (perfmodel.PHASES) and are closed into a ring
        # entry and the report's train_* keys by the next report().
        from ..util import perfmodel

        self._step_perf = perfmodel.StepAccounting()
        self._step_open = False
        self._steps = 0         # wrapped steps, for the step annotation
        self._perf_gauges = None

    def record_device(self, cost=None):
        """wrap_step's sink: one wrapped step under its annotation,
        whose ``train.dispatch`` / ``train.wait`` spans the caller holds
        on the returned accounting; the priced StepCost is folded into
        the next report()."""
        acc = self._step_perf
        if cost is not None:
            acc.add_cost(cost)
        self._steps += 1
        return acc

    def _drain_step_perf(self) -> Optional[dict]:
        """Close the step that ran since the last report into a host-
        vs-device breakdown and open the next (None when nothing was
        recorded — loops that don't use wrap_step report exactly as
        before — and on the first report, which has no step behind
        it)."""
        acc = self._step_perf
        step = (acc.finish(record_as="train.step",
                           attrs={"trial": self.ctx.trial_name})
                if self._step_open else None)
        acc.begin()
        self._step_open = True
        if step is None:
            return None
        out = {
            "train_step_ms": step["step_ms"],
            "train_device_ms": step["device_ms"],
            "train_host_gap_ms": step["host_gap_ms"],
            # The device span's two halves: the jitted call until it
            # returns, and the wait for its outputs.
            "train_dispatch_ms": step["device_ms_by"].get("dispatch", 0.0),
            "train_ready_wait_ms": step["device_ms_by"].get("wait", 0.0),
            "train_data_wait_ms": step["phases_ms"].get(
                "data.next_batch", 0.0),
            # What the interpreter probe put down to the step as the
            # machine's: it woke over 50 ms late and the process's CPU
            # clock had stood still (perfmodel._InterpreterProbe).
            "train_standstill_ms": step["standstill_ms"],
        }
        if "mfu" in step:       # only with a peak: never on the CPU
            out.update(train_mfu=step["mfu"],
                       train_hbm_util=step["hbm_util"],
                       train_roofline=step["verdict"])
        self._publish_perf_gauges(out)
        return out

    def _publish_perf_gauges(self, perf: dict):
        """train_* breakdown onto the telemetry plane (worker flusher ->
        node user_metrics -> head series train_mfu:<trial>, ...)."""
        try:
            if self._perf_gauges is None:
                from ray_tpu.util.metrics import Gauge

                keys = ("trial",)
                self._perf_gauges = {
                    "train_step_ms": Gauge(
                        "rtpu_train_step_ms",
                        "Report-to-report train step wall time (ms)",
                        tag_keys=keys),
                    "train_device_ms": Gauge(
                        "rtpu_train_device_ms",
                        "Train step device time, dispatch to "
                        "block_until_ready (ms)", tag_keys=keys),
                    "train_host_gap_ms": Gauge(
                        "rtpu_train_host_gap_ms",
                        "Train step host time around the device span "
                        "(ms)", tag_keys=keys),
                    "train_mfu": Gauge(
                        "rtpu_train_mfu",
                        "Model FLOPs utilization of the train step's "
                        "device span [0,1]", tag_keys=keys),
                    "train_hbm_util": Gauge(
                        "rtpu_train_hbm_util",
                        "HBM-bandwidth utilization of the train step's "
                        "device span [0,1]", tag_keys=keys),
                }
            tags = {"trial": self.ctx.trial_name or "?"}
            for key, gauge in self._perf_gauges.items():
                if key in perf:     # no MFU / HBM util on the CPU backend
                    gauge.set(float(perf[key]), tags=tags)
        except Exception:  # noqa: BLE001 - telemetry is best-effort
            pass

    def report(self, metrics: dict, checkpoint: Optional[Checkpoint] = None):
        metrics = dict(metrics)
        perf = self._drain_step_perf()  # _step_perf -> breakdown
        with self._step_perf.phase("train.report"):
            if perf is not None:
                for k, v in perf.items():
                    metrics.setdefault(k, v)
            self.reports.put(("report", metrics, checkpoint))
        if self.stop_event.is_set():
            raise StopIteration("training stopped by the controller")


def _bind(session: "_TrainSession"):
    from ..util import perfmodel

    _tls.session = session
    # Code under the loop that does not know the session (Data's batch
    # iterator) finds the step's accounting through perfmodel.
    perfmodel.bind_accounting(session._step_perf)
    return session


def _unbind():
    from ..util import perfmodel

    _tls.session = None
    perfmodel.bind_accounting(None)


def _get() -> Optional[_TrainSession]:
    return getattr(_tls, "session", None)


# -- public API (ray_tpu.train.*) -------------------------------------------
def report(metrics: dict, checkpoint: Optional[Checkpoint] = None):
    """Report metrics (and optionally a checkpoint) from the training loop."""
    s = _get()
    if s is None:
        raise RuntimeError("ray_tpu.train.report() called outside a training loop")
    s.report(metrics, checkpoint)


def get_context() -> TrainContext:
    s = _get()
    if s is None:
        return TrainContext()
    return s.ctx


def get_checkpoint() -> Optional[Checkpoint]:
    """The checkpoint to resume from (set on gang restart after failure)."""
    s = _get()
    return s.ctx.loaded_checkpoint if s else None


def wrap_step(step_fn, cfg=None):
    """Instrument a jitted train step for the device-step performance
    plane: each call is timed dispatch-to-``block_until_ready`` (the
    device span, as opposed to the host work between steps), and priced
    by the shared cost model when ``cfg`` (a GPTConfig-shaped object) is
    given — the (batch, seq) shape is taken from the integer token batch
    among the arguments. The next ``report()`` then carries
    ``train_step_ms``/``train_device_ms``/``train_host_gap_ms``/
    ``train_mfu``/``train_hbm_util`` and publishes the same values as
    telemetry series.

        step = train.wrap_step(gpt.make_train_step(cfg, opt, mesh), cfg)
        state, metrics = step(state, tokens)
        train.report({"loss": float(metrics["loss"])})

    Inside a training loop each call also records one step-boundary
    entry (group ``step/<experiment>``) in the gang flight recorder —
    the in-graph collectives inside the compiled step are not
    individually interceptable, so this entry is what the desync
    watchdog aligns for jitted loops (see parallel/flightrec.py).

    Outside a training loop the wrapper still times the call but records
    nowhere — safe for bench/offline use."""

    def timed_step(*args, **kwargs):
        import jax

        s = _get()
        if s is None:
            # No session, nowhere to record: the same blocking call.
            out = step_fn(*args, **kwargs)
            jax.block_until_ready(out)
            return out
        from ..parallel import flightrec
        from ..util import perfmodel

        rec = flightrec.record_op(
            f"step/{s.ctx.experiment_name or 'train'}", "train_step")
        cost = None
        if cfg is not None:
            shape = _token_batch_shape(args)
            if shape is not None:
                cost = perfmodel.train_step_cost(cfg, shape[0], shape[1])
        acc = s.record_device(cost)
        with rec, acc.step("train.step", s._steps):
            # Two halves of one device span: a slow step shows which
            # grew, the host's dispatch or the wait for the device.
            with acc.device("train.dispatch"):
                out = step_fn(*args, **kwargs)
            with acc.device("train.wait"):
                jax.block_until_ready(out)
        return out

    return timed_step


def _token_batch_shape(args) -> Optional[tuple]:
    """(batch, seq) of the first 2-D integer array in the argument
    pytree — make_train_step's ``tokens`` operand."""
    import jax
    import numpy as np

    for leaf in jax.tree_util.tree_leaves(args):
        dtype = getattr(leaf, "dtype", None)
        if dtype is not None and np.issubdtype(dtype, np.integer) \
                and getattr(leaf, "ndim", 0) == 2:
            return tuple(int(x) for x in leaf.shape)
    return None


def get_dataset_shard(name: str = "train"):
    """This worker's shard of a dataset passed to the trainer
    (parity: ray.train.get_dataset_shard; reference streaming_split ingest
    /root/reference/python/ray/train/_internal/data_config.py:112)."""
    s = _get()
    if s is None or name not in s.ctx.datasets:
        raise KeyError(f"no dataset '{name}' attached to this training run")
    return s.ctx.datasets[name]
