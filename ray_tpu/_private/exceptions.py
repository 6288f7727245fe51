"""User-facing error types.

Capability parity target: the reference's exception catalogue
(/root/reference/python/ray/exceptions.py) — task errors wrapping the remote
traceback, actor death, object loss, OOM, and cancellation.
"""

from __future__ import annotations

import traceback


class RayTpuError(Exception):
    """Base class for all framework errors."""


class TaskError(RayTpuError):
    """A remote task raised an exception. ``cause`` is the original exception
    (if it could be pickled) and ``remote_traceback`` the formatted remote
    stack."""

    def __init__(self, message: str, cause: BaseException | None = None,
                 remote_traceback: str | None = None, task_name: str = ""):
        super().__init__(message)
        self.cause = cause
        self.remote_traceback = remote_traceback
        self.task_name = task_name

    def __str__(self):
        base = super().__str__()
        if self.remote_traceback:
            return f"{base}\n\n--- remote traceback ({self.task_name}) ---\n{self.remote_traceback}"
        return base

    @classmethod
    def from_exception(cls, e: BaseException, task_name: str = "") -> "TaskError":
        tb = traceback.format_exc()
        try:
            import cloudpickle

            cloudpickle.dumps(e)
            cause = e
        except Exception:  # lint: allow-swallow(unpicklable cause; message+traceback still carried)
            cause = None
        return cls(f"{type(e).__name__}: {e}", cause=cause,
                   remote_traceback=tb, task_name=task_name)


class WorkerCrashedError(TaskError):
    """The worker process executing the task died (segfault/OOM-kill/exit)."""

    def __init__(self, message="The worker died while running the task.",
                 task_name: str = ""):
        super().__init__(message, task_name=task_name)


class ActorDiedError(TaskError):
    """The actor is dead (init failure, crash beyond max_restarts, or kill)."""

    def __init__(self, message="The actor died.", task_name: str = ""):
        super().__init__(message, task_name=task_name)


class ActorUnavailableError(TaskError):
    """The actor is temporarily unavailable (restarting)."""


class ObjectLostError(RayTpuError):
    """Object data was lost and could not be reconstructed from lineage."""


class ObjectFreedError(ObjectLostError):
    """The object's value was explicitly released via ``ray_tpu.free`` —
    dangling refs resolve to this error instead of hanging (reference:
    ray._private.internal_api.free / `ray.exceptions.ObjectFreedError`)."""


class GetTimeoutError(RayTpuError, TimeoutError):
    """`get` exceeded its timeout."""


class TaskCancelledError(TaskError):
    def __init__(self, message="Task was cancelled.", task_name: str = ""):
        super().__init__(message, task_name=task_name)


class OutOfMemoryError(TaskError):
    """Worker killed by the memory monitor."""


class RuntimeEnvSetupError(RayTpuError):
    """Failed to set up the runtime environment for a task/actor."""
