"""Streaming LLM deployment: the continuous-batching engine behind
Serve's generator/chunked-transfer path.

Reference layer map: the "LLM serving" integration the reference runtime
provides by fronting external engines — here the engine is native
(ray_tpu.llm). One replica hosts ONE LLMEngine; Serve's replica thread
pool delivers concurrent ``__call__``s, each of which registers a
request with the shared engine EAGERLY (so TTFT starts at arrival, not
at first stream pull) and returns a ``PushedStream``. During a step the
interpreter is the engine's: its thread and the serving threads meet
ONCE on the way out, where the engine hands its sink (``_hand_over``)
what the step decided for every lane and the sink puts all of it into
the replica's streams under one lock, with one wake-up of the poll that
waits for them (no thread a stream). The streams ride the STREAM_MARKER
protocol: the proxy reads them as it reads a generator's, and HTTP
clients see ndjson chunked transfer — one frame per token.

SLO + telemetry: per-request TTFT and TPOT are recorded as serve phases
(slo.record_phase), so ``serve.status()`` reports their p50/p95/p99 next
to the routing phases and the head keeps ``serve_p95_ms:<dep>:ttft``
series; the engine itself publishes tokens/s, KV-pool utilization and
in-flight batch size gauges that surface as ``llm_tokens_per_s:<dep>``
et al. in ``state.timeseries()`` (the PR-6 telemetry plane).

Tokenization is byte-level (ids 0..255) so the subsystem is runnable
without any external vocabulary: string prompts encode to UTF-8 bytes,
and the final frame carries the decoded text when every token is a byte.
"""

from __future__ import annotations

import gc
import sys
import threading
import time
from typing import Any, Optional

from . import slo
from .deployment import deployment
from .replica import PushedStream, push


def encode(text: str):
    """Byte-level tokenize (ids 0..255)."""
    return list(text.encode("utf-8"))


def decode(tokens) -> Optional[str]:
    """Inverse of encode(); None if any token is out of byte range."""
    if any(t < 0 or t > 255 for t in tokens):
        return None
    return bytes(tokens).decode("utf-8", errors="replace")


# The interpreter's switch interval in a process that serves a model.
# The engine's thread and the serving threads (the handle's poller, the
# proxy's loop, the actor threads) pass ONE interpreter between them
# every step, and a thread that wakes with work asks the holder for it
# only after this interval: at CPython's 5 ms, a quarter of a chat
# step, a first token's way in and out through those threads grew by
# 6-10 ms when the device step gave them 1.2 ms less of the interpreter
# (PERF.md section 6, PR 61); at 1 ms it did not.
SWITCH_INTERVAL_S = 0.001

_freeze_lock = threading.Lock()
_frozen = False
# Programs this process has built so far (jax's own event, which a hit
# in its persistent cache raises too), counted from the first replica
# on: a replica whose requests finish while this stands still is warm.
_programs_built = 0
_counting = False


def _count_programs():
    global _counting
    with _freeze_lock:
        if _counting:
            return
        _counting = True
    import jax

    def built(event, duration, **_):
        global _programs_built
        if event == "/jax/core/compile/backend_compile_duration":
            _programs_built += 1

    jax.monitoring.register_event_duration_secs_listener(built)


def _freeze_setup_heap():
    """Once a process, when a serving replica's engine is built and
    warm: collect, then move everything that is left (what jax, Serve,
    the model and set-up built, which lives as long as the process)
    out of the collector's sight. Its passes still run at their
    thresholds, over what is allocated from here on; a pass of the
    oldest generation no longer walks the set-up's heap with the
    interpreter held, which was the longest pause of a serving window
    (PERF.md section 6, PR 52)."""
    global _frozen
    with _freeze_lock:
        if _frozen:
            return
        _frozen = True
    gc.collect()
    gc.freeze()


class _LLMServer:
    """User class for the generation deployment (wrapped by
    ``LLMServer = serve.deployment(_LLMServer)`` below; use
    ``build_app()`` for the common case)."""

    def __init__(self, cfg=None, params=None, *, seed: int = 0,
                 num_blocks: int = 64,
                 window_blocks: Optional[int] = None,
                 state_slots: Optional[int] = None,
                 block_size: int = 16,
                 max_batch: int = 8, default_max_tokens: int = 32,
                 prefill_chunk_tokens: Optional[int] = 32,
                 prefix_cache: bool = True,
                 speculative=None,
                 system_prompt=None):
        import jax

        from ..llm.engine import LLMEngine
        from ..models import serving
        from ..models.gpt import TINY

        # Any configuration whose module stands behind the serving seam
        # (models/__init__.py); the default is the tiny GPT preset.
        cfg = cfg if cfg is not None else TINY
        if params is None:
            params = serving(cfg).init(jax.random.PRNGKey(seed), cfg)
        # Replica.__init__ sets the process deployment name before
        # constructing us — tag the engine's gauges with it.
        name = slo.current_deployment() or "llm"
        self.default_max_tokens = int(default_max_tokens)
        # Deployment-wide prefix hint: prepended to every prompt, so
        # with the prefix cache on it is computed once and every later
        # request's cached span covers it (the shared-system-prompt
        # serving pattern).
        if isinstance(system_prompt, str):
            system_prompt = encode(system_prompt)
        self.system_prompt = [int(t) for t in (system_prompt or ())]
        sys.setswitchinterval(min(sys.getswitchinterval(),
                                  SWITCH_INTERVAL_S))
        # Warm is when as many requests as the engine has lanes have
        # finished since the process last built a program: whatever
        # warms a deployment up (a request a chunk length, a request a
        # shared prefix) builds as it goes or is fewer than that.
        _count_programs()
        self._built_seen = _programs_built
        self._finished_since = 0
        # Serving defaults to chunked prefill (bounded per-step prefill
        # keeps decode streams emitting every step) and prefix caching.
        # ``speculative`` (None | dict | SpecConfig — llm/spec.py) turns
        # decode steps into k+1-position verify steps; output tokens are
        # bit-identical either way, so it is purely a throughput knob.
        # ``window_blocks`` sizes the second pool of a model that has a
        # kind of layer with a window (None: twice what max_batch lanes
        # hold); a model without one has no such pool. ``state_slots``
        # sizes the slots of a model whose sequences keep a state (a
        # lane's each and parked snapshots; None: twice max_batch).
        self.engine = LLMEngine(params, cfg, num_blocks=num_blocks,
                                window_blocks=window_blocks,
                                state_slots=state_slots,
                                block_size=block_size,
                                max_batch=max_batch,
                                prefill_chunk_tokens=prefill_chunk_tokens,
                                prefix_cache=prefix_cache,
                                speculative=speculative, name=name,
                                sink=self._hand_over)
        self.engine.start()

    def __call__(self, request: Any):
        """request: {"prompt": str | [int], "max_tokens": int?,
        "temperature": float?, "top_k": int?, "seed": int?,
        "stop_tokens": [int]?}. Streams {"token": id} frames, then a
        final {"done": ..., "text": ...} frame."""
        if isinstance(request, str):
            request = {"prompt": request}
        prompt = request.get("prompt")
        if isinstance(prompt, str):
            prompt = encode(prompt)
        if not prompt:
            raise ValueError("request needs a non-empty 'prompt'")
        if self.system_prompt:
            prompt = self.system_prompt + list(prompt)
        # Register with the engine NOW: the request joins the in-flight
        # batch at the next step, and what the engine decides for it
        # before the replica has the stream on its books waits in the
        # stream. The replica span's trace context is captured HERE
        # (this thread): the frames are made on the engine's thread,
        # which has none set.
        from ray_tpu.util import tracing

        trace_ctx = tracing.current_context.get()
        stream = PushedStream()
        self.engine.add_request(
            prompt,
            max_tokens=int(request.get("max_tokens",
                                       self.default_max_tokens)),
            temperature=float(request.get("temperature", 0.0)),
            top_k=int(request.get("top_k", 0)),
            seed=int(request.get("seed", 0)),
            stop_tokens=request.get("stop_tokens", ()),
            trace_ctx=trace_ctx, consumer=stream)
        return stream

    def _hand_over(self, handed):
        """The engine's sink: a hand-over's ``(request, tokens,
        finish_reason)`` as frames, all into their streams in one
        ``push``. Runs on the engine's thread under the engine's lock
        and takes the replica's stream condition inside it, never the
        reverse: it calls nothing of the engine, and the replica's
        stream calls take no engine lock. The SLO phases are recorded
        where a first token and a finish are handed over: ``ttft`` ends
        HERE, before the reader's poll has woken."""
        dep = self.engine.name
        now = time.time()
        pushes = []
        for req, tokens, reason in handed:
            frames = [{"token": tok} for tok in tokens]
            first = tokens and req.emitted == len(tokens)
            if first or reason is not None:
                trace_id = (req.trace_ctx or {}).get("trace_id")
            if first:
                slo.record_phase("ttft", now - req.submit_t, dep,
                                 trace_id=trace_id)
                # The part of it spent waiting for a lane:
                # add_request -> first admission into the batch.
                slo.record_phase("engine_queue",
                                 req.admit_t - req.submit_t, dep,
                                 trace_id=trace_id)
            if reason is not None:
                if req.first_token_t and len(req.output) > 1:
                    slo.record_phase(
                        "tpot",
                        (req.finish_t - req.first_token_t)
                        / (len(req.output) - 1), dep, trace_id=trace_id)
                frames.append({"done": True,
                               "finish_reason": reason,
                               "num_tokens": len(req.output),
                               "preemptions": req.preemptions,
                               "cached_tokens": req.cached_tokens,
                               "text": decode(req.output)})
            pushes.append((req.consumer, frames, reason is not None, None))
        push(pushes)
        if not _frozen:
            # In a deployment's set-up, not in an engine's
            # (``LLMEngine.start``): an engine made by a test or by
            # data/llm does not freeze its process.
            if self._built_seen != _programs_built:
                self._built_seen, self._finished_since = _programs_built, 0
            else:
                self._finished_since += sum(p[2] for p in pushes)
                if self._finished_since >= self.engine.max_batch:
                    _freeze_setup_heap()

    def engine_stats(self) -> dict:
        """Engine introspection over the handle
        (``h.options(method_name="engine_stats")``)."""
        out = self.engine.stats()
        # Cumulative sum/count a phase (ttft, tpot, engine_queue,
        # stream_hold, ...): two readings give a window's mean.
        out["phase_hist"] = slo.phase_hist(self.engine.name)
        # Cumulative CPU seconds (and wait for a core) by thread group
        # of this process, the replica's: two readings give a window's.
        from ray_tpu._private.profiler import thread_cpu

        out["threads"] = thread_cpu()
        return out

    def check_health(self) -> bool:
        return True

    def __del__(self):
        try:
            self.engine.stop()
        except Exception:  # noqa: BLE001 - interpreter teardown
            pass


# The engine's params and KV pools live on the chip, and a chip belongs
# to one process at a time — the one hosting the device lane. So the
# replica is placed there by default; a CPU-lane worker would serve from
# the CPU platform through the Pallas interpreter.
LLMServer = deployment(
    name="LLMServer",
    ray_actor_options={"scheduling_strategy": "device"})(_LLMServer)


def build_app(cfg=None, **kwargs):
    """The copy-pasteable entrypoint:

        from ray_tpu.serve.llm import build_app
        serve.run(build_app(), name="llm")
    """
    return LLMServer.bind(cfg, **kwargs)
