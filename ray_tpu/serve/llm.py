"""Streaming LLM deployment: the continuous-batching engine behind
Serve's generator/chunked-transfer path.

Reference layer map: the "LLM serving" integration the reference runtime
provides by fronting external engines — here the engine is native
(ray_tpu.llm). One replica hosts ONE LLMEngine; Serve's replica thread
pool delivers concurrent ``__call__``s, each of which registers a
request with the shared engine EAGERLY (so TTFT starts at arrival, not
at first stream pull) and returns a generator. The generator rides the
existing STREAM_MARKER protocol: the replica parks it, the proxy drains
it chunk-at-a-time, and HTTP clients see ndjson chunked transfer — one
frame per token.

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

import time
from typing import Any, Optional

from . import slo
from .deployment import deployment


def encode(text: str):
    """Byte-level tokenize (ids 0..255)."""
    return list(text.encode("utf-8"))


def decode(tokens) -> Optional[str]:
    """Inverse of encode(); None if any token is out of byte range."""
    if any(t < 0 or t > 255 for t in tokens):
        return None
    return bytes(tokens).decode("utf-8", errors="replace")


class _LLMServer:
    """User class for the generation deployment (wrapped by
    ``LLMServer = serve.deployment(_LLMServer)`` below; use
    ``build_app()`` for the common case)."""

    def __init__(self, cfg=None, params=None, *, seed: int = 0,
                 num_blocks: int = 64,
                 window_blocks: Optional[int] = None,
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
        # Serving defaults to chunked prefill (bounded per-step prefill
        # keeps decode streams emitting every step) and prefix caching.
        # ``speculative`` (None | dict | SpecConfig — llm/spec.py) turns
        # decode steps into k+1-position verify steps; output tokens are
        # bit-identical either way, so it is purely a throughput knob.
        # ``window_blocks`` sizes the second pool of a model that has a
        # kind of layer with a window (None: twice what max_batch lanes
        # hold); a model without one has no such pool.
        self.engine = LLMEngine(params, cfg, num_blocks=num_blocks,
                                window_blocks=window_blocks,
                                block_size=block_size,
                                max_batch=max_batch,
                                prefill_chunk_tokens=prefill_chunk_tokens,
                                prefix_cache=prefix_cache,
                                speculative=speculative, name=name)
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
        # batch at the next step even though the generator body below
        # only runs when the stream is first pulled. The replica span's
        # trace context is captured HERE (this thread) because gen()
        # executes later on the stream's feeder thread with no context set.
        from ray_tpu.util import tracing

        trace_ctx = tracing.current_context.get()
        trace_id = (trace_ctx or {}).get("trace_id")
        req = self.engine.add_request(
            prompt,
            max_tokens=int(request.get("max_tokens",
                                       self.default_max_tokens)),
            temperature=float(request.get("temperature", 0.0)),
            top_k=int(request.get("top_k", 0)),
            seed=int(request.get("seed", 0)),
            stop_tokens=request.get("stop_tokens", ()),
            trace_ctx=trace_ctx)
        dep = self.engine.name

        def gen():
            first = True
            for tok in req.tokens():
                if first:
                    first = False
                    slo.record_phase("ttft", time.time() - req.submit_t,
                                     dep, trace_id=trace_id)
                    # The part of it spent waiting for a lane:
                    # add_request -> first admission into the batch.
                    slo.record_phase("engine_queue",
                                     req.admit_t - req.submit_t, dep,
                                     trace_id=trace_id)
                yield {"token": tok}
            if req.first_token_t and req.finish_t \
                    and len(req.output) > 1:
                slo.record_phase(
                    "tpot",
                    (req.finish_t - req.first_token_t)
                    / (len(req.output) - 1), dep, trace_id=trace_id)
            yield {"done": True,
                   "finish_reason": req.finish_reason,
                   "num_tokens": len(req.output),
                   "preemptions": req.preemptions,
                   "cached_tokens": req.cached_tokens,
                   "text": decode(req.output)}

        return gen()

    def engine_stats(self) -> dict:
        """Engine introspection over the handle
        (``h.options(method_name="engine_stats")``)."""
        out = self.engine.stats()
        # Cumulative sum/count a phase (ttft, tpot, engine_queue,
        # stream_hold, ...): two readings give a window's mean.
        out["phase_hist"] = slo.phase_hist(self.engine.name)
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
