"""Continuous-batching generation engine (iteration-level scheduling).

Reference layer map: the Orca-style scheduler (Yu et al., OSDI '22) the
reference runtime fronts with external inference servers — here it is
native. One engine owns the model params, the paged KV pool
(llm/kv_cache.py) and a step loop; N in-flight requests share ONE
device-resident batch, and what a step decides for them leaves the
engine in one hand-over to its sink (Serve's replica: serve/llm.py),
or, for a request added without a consumer behind that sink, through
its own queue (``Request.tokens()``).

Scheduling is per STEP, not per request: every step first admits waiting
requests into the in-flight batch (prefill), then runs ONE decode token
for every running sequence. A request that arrives mid-generation joins
the very next step — the batch is recomposed continuously instead of
draining.

Request lifecycle (every transition emits an event — the concurrency-net
lint in tests/test_concurrency_net.py holds these sites to it):

    WAITING --admit--> PREFILL --activate--> RUNNING --finish--> FINISHED
                          ^                     |
                          '----- PREEMPTED <----'  (pool exhausted)

Preemption is recompute-on-resume: the victim's blocks are freed (its
generated tokens are kept host-side) and on re-admission the engine
re-prefills prompt + generated-so-far. Sampling is keyed by
(seed, position) only (llm/sampling.py), so a resumed sequence produces
bit-identical output — admission beyond pool capacity degrades latency,
never correctness, and never OOMs.

Two admission-path optimizations (both on by default for serving):

  * PREFIX CACHING (prefix_cache=True): the pool is a PrefixPool —
    released blocks keep their content hash-indexed by token-prefix
    chain, so an equal prefix (shared system prompt, multi-turn
    history, or a preempted request resuming) is re-acquired by
    refcount bump instead of recomputed; divergence on a shared
    partially-filled tail block is handled copy-on-write.
  * CHUNKED PREFILL (prefill_chunk_tokens=N): at most N uncached
    prompt tokens prefill per step, a per-request ``prefilled_upto``
    cursor carrying across steps, so running decode streams emit a
    token EVERY step instead of stalling behind a long prompt
    (Sarathi-style stall-free admission).

And one decode-path optimization (opt-in, ``speculative=...``):
SPECULATIVE DECODING (llm/spec.py) — a proposer guesses up to k next
tokens per sequence and the decode step scores k+1 rows per lane, not
one, through the same program and paged-attention kernel; the accepted
prefix plus one corrected/bonus token emit in a single step. Because
sampling is keyed by (seed, position) alone, acceptance is an equality
check against the replayed keyed draw — the output token stream is
bit-identical to non-speculative decoding, preemption and all.
"""

from __future__ import annotations

import collections
import functools
import itertools
import queue
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Deque, List, Optional, Tuple

import jax
import numpy as np

from ..models import (pack_span, pack_spans, served_params, serving,
                      step_columns, window_table_len)
from ..util import perfmodel, tracing
from .kv_cache import (BlockChain, PagedKVCache, PrefixPool, StatePool,
                       WindowPool)
from .sampling import accept_draws, is_greedy, sample, verify_tokens
from .spec import make_spec

# Roofline verdict -> coded gauge value for the telemetry plane
# (0 = idle-decayed / no accounted step yet; _private/alerting.py's
# VERDICT_CODES is the inverse map the evidence bundle uses).
_VERDICT_CODE = {"compute": 1.0, "hbm": 2.0, "host": 3.0}

# Request states (the event vocabulary).
WAITING = "WAITING"
PREFILL = "PREFILL"
RUNNING = "RUNNING"
PREEMPTED = "PREEMPTED"
FINISHED = "FINISHED"


@dataclass
class Request:
    rid: int
    prompt: List[int]
    max_tokens: int
    temperature: float = 0.0
    top_k: int = 0
    seed: int = 0
    stop_tokens: Tuple[int, ...] = ()
    state: str = WAITING
    block_table: List[int] = field(default_factory=list)
    # The table of the kind of layer with a window, where the model has
    # one: the sequence's blocks from ``window_first`` on.
    window_table: List[int] = field(default_factory=list)
    window_first: int = 0
    # Where the model's sequences keep a state: the lane's slot of the
    # state pools, held from admission to release, and the parked
    # snapshot its first span is to start from (None: its own slot).
    state_slot: Optional[int] = None
    state_from: Optional[int] = None
    # The prompt's chain of prefix-index keys, made once in
    # add_request (None where the pool indexes nothing); blocks of
    # generated tokens join it as they fill.
    chain: Optional[BlockChain] = None
    # The row of the engine's packed step array this request holds
    # while it is RUNNING.
    lane: Optional[int] = None
    context_len: int = 0          # tokens resident in the KV pool
    prefilled_upto: int = 0       # prompt tokens computed OR cache-hit
    cached_tokens: int = 0        # prefix-cache hit span at admission
    output: List[int] = field(default_factory=list)
    emitted: int = 0              # tokens already pushed to the consumer
    finish_reason: Optional[str] = None
    submit_t: float = 0.0
    admit_t: Optional[float] = None     # first admission into the batch
    first_token_t: Optional[float] = None
    finish_t: Optional[float] = None
    preemptions: int = 0
    # Serving-lane trace context ({"trace_id", "span_id"} of the request
    # span this generation belongs to); None outside traced requests.
    trace_ctx: Optional[dict] = None
    # ``add_request``'s: whatever the engine's sink finds this request's
    # reader by, which the engine never looks at (None: its tokens and
    # its finish leave on ``out_q``).
    consumer: object = None
    out_q: "queue.Queue" = field(default_factory=queue.Queue)

    @property
    def greedy(self) -> bool:
        """Takes the argmax: its decode tokens are the ids the program
        returns, and no logits of its lane leave the device."""
        return is_greedy(self.temperature, self.top_k)

    def tokens(self):
        """Blocking generator over this request's output tokens, for a
        request added without a consumer (data/llm.py, tests, tools)."""
        while True:
            tok = self.out_q.get()
            if tok is None:
                return
            yield tok


@dataclass
class _Span:
    """A span of a prompt that the step's budget has bought and no
    program carries yet (``LLMEngine._run_prefills``)."""
    req: Request
    seq: List[int]              # the request's tokens, prompt and output
    t0: float                   # wall clock where the span was cut
    upto: int                   # tokens resident before it
    c: int                      # its tokens, and the rows that pad them
    pad: int                    # to whole blocks
    total: int                  # the prompt's tokens
    snap_at: int                # where a state's snapshot is due, or 0


@dataclass
class _Chunk:
    """A span of a prompt in a chunk program the step has dispatched
    and not yet seen done: what it owes the host, settled in
    ``LLMEngine._settle``. The spans that rode in ONE program share its
    device span, its results and its row of the log, and lie side by
    side in ``_pending``."""
    req: Optional[Request]      # None: preempted with the chunk in flight
    span: "perfmodel._Program"  # its program's device span, open
    result: jax.Array           # its program's ids (greedy) or rows, and
    index: Optional[int]        # its own row of them (None: they are its)
    done: bool                  # the prompt ends with this chunk
    log: list                   # its program's row of ``prefill_chunks``
    t0: float                   # wall clock at its start, and the span's
    tokens: int                 # prompt tokens it computed, and how many
    upto: int                   # are resident after it, of
    total: int                  # so many: the request's ``llm.prefill``
    handed: bool = False        # its lane is taken; its id goes to it


# (program, pool specs, its shapes) -> how its kernel runs; see
# LLMEngine._paged_kernel_mode and _chunk_attention_mode.
_KERNEL_MODES: dict = {}


@jax.jit
def _place_first(firsts, lane, tok, at=None):
    """``firsts`` (``Serving.step``) with a chunk program's argmax id
    at a lane: three device arrays, the lane a traced scalar, so ONE
    tiny program whatever the lane, and no value crosses to the host
    between the chunk and the decode step queued behind it. Where the
    program carried several spans ``tok`` holds an id a span and ``at``
    is the span's index, a device array too."""
    return firsts.at[lane].set(tok if at is None else tok[at])


def _i32(*shape):
    return jax.ShapeDtypeStruct(shape, np.int32)


@functools.lru_cache(maxsize=32)
def _jit_programs(cfg):
    """Process-wide compiled-program cache: (decode step, prefill
    chunk). jax.jit's executable cache is keyed by the wrapped
    callable's identity, so per-engine ``jax.jit(partial(...))``
    wrappers re-trace and re-compile the same (cfg, shapes) program for
    every engine instance — per-block data workers, serve redeploys,
    and tests all pay it. Engines with equal cfg share one pair of
    wrappers instead; donation is per-call, so two live engines sharing
    a program donate only their own pools."""
    model = serving(cfg)
    # Both programs are donated the pools and return them written:
    # every kind's follow the two leading arguments (the parameters and
    # the program's array), as many as the kinds say they have
    # (``LayerKind.rows``); in the chunk a kind with a window has its
    # own after the full kind's table. The pools of what a sequence
    # keeps (``Serving.state``) ride last in both, behind the window
    # kind's pools and its array.
    n = [len(kind.rows) for kind in model.kinds] + [0]
    ns = len(model.state.parts) if model.state is not None else 0
    step_pools = tuple(range(2, 2 + n[0] + n[1] + ns))
    after = 3 + n[0] + n[1] + bool(n[1])
    chunk_pools = tuple(range(2, 2 + n[0])) \
        + tuple(range(3 + n[0], 3 + n[0] + n[1])) \
        + tuple(range(after, after + ns))

    def program(name, fn, **jit_kwargs):
        # The name is what a device trace's ``XLA Modules`` line shows
        # (``jit_<name>``); a functools.partial has none of its own.
        def named(*args, **shapes):
            return fn(*args, cfg=cfg, **shapes)

        named.__name__ = named.__qualname__ = name
        return jax.jit(named, **jit_kwargs)

    # The step program is ``jit_llm_decode`` at every q (one row a lane,
    # or 1 + k under speculation): q is a shape of the program, which
    # takes its one packed array apart by it; ``firsts`` rides as a
    # keyword too, a device array behind the donated pools.
    return (program("llm_decode", model.step, donate_argnums=step_pools,
                    static_argnames=("q",)),
            program("llm_prefill_chunk", model.chunk,
                    donate_argnums=chunk_pools))


class LLMEngine:
    """One model + one KV pool + one step scheduler.

    Thread-safe: add_request() may be called from any thread (serve
    replicas run requests on a thread pool); step() is driven either by
    the background loop (start()) or manually (tests)."""

    def __init__(self, params, cfg, *, num_blocks: int = 64,
                 window_blocks: Optional[int] = None,
                 state_slots: Optional[int] = None,
                 block_size: int = 16, max_batch: int = 8,
                 prefill_chunk_tokens: Optional[int] = None,
                 prefix_cache: bool = True,
                 speculative=None, name: str = "llm",
                 sink: Optional[Callable[[list], None]] = None):
        self.cfg = cfg
        # What the model's module says of serving it: the two programs,
        # the kinds of layer its cache has, its costs (the seam,
        # models/seam.py). Nothing below names a model's fields.
        self.model = serving(cfg)
        self.name = name
        self.max_batch = int(max_batch)
        # prefix_cache -> PrefixPool: freed blocks keep their content
        # hash-indexed so an equal prompt prefix (shared system prompt,
        # multi-turn history, preempt/resume) skips prefill for the
        # cached span. Refcounts + COW keep sharing transparent.
        pool_cls = PrefixPool if prefix_cache else PagedKVCache
        self.kv = pool_cls(cfg, num_blocks=num_blocks,
                           block_size=block_size)
        self._prefix = prefix_cache
        # Read once: a decode step donates the pools, so between dispatch
        # and reassignment a pool is a deleted array to other threads.
        self._device = next(iter(self.kv.pools[0].devices()))
        self._pool_specs = tuple(
            jax.ShapeDtypeStruct(p.shape, p.dtype, sharding=p.sharding)
            for p in self.kv.pools)
        # Sarathi-style chunked prefill admission: at most this many
        # UNCACHED prompt tokens run per step (None = whole prompt at
        # once), so running decode streams emit a token every step even
        # while a long prompt prefills.
        self.prefill_chunk_tokens = (None if prefill_chunk_tokens is None
                                     else int(prefill_chunk_tokens))
        # The parameters as the two programs read them, made once here
        # (the seam's ``Serving.at_rest``: GPT's float32 checkpoint
        # leaves rounded to ``cfg.dtype`` now and not by every step);
        # what was given is not kept. The count says the seam engaged.
        self.params = served_params(params, cfg)
        leaves = jax.tree_util.tree_leaves
        self._params_cast = sum(
            getattr(given, "dtype", None) != kept.dtype
            for given, kept in zip(leaves(params), leaves(self.params)))
        # Fixed decode shapes — one compile: batch padded to max_batch,
        # tables padded to the worst-case blocks/sequence. Prefill
        # recompiles per length bucket (lengths are padded to a block
        # multiple, so at most max_seq/block_size variants), with or
        # without a table. Programs come from the process-wide cache
        # above.
        self.max_nb = self.kv.blocks_for_tokens(self.model.max_seq)
        self._decode, self._prefill_chunk = _jit_programs(cfg)
        # The spans ONE call of the family's chunk program takes (the
        # seam's ``Serving.chunk_spans``), read here once: a step's
        # spans are dispatched in groups of up to so many.
        self._chunk_spans = int(self.model.chunk_spans)
        if self.model.state is not None and speculative is not None:
            raise ValueError(
                "speculative decoding is refused for a model whose "
                "sequences keep a state: a verify step's rejected rows are "
                "rolled back by truncating the block table, and a "
                "state-space layer's state has already moved past them "
                "and cannot be cut back")
        # Speculative decoding (llm/spec.py): when enabled, a decode
        # step scores k+1 rows per lane (fixed q shape, one compile)
        # and the accepted prefix + one corrected/bonus token all land
        # in a single step. None is one row a lane and no proposer.
        self._spec = make_spec(speculative, target_params=self.params,
                               target_cfg=cfg)
        self._q_rows = 1 if self._spec is None else self._spec.k + 1
        # A kind of layer with a window has a pool of its own, in which
        # a lane holds only the blocks that cover its window: a second
        # block table a lane. ``window_blocks`` must hold every lane's
        # window at once, so a grant there never waits on a preemption
        # (parked prefix tails are evicted for it).
        self.kv_window: Optional[WindowPool] = None
        self._win_len = 0
        if len(self.model.kinds) > 1:
            nbw = window_table_len(self.model.kinds[1].window, block_size,
                                   self._q_rows)
            if window_blocks is None:
                window_blocks = 2 * self.max_batch * nbw + 1
            if window_blocks - 1 < self.max_batch * nbw:
                raise ValueError(
                    f"window_blocks {window_blocks} cannot hold "
                    f"{self.max_batch} lanes of {nbw} blocks")
            self.kv_window = WindowPool(cfg, num_blocks=window_blocks,
                                        block_size=block_size)
            self._win_len = nbw
        # What a SEQUENCE keeps (a state-space layer's state) lives in
        # slots: one a lane, held from admission to release, and parked
        # snapshots the prefix index hands to later sequences.
        # ``state_slots`` must give every lane its slot, so a grant
        # waits for nothing but a snapshot another admission has yet to
        # read (parked snapshots are evicted for it).
        self.states: Optional[StatePool] = None
        if self.model.state is not None:
            if self.kv_window is not None:
                raise ValueError("a model with a window kind AND a state "
                                 "is not built: their two answers to a "
                                 "prefix match are not combined")
            if state_slots is None:
                state_slots = 2 * self.max_batch + 1
            if state_slots - 1 < self.max_batch:
                raise ValueError(
                    f"state_slots {state_slots} cannot give {self.max_batch} "
                    f"lanes a slot each (slot 0 is scratch)")
            self.states = StatePool(cfg, state_slots)
        self._kv_window_util_peak = 0.0
        self._window_live = 0         # window blocks lanes hold, last step
        self._counters = {}           # the step program's own, last step
        # The decode program's ONE host array, kept for the engine's
        # lifetime (models/seam.py ``step_columns``): a RUNNING
        # request holds a lane's row of it; a block id goes into the
        # row where the block is granted and out where it is given
        # back, a step writes a lane's ``head`` columns (its rows'
        # tokens, positions and slots, its lengths), and a row goes
        # back to the scratch lane's values when its request leaves.
        # So the host's part of a step costs what changed since the
        # step before, and the jitted call hands over one array: each
        # is a copy to the device and, beside the serving threads, a
        # hand-over of the interpreter lock, 0.85 ms apiece in the chat
        # cell (PERF.md section 6, PRs 30 and 46). A host array, so the
        # pools the step returns stay uncommitted.
        self._cols = step_columns(self._q_rows, self._win_len,
                                  self.states is not None)
        self._inputs = np.zeros(
            (self.max_batch, self._cols.table + self.max_nb), np.int32)
        self._inputs[:, self._cols.context_len:self._cols.head] = 1
        self._free_lanes = list(range(self.max_batch - 1, -1, -1))
        self._inputs_written = 0      # elements written, this step
        # The decode program's other operand, on the device (``firsts``
        # of ``Serving.step``): -1 a lane, and in a step that queues
        # the decode program behind its chunks the id of each greedy
        # prompt that ends in one of them, put at its lane by
        # ``_place_first``. The lanes' indices wait on the device too,
        # so placing one hands over no host value.
        self._no_firsts = jax.numpy.full((self.max_batch,), -1, np.int32)
        self._lane_ids = list(jax.numpy.arange(self.max_batch,
                                               dtype=np.int32))
        # ... and a span's index into its program's ids, where a
        # program carries several.
        self._span_ids = list(jax.numpy.arange(self._chunk_spans,
                                               dtype=np.int32))
        self._pending: List[_Chunk] = []    # dispatched, not seen done

        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._waiting: Deque[Request] = collections.deque()
        self._active: List[Request] = []      # PREFILL/RUNNING, batch order
        # A request is held while it waits or runs, and no longer: a
        # finished one is its caller's to keep (kept here, prompt and
        # all, it was the collector's to walk in every oldest pass).
        self._ids = itertools.count(1)
        self._events: Deque[tuple] = collections.deque(maxlen=4096)
        # (step_idx, (rid, ...)) per step — the in-flight composition
        # trace the batch-recomposition test asserts on.
        self.step_log: Deque[tuple] = collections.deque(maxlen=1024)
        self._steps = 0
        self._last_prefill_count = 0
        self._finished_count = 0
        self._token_times: Deque[tuple] = collections.deque()  # (t, n)
        self._tokens_in_window = 0    # the sum of the deque's n
        self._thread: Optional[threading.Thread] = None
        self._stop = False
        self._fatal: Optional[BaseException] = None   # step loop died
        self._gauges = None
        # Shared idle-decay clock (the PR-10 gauge contract, one
        # implementation for the whole repo): touched per busy publish;
        # idle ticks keep the last busy values until the window lapses,
        # then the series fall to zero instead of freezing.
        from ray_tpu._private.telemetry import GaugeIdleDecay

        self._idle_decay = GaugeIdleDecay()
        self._prefill_chunks = 0      # chunk dispatches (whole=1 chunk)
        self._prefill_spans = 0       # the spans they carried
        self._kv_util_peak = 0.0      # high-water pool utilization
        # Device-step accounting: every step's dispatch->block_until_ready
        # span is timed apart from the host work around it and priced by
        # the shared cost model (util/perfmodel.py) into MFU / HBM-util /
        # roofline-verdict series. The concurrency-net lint holds
        # _run_prefills/_run_decode/step to feeding it.
        # Named host phases and device spans by kind ride the same
        # object (perfmodel.PHASES), so a step's ring entry says which
        # part of the host gap was admission, input building, sampling,
        # emission or publishing, and what the loop did between steps
        # (its wait for this lock, its sleep on an empty engine).
        self._step_perf = perfmodel.StepAccounting(between="llm.between")
        self._arrived = 0       # add_request calls since the last ring entry
        self._preempt_count = 0       # preemptions, all steps
        self._chunk_log: List[list] = []    # this step's chunk programs
        self._span_log: List[list] = []     # and the spans of each
        # lanes, context tokens, decode tokens, lanes decided on the device
        self._counts = (0, 0, 0, 0)
        # Output tokens by where they were decided: a program's own
        # argmax (a greedy request's decode tokens and the first one,
        # from its last prefill chunk) or a logits row sampled on the
        # host (every token of a request with a temperature).
        self._decided = {"device": 0, "host": 0}
        # What the step has decided for the requests that have a
        # consumer, as ``[request, tokens, finish reason or None]`` in
        # the order decided, until ``_hand_over`` gives it to the sink
        # in ONE call: where the settled chunks' first tokens are all
        # decided (before the wait for the decode program, which they do
        # not sit out) and where the decode step's emission ends. The
        # sink runs on the engine's thread under its lock and calls
        # nothing of the engine.
        self._sink = sink
        self._outbox: List[list] = []
        self._handovers = 0           # calls of the sink, this step
        self._tokens_handed = 0       # the tokens they carried

    # -- events ------------------------------------------------------------

    def _event(self, req: Request, state: str):
        req.state = state
        self._events.append((time.time(), req.rid, state))

    def events(self) -> List[tuple]:
        return list(self._events)

    # -- submission --------------------------------------------------------

    def add_request(self, prompt: List[int], max_tokens: int = 16, *,
                    temperature: float = 0.0, top_k: int = 0,
                    seed: int = 0, stop_tokens=(),
                    trace_ctx: Optional[dict] = None,
                    consumer=None) -> Request:
        """Validate + enqueue; returns the Request whose .tokens()
        generator streams the output. With a ``consumer`` the output
        goes to the engine's sink instead: a step calls it with a list
        of ``(request, tokens, finish_reason)`` (the reason None while
        the request runs), one call for all such requests, and the
        consumer rides on the request for the sink to find its reader
        by. Raises if the request could never run (so the
        pool-exhaustion path is always recoverable by preemption, never
        a livelock)."""
        if consumer is not None and self._sink is None:
            raise ValueError("a consumer needs an engine built with a sink")
        if self._fatal is not None:
            raise RuntimeError(
                f"the engine's step loop died: {self._fatal!r}"
            ) from self._fatal
        prompt = [int(t) for t in prompt]
        if not prompt:
            raise ValueError("empty prompt")
        if len(prompt) + max_tokens > self.model.max_seq:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_tokens ({max_tokens}) "
                f"exceeds max_seq {self.model.max_seq}")
        need = self.kv.blocks_for_tokens(len(prompt) + max_tokens)
        if need > self.kv.capacity:
            raise ValueError(
                f"request needs {need} KV blocks; pool capacity is "
                f"{self.kv.capacity} — it could never be admitted")
        if trace_ctx is None:
            # Implicit propagation: inside a traced serve request the
            # replica span is the calling thread's current context.
            from ray_tpu.util import tracing

            trace_ctx = tracing.current_context.get()
        # The prompt's block keys are made here, once, on the caller's
        # thread and outside the engine's lock; a pool that indexes
        # nothing has no use for them.
        chain = (BlockChain(self.kv.block_size, prompt)
                 if self._prefix else None)
        req = Request(rid=next(self._ids), prompt=prompt,
                      max_tokens=int(max_tokens),
                      temperature=float(temperature), top_k=int(top_k),
                      seed=int(seed),
                      stop_tokens=tuple(int(t) for t in stop_tokens),
                      submit_t=time.time(), trace_ctx=trace_ctx,
                      chain=chain, consumer=consumer)
        with self._cond:
            self._waiting.append(req)
            self._arrived += 1
            self._event(req, WAITING)
            self._cond.notify()
        return req

    # -- scheduler ---------------------------------------------------------

    def _admit(self):
        """Move waiting requests into the batch while blocks last.
        FIFO head-of-line: a request that doesn't fit blocks the ones
        behind it (simple + starvation-free given the add_request
        capacity check)."""
        while self._waiting and len(self._active) < self.max_batch:
            req = self._waiting[0]
            seq = self._seq(req)
            if self._prefix:
                # A prefix can be taken up only where every kind of
                # layer still holds what a query there reads: all of it
                # in the full kind, the window behind it in the other.
                upto, tail, snap = None, (0, []), None
                if self.kv_window is not None:
                    upto, *tail = self.kv_window.match_tail(
                        seq, self.kv.match(seq, req.chain), req.chain)
                elif self.states is not None:
                    # ... and where a state at its end is parked. At
                    # least one token is left to compute: a state cannot
                    # be held a position back as a block table can.
                    matched = self.kv.match(seq, req.chain)
                    upto, snap = self.states.match(req.chain, matched,
                                                   len(seq) - 1)
                    if not self._grant_state(req, snap):
                        break
                got = self.kv.admit(seq, len(seq) + 1, upto=upto,
                                    chain=req.chain)
                if got is None:
                    self._return_state(req)
                    break
                grant, cached = got
                if self.states is not None:
                    self.states.take_up(snap, cached, matched)
                req.block_table = grant
                req.cached_tokens = cached
                if self.kv_window is not None:
                    req.window_first, req.window_table = tail
                    self.kv_window.acquire(req.window_table)
                if cached >= len(seq):
                    # Full hit: every token is already resident. Hold
                    # the LAST position back — there is no prefill
                    # output to sample from, so the next decode step
                    # recomputes its logits via write-then-attend
                    # (COW-splitting the shared tail block first).
                    req.context_len = len(seq) - 1
                    req.prefilled_upto = len(seq)
                else:
                    # Cached spans are whole blocks (the exact-tail key
                    # only matches a FULL hit), so the chunked prefill
                    # below resumes block-aligned at `cached`.
                    req.context_len = cached
                    req.prefilled_upto = cached
            else:
                if self.states is not None \
                        and not self._grant_state(req, None):
                    break
                grant = self.kv.alloc(
                    self.kv.blocks_for_tokens(len(seq) + 1))
                if grant is None:
                    self._return_state(req)
                    break
                req.block_table = grant
                req.cached_tokens = 0
                req.context_len = 0
                req.prefilled_upto = 0
            self._waiting.popleft()
            self._active.append(req)
            self._event(req, PREFILL)
            if req.admit_t is None:
                # The end of the request's queue wait (a resume after
                # preemption is not a second arrival).
                req.admit_t = time.time()
            if req.preemptions and req.trace_ctx is not None:
                # Resume after preemption: an instant on the victim's
                # own trace closing the preempt->resume gap.
                tracing.emit("llm.resume", req.trace_ctx,
                             time.time(), 0.0,
                             {"rid": req.rid,
                              "preemptions": req.preemptions})

    def _grant_state(self, req: Request, snap: Optional[int]) -> bool:
        """The lane's slot of the state pools, from admission on (a
        prefill span leaves its state there), and a hold on the parked
        snapshot ``snap`` the request will start from, taken first so
        that the grant does not evict it. False if no slot can be had
        yet (every other one is a lane's or held)."""
        if snap is not None:
            self.states.hold(snap)
        slot = self.states.grant()
        if slot is None:
            if snap is not None:
                self.states.read(snap)
            return False
        req.state_slot, req.state_from = slot, snap
        return True

    def _return_state(self, req: Request):
        """The request's slot goes back (a new tenant starts from zeros
        or from a snapshot, never from what is left there), and its
        hold on a snapshot it never came to read."""
        if req.state_slot is None:
            return
        if req.state_from is not None:
            self.states.read(req.state_from)
        self.states.give_back(req.state_slot)
        req.state_slot = req.state_from = None

    def _activate(self, req: Request):
        """Prefill done, as far as the host's side goes: the request
        enters the decode batch and takes its lane."""
        self._event(req, RUNNING)
        self._take_lane(req)

    def _release_blocks(self, req: Request):
        """Return req's blocks to the pool. With the prefix pool the
        resident span — pool slot j holds seq[j]'s K/V for
        j < context_len — is registered first, so a resumed (or
        identical later) request re-acquires those blocks as cache hits
        instead of recomputing them."""
        seq = None
        if self._prefix:
            seq = self._seq(req, req.context_len)
            self.kv.release(req.block_table, seq=seq, chain=req.chain)
        else:
            self.kv.free(req.block_table)
        if self.kv_window is not None:
            self.kv_window.release(req.window_table, seq=seq,
                                   first=req.window_first, chain=req.chain)
            req.window_table, req.window_first = [], 0
        self._return_state(req)
        self._drop_lane(req)

    @staticmethod
    def _seq(req: Request, n: Optional[int] = None) -> List[int]:
        """The request's tokens, prompt then output, or the first ``n``
        of them: the prompt itself where that is all of it (read, never
        written, by every caller)."""
        if n is None:
            return req.prompt + req.output if req.output else req.prompt
        k = len(req.prompt)
        if n == k:
            return req.prompt
        return req.prompt[:n] if n < k else req.prompt + req.output[:n - k]

    # -- the decode program's array ----------------------------------------

    def _take_lane(self, req: Request):
        """A request that starts to decode takes a free row of the
        packed array and writes its tables there, once."""
        req.lane = self._free_lanes.pop()
        t0 = self._cols.table
        table = req.block_table
        self._inputs[req.lane, t0:t0 + len(table)] = table
        self._inputs_written += len(table)
        if self.kv_window is not None:
            self._write_window(req, 0)
        if self.states is not None:
            self._inputs[req.lane, self._cols.state_slot] = req.state_slot
            self._inputs_written += 1

    def _write_window(self, req: Request, held: int):
        """The lane's window table and its first block, whole: the
        table starts where the window does, so a slide moves every
        entry. ``held`` is how many it held before."""
        c, row = self._cols, self._inputs[req.lane]
        table = req.window_table
        n = max(len(table), held)
        row[c.win_first] = req.window_first
        row[c.win_table:c.win_table + n] = table + [0] * (n - len(table))
        self._inputs_written += 1 + n

    def _blocks_left(self, req: Request, col: int, table, n: int):
        """``n`` blocks went back to the pool off the end of ``table``
        (a rollback's ``truncate``): out of the lane's row too."""
        if n and req.lane is not None:
            at = col + len(table)
            self._inputs[req.lane, at:at + n] = 0
            self._inputs_written += n

    def _drop_lane(self, req: Request):
        """The request leaves the decode batch (a finish, a
        preemption): its row goes back to the scratch lane's values,
        block 0 and context 1, and to the free lanes."""
        if req.lane is None:
            return
        c, row = self._cols, self._inputs[req.lane]
        held = c.table + len(req.block_table)
        row[:held] = 0
        row[c.context_len:c.head] = 1
        self._inputs_written += held
        self._free_lanes.append(req.lane)
        req.lane = None

    def _preempt(self, req: Request):
        """Evict req from the batch, release its blocks (registered in
        the prefix index — resume is then mostly cache hits, not a full
        recompute), requeue at the FRONT (resume priority beats fresh
        admissions — bounds each request's preemption count)."""
        self._active.remove(req)
        self._release_blocks(req)
        for chunk in self._pending:
            if chunk.req is req:
                # Its last chunk is still in flight: the first token
                # is never fetched; the resume decides it again, the
                # same one (the prompt's blocks are indexed above).
                chunk.req = None
        req.block_table = []
        req.context_len = 0
        req.prefilled_upto = 0
        req.cached_tokens = 0
        req.preemptions += 1
        self._preempt_count += 1
        self._waiting.appendleft(req)
        self._event(req, PREEMPTED)
        if req.trace_ctx is not None:
            # Link the eviction back to the VICTIM's trace: its
            # waterfall shows who got preempted and why its tokens
            # stalled (recompute-on-resume).
            tracing.emit("llm.preempt", req.trace_ctx, time.time(), 0.0,
                         {"rid": req.rid,
                          "preemptions": req.preemptions,
                          "kv_util": self.kv.utilization()})

    def _finish(self, req: Request, reason: str):
        if req in self._active:
            self._active.remove(req)
        if req.block_table:
            self._release_blocks(req)
            req.block_table = []
        req.chain = None            # nothing walks it again
        req.finish_reason = reason
        req.finish_t = time.time()
        self._finished_count += 1
        self._event(req, FINISHED)
        if req.consumer is None:
            req.out_q.put(None)
        else:
            self._outgoing(req)[2] = reason

    def _outgoing(self, req: Request) -> list:
        """The request's entry of the coming hand-over: a request's
        tokens of one step are decided in a row, so it is the last one
        or a new one."""
        out = self._outbox
        if not out or out[-1][0] is not req:
            out.append([req, [], None])
        return out[-1]

    def _hand_over(self):
        """Everything decided since the last hand-over leaves in ONE
        call of the sink: one lock and one wake-up on the serving side,
        however many lanes emitted."""
        handed, self._outbox = self._outbox, []
        if handed:
            self._handovers += 1
            self._tokens_handed += sum(len(e[1]) for e in handed)
            self._sink(handed)

    def _emit_token(self, req: Request, tok: int) -> bool:
        """Append an already-decided token (sampled, or an accepted/
        corrected speculative draw — identical by construction), push it
        to the consumer (its queue, or the coming hand-over to the
        sink), apply stop conditions. Returns True if the request
        finished."""
        tok = int(tok)
        req.output.append(tok)
        now = time.time()
        if req.first_token_t is None:
            req.first_token_t = now
        self._token_times.append((now, 1))
        self._tokens_in_window += 1
        if req.consumer is None:
            while req.emitted < len(req.output):
                req.out_q.put(req.output[req.emitted])
                req.emitted += 1
        else:
            self._outgoing(req)[1].extend(req.output[req.emitted:])
            req.emitted = len(req.output)
        if tok in req.stop_tokens:
            self._finish(req, "stop")
            return True
        if len(req.output) >= req.max_tokens:
            self._finish(req, "length")
            return True
        return False

    def _run_prefills(self):
        """Prefill newly admitted requests (prompt lengths are ragged;
        padding to a block multiple bounds recompiles to
        max_seq/block_size variants).

        Two refinements over run-the-whole-prompt:
          * the prefix-cached span was skipped at admission —
            ``prefilled_upto`` starts there, and a FULL hit computes
            nothing at all (the decode step samples it);
          * with ``prefill_chunk_tokens`` set, at most that many
            uncached tokens run per STEP across all prefilling
            requests, the cursor carrying over — decode lanes keep
            emitting a token every step under long-prompt arrivals.

        This loop cuts the budget into SPANS, one a request; the spans
        ride in chunk programs (``_dispatch_spans``), as many in one as
        the family's program takes (``Serving.chunk_spans``): the tail
        of one prompt's body and the head of the next are ONE program,
        which reads the weights once. A group is dispatched when it is
        full, when the next span's context blocks would not fit the
        program's one table of ``max_nb`` blocks beside the group's,
        when a span's result has to be fetched before the decode step
        can be built, and where the loop ends. A family whose program
        takes one span gets groups of one, and is dispatched span by
        span as it always was.

        A program is DISPATCHED here, not awaited: the pools come back
        as futures and go into the next program, so the device runs
        the step's programs in dispatch order with no host between
        them, and what a chunk owes the host waits in ``_pending``
        until the decode program is queued too (``_settle``; a chunk
        that is alone in flight is settled before the decode step is
        built, ``_run_decode``). A greedy prompt that ends here takes
        its lane at once, and its first token can reach the decode
        program on the device (``firsts``): the host knows everything
        else of the new lane. Only a result the host must have before
        it can build the decode step is fetched here: the logits row of
        a request that samples, and a first token the proposer is to
        continue from.
        """
        prefills = [r for r in self._active if r.state == PREFILL]
        self._last_prefill_count = len(prefills)
        bs = self.kv.block_size
        budget = self.prefill_chunk_tokens
        perf = self._step_perf
        group: List[_Span] = []     # bought, and in no program yet
        for req in prefills:
            with perf.phase("llm.prefill.host"):
                t0 = time.time()
                seq = self._seq(req)
                T = len(seq)
                if req.prefilled_upto >= T:
                    # Full prefix-cache hit: zero prefill compute, and
                    # nothing decided yet; the same step's decode
                    # recomputes the last position's logits.
                    self._activate(req)
                    if req.trace_ctx is not None:
                        tracing.emit("llm.prefill", req.trace_ctx, t0, 0.0,
                                     {"rid": req.rid, "tokens": T,
                                      "cached": req.cached_tokens,
                                      "resumed": bool(req.preemptions),
                                      "device_ms": 0.0, "host_ms": 0.0})
                    continue
                if budget is not None and budget <= 0:
                    break   # out of chunk budget; cursor resumes next step
                upto = req.prefilled_upto
                rem = T - upto
                c = rem if budget is None else min(rem, budget)
                if c < rem:
                    # Mid-prompt chunks stay block-aligned (the chunk
                    # program writes whole blocks); a budget below one
                    # block still makes one block of progress.
                    c = (c // bs) * bs or min(bs, rem)
                # A sequence with a state ends a span at its last block
                # boundary, where a snapshot is taken: a ragged prompt
                # runs one short span more behind it.
                snap_at = self._snapshot_at(req, T, upto)
                if upto < snap_at < upto + c:
                    c = snap_at - upto
                if budget is not None:
                    budget -= c
                span = _Span(req, seq, t0, upto, c, -c % bs, T, snap_at)
                # The contexts of a program's spans lie end to end in
                # ONE table of max_nb blocks: two spans behind long
                # documents do not fit one, and go in a program each.
                fits = not group or sum(
                    -(-s.upto // bs) for s in group + [span]) <= self.max_nb
            if not fits:
                self._dispatch_spans(group)
                group = []
            group.append(span)
            # The sampler needs the row, the proposer the token, before
            # the decode step can be built.
            fetch_now = upto + c >= T and (not req.greedy
                                           or self._spec is not None)
            if len(group) == self._chunk_spans or fetch_now:
                self._dispatch_spans(group)
                group = []
                if fetch_now:
                    self._settle()
        if group:
            self._dispatch_spans(group)

    def _dispatch_spans(self, group: List[_Span]):
        """ONE dispatch of the chunk program (``Serving.chunk``) for
        the spans of ``group`` (one span where the family's program
        takes one): it is donated the pools, writes the spans' rows
        into them and returns, a span, the last row's logits and their
        argmax. Two host arrays a program (each is a hand-over of the
        interpreter lock beside the serving threads): the tokens, and
        the table the program reads with the blocks it writes and each
        span's lengths behind it (``pack_span``; ``pack_spans`` where
        the program takes several). A span from the prompt's start has
        no context: alone in its program it has an empty table, and
        attends over itself alone. One ``_Chunk`` a span goes to
        ``_pending``; they share the program's device span, which stays
        open (the wait for it comes when the step's programs are all
        queued), and its row of the step's ``prefill_chunks``."""
        perf = self._step_perf
        bs = self.kv.block_size
        several = self._chunk_spans > 1
        with perf.phase("llm.prefill.host"):
            n = sum(s.c + s.pad for s in group)
            toks = np.zeros((1, n), np.int32)
            row = 0
            for s in group:
                toks[0, row:row + s.c] = s.seq[s.upto:s.upto + s.c]
                row += s.c + s.pad

            def written(s):     # the blocks a span's rows go to
                return s.req.block_table[
                    s.upto // bs:(s.upto + s.c + s.pad) // bs]

            window = ()
            if several:
                table = pack_spans(
                    [(s.req.block_table[:-(-s.upto // bs)], written(s),
                      s.upto, s.c) for s in group],
                    self.max_nb, bs, self._chunk_spans)
            else:
                (s,), req = group, group[0].req
                read = np.zeros((self.max_nb if s.upto else 0,), np.int32)
                if s.upto:
                    read[:len(req.block_table)] = req.block_table
                # A sequence with a state: the slot its span starts from
                # (a parked snapshot's behind a prefix hit, else its
                # own) and its own, which the span's end state goes to.
                state = () if self.states is None else (
                    req.state_slot if req.state_from is None
                    else req.state_from, req.state_slot)
                table = pack_span(read, written(s), s.upto, s.c - 1, *state)
                if self.kv_window is not None:
                    window = (*self.kv_window.pools,
                              self._slide_window(req, s.upto, s.c, s.pad))
                elif self.states is not None:
                    window = self.states.pools
        with perf.dispatch("llm.prefill.device") as program:
            rows, ids, *pools = self._prefill_chunk(
                self.params, toks, *self.kv.pools, table, *window)
        with perf.phase("llm.pools"):
            # Here, under its name, and not when this function
            # returns: the window kind's old arrays live on in
            # ``window``.
            self._take_back(pools)
            del window, pools
        with perf.phase("llm.prefill.host"):
            self._prefill_chunks += 1
            self._prefill_spans += len(group)
            # A PROGRAM's row: [positions computed (padded to whole
            # blocks, as priced), context tokens resident before them
            # (both summed over its spans), ms, of them the host's
            # dispatch]: the last two once the program is seen done.
            log = [n, sum(s.upto for s in group), 0.0, 0.0]
            self._chunk_log.append(log)
            self._span_log.append([s.c + s.pad for s in group])
            for i, s in enumerate(group):
                req, upto = s.req, s.upto + s.c
                req.prefilled_upto = req.context_len = upto
                # Only the UNCACHED span is priced: ctx_tokens covers
                # what was skipped or ran in earlier chunks, keeping MFU
                # honest. Attention is a span's; the weights are read
                # once a program, for all its rows.
                perf.add_cost(perfmodel.prefill_cost(
                    self.cfg, s.c + s.pad, ctx_tokens=s.upto,
                    weight_rows=0 if i else n))
                done = upto >= s.total
                # What the host will fetch of it: the program's argmax
                # id for a greedy request whose prompt ends here, that
                # row of logits for one that samples; of a mid-prompt
                # chunk nothing, its id only says the program is done.
                chunk = _Chunk(
                    req, program, ids if req.greedy or not done else rows,
                    i if several else None, done, log, s.t0, s.c, upto,
                    s.total)
                self._pending.append(chunk)
                if not done:
                    continue
                if self._prefix:
                    # Index the prompt's chunks for later arrivals
                    # (shared system prompts hit from here on): a
                    # program that reads these blocks is queued
                    # behind the one that writes them.
                    self.kv.register(s.seq, req.block_table, req.chain)
                    if self.kv_window is not None:
                        self.kv_window.register_tail(
                            s.seq, req.window_table, req.window_first,
                            req.chain)
                if req.greedy and self._spec is None \
                        and len(req.output) + 1 < req.max_tokens:
                    # The lane's position, slot and tables are host
                    # facts: it is taken now, and the decode step can
                    # be built before the token is seen. (A request
                    # that samples, or feeds a proposer, is settled
                    # first; one whose first token ends it by length
                    # never takes a lane, and finishes where it is
                    # settled.)
                    self._activate(req)
                    chunk.handed = True
        if self.states is not None:
            (s,), req = group, group[0].req
            if req.state_from is not None:
                # The program that reads the parked snapshot is
                # queued: it is the lane's own state from here on.
                with perf.phase("llm.state_restore"):
                    self.states.read(req.state_from)
                    req.state_from = None
            if s.upto + s.c == s.snap_at:
                # Behind the span that left the state there, before
                # the program that moves it on.
                with perf.phase("llm.state_snapshot"):
                    self.states.snapshot(
                        req.chain.reach(s.seq, s.snap_at)
                        .keys[s.snap_at // bs - 1], s.snap_at,
                        req.state_slot)

    def _settle(self):
        """Collect what the dispatched chunk programs owe the host, in
        the device's order. For each PROGRAM: the one wait for it,
        which closes its device span (a ``device_get`` of its ids or
        rows returns when THAT program is done, whatever is queued
        behind it), and its row of the step's chunk log. For each of
        its spans: a prompt's first (or first-since-resume) token,
        decided and emitted then and there, so its TTFT is stamped when
        its chunk is done and not at the step's end; the request's
        ``llm.prefill`` span. A request that was preempted with its
        chunk in flight is owed nothing."""
        perf = self._step_perf
        pending, self._pending = self._pending, []
        for program, chunks in itertools.groupby(pending,
                                                 key=lambda ch: ch.span):
            chunks = list(chunks)
            # What the host needs of the program: its ids (the greedy
            # prompts that end in it), its rows (those that sample),
            # each in one fetch.
            wanted = {id(ch.result): ch.result for ch in chunks
                      if ch.done and ch.req is not None}
            results, got = list(wanted.values()), {}
            with program.waiting():
                if results:
                    got = dict(zip(wanted, jax.device_get(results)))
                else:
                    jax.block_until_ready(chunks[0].result)
            device_s = program.seconds
            chunks[0].log[2:] = [device_s * 1e3,
                                 program.dispatch_seconds * 1e3]
            for chunk in chunks:
                self._settle_chunk(chunk, got, device_s)
        # Then and there: a first token does not sit out the wait for
        # the decode program.
        if self._outbox:
            with perf.phase("llm.emit"):
                self._hand_over()

    def _settle_chunk(self, chunk: _Chunk, got: dict, device_s: float):
        """One span of a settled program (``_settle``): ``got`` holds
        the program's fetched results by the array's id."""
        perf = self._step_perf
        req = chunk.req
        if req is None:
            return
        fetch = chunk.done
        if fetch:
            first = got[id(chunk.result)]
            if chunk.index is not None:
                first = first[chunk.index]
        if fetch and req.greedy:
            self._decided["device"] += 1
        elif fetch:
            # Sampled on the host at the request's absolute
            # position (keyed by (seed, position) alone).
            with perf.phase("llm.sample"):
                self._decided["host"] += 1
                first = sample(
                    first, temperature=req.temperature,
                    top_k=req.top_k, seed=req.seed,
                    position=len(req.prompt) + len(req.output))
        with perf.phase("llm.emit"):
            if fetch:
                if req.lane is None:
                    self._event(req, RUNNING)
                # A first token that ends the request (a stop
                # token, its length) gives back the lane it was
                # handed ahead of time, if any: the row that lane
                # ran in this step is dropped with it.
                if not self._emit_token(req, first) \
                        and req.lane is None:
                    self._take_lane(req)
            if req.trace_ctx is not None:
                dur = time.time() - chunk.t0
                tracing.emit("llm.prefill", req.trace_ctx, chunk.t0,
                             dur,
                             {"rid": req.rid,
                              "tokens": chunk.tokens,
                              "upto": chunk.upto, "total": chunk.total,
                              "cached": req.cached_tokens,
                              "done": chunk.done,
                              "resumed": bool(req.preemptions),
                              "device_ms": round(device_s * 1e3, 3),
                              "host_ms": round(
                                  max(dur - device_s, 0.0) * 1e3, 3)})

    def _take_back(self, pools):
        """The pools a program was donated, as it returned them written:
        the full kind's, then the window kind's or the state's. The
        arrays that were given away lose their last reference here or
        soon after, while the program that took them is still in the
        device's queue: freeing them then is not free (``llm.pools``:
        a third of Laguna's host gap was this, under no name)."""
        n = len(self.kv.pools)
        self.kv.pools = tuple(pools[:n])
        if self.kv_window is not None:
            self.kv_window.pools = tuple(pools[n:])
        elif self.states is not None:
            self.states.pools = tuple(pools[n:])

    def _snapshot_at(self, req: Request, T: int, upto: int) -> int:
        """Where in a sequence of ``T`` tokens, prefilled as far as
        ``upto``, a span has to end for a snapshot of the state: its
        last block boundary, if that is still ahead and the prefix
        index could hand the snapshot on; else 0."""
        if self.states is None or req.chain is None:
            return 0
        at = T // self.kv.block_size * self.kv.block_size
        return at if at > upto else 0

    def _slide_window(self, req: Request, upto: int, c: int, pad: int):
        """The window kind's side of a chunk, on the host and BEFORE
        the chunk's dispatch: returns the kind's array for the chunk
        program (models/laguna.py: the table as the chunk reads it, its
        first block's index, the block each of the chunk's blocks is
        written to). The lane's window then slides to where its next
        query sits, after the chunk, and only the chunk's blocks that
        are still inside it are granted; the leading ones land in the
        scratch block 0. The slide may free a block that the grant
        hands straight back: the program reads every layer's context
        before it writes (its docstring), so the chunk still sees what
        the block held."""
        kvw, bs = self.kv_window, self.kv.block_size
        nd = (c + pad) // bs
        win = np.zeros((self._win_len + 1 + nd,), np.int32)
        win[:len(req.window_table)] = req.window_table
        win[self._win_len] = req.window_first
        req.window_first = kvw.slide(req.window_table, req.window_first,
                                     upto + c)
        b0 = max(upto // bs, kvw.keep_from(upto + c))
        if not req.window_table:
            # Everything before the chunk slid out; where the chunk is
            # longer than the window, so did its own leading blocks,
            # and the table starts again at the first block kept.
            req.window_first = b0
        grant = kvw.alloc((upto + c + pad) // bs - b0)
        if grant is None:
            raise RuntimeError("the window pool cannot hold a lane's "
                               "window: window_blocks is too small")
        req.window_table.extend(grant)
        win[len(win) - len(grant):] = grant
        return win

    def _preempt_for(self, req: Request) -> bool:
        """Free pool blocks by preempting a LIFO victim; req itself is
        the last resort (returns False then — req left the batch)."""
        victims = [r for r in self._active
                   if r.state == RUNNING and r is not req]
        if victims:
            self._preempt(victims[-1])
            return True
        self._preempt(req)
        return False

    def _ensure_slots(self, req: Request, n: int = 1) -> bool:
        """Guarantee req's next ``n`` tokens have WRITABLE pool slots
        (n = 1 for plain decode; 1 + proposals under speculation),
        preempting LIFO victims if the pool is dry. With the
        prefix pool each touched block must also be private: a block
        with co-readers, or one whose registered span covers a write
        offset (the shared partially-filled tail a diverging request
        hits), is COW-split first — the write never corrupts what other
        requests or the index can still read. Returns False if req
        itself was preempted (the last resort when it is the newest —
        and possibly only — sequence)."""
        bs = self.kv.block_size
        c, row = self._cols, self._inputs[req.lane]
        kinds = [(self.kv, req.block_table, 0, c.table)]
        if self.kv_window is not None:
            # The window slides first: blocks the next query no longer
            # keeps go back before new ones are granted, so a lane never
            # holds more than its window's worth.
            held, first = len(req.window_table), req.window_first
            req.window_first = self.kv_window.slide(
                req.window_table, first, req.context_len)
            if req.window_first != first:
                self._write_window(req, held)
            kinds.append((self.kv_window, req.window_table,
                          req.window_first, c.win_table))
        # A block id goes into the lane's row where it is granted (or
        # split off): the row is the table, kept.
        for j in range(n):
            slot = req.context_len + j
            for kv, table, first, col in kinds:
                bi = slot // bs - first
                while True:
                    if bi >= len(table):
                        grant = kv.alloc(1)
                        if grant is None:
                            if not self._preempt_for(req):
                                return False
                            continue
                        table.extend(grant)
                        row[col + bi] = grant[0]
                        self._inputs_written += 1
                    if self._prefix:
                        bid = table[bi]
                        if kv.needs_cow(bid, slot % bs):
                            nb = kv.cow(bid)
                            if nb is None:
                                if not self._preempt_for(req):
                                    return False
                                continue
                            table[bi] = row[col + bi] = nb
                            self._inputs_written += 1
                    break
        return True

    def _roofline_attrs(self, cost, device_s: float, dur: float) -> dict:
        """mfu / hbm_util / verdict span attributes for one step; empty
        on the CPU backend, which has no peak to price against."""
        rl = perfmodel.roofline(cost, device_s, max(dur - device_s, 0.0),
                                hw=self._step_perf.hw)
        if not rl:
            return {}
        return {"mfu": round(rl["mfu"], 4),
                "hbm_util": round(rl["hbm_util"], 4),
                "verdict": rl["verdict"]}

    def _fetch_decisions(self, logits, ids, all_greedy: bool):
        """What the host needs of the decode program's outputs to
        decide every lane's tokens, fetched once a step (the caller
        has blocked on ``ids``, so these are copies, not waits).
        Returns ``(ids, rows)``: the program's argmax ids as Python
        ints, which a greedy lane takes as they are; and the logits,
        or None when every lane is greedy and they stay on the device.
        A lane that samples with a temperature hands sample() its own
        row of them with its (seed, position) key, so no lane's tokens
        depend on what the others asked for."""
        return (jax.device_get(ids).tolist(),
                None if all_greedy else jax.device_get(logits))

    def _run_decode(self):
        """One decode step for every RUNNING sequence: Q rows a lane in
        ONE batched paged-attention forward (models/gpt.py
        forward_step), Q = 1 + k under speculation and 1 without. Row 0
        of a lane feeds its current token and rows 1.. its proposals
        (none without a proposer); all are written into their pool
        slots and scored together. The longest proposal prefix equal to
        the target's own keyed draws is accepted and one corrected/bonus
        token follows it — several output tokens a step at exactly the
        non-speculative token stream (the sampler is keyed by (seed,
        position) alone, so acceptance is an equality check, not a new
        random process), and with no proposals just the step's one
        token. Rejected slots are rolled back with kv.truncate(); the
        fixed [max_batch, Q] shapes compile ONCE, rows and lanes that
        are not live padding onto scratch block 0."""
        spec = self._spec
        perf = self._step_perf
        if len(self._pending) == 1:
            # ONE chunk in flight is awaited before the decode step is
            # built, as every chunk was before PR 47: the host blocks
            # once for it either way, and while it does the serving
            # threads have the interpreter. Queued behind a lone short
            # chunk the decode step left them too little of it: the
            # chat cell's ``ttft_p50_ms`` rose 37% (PERF.md section 6,
            # PR 47). With two or more in flight the decode program is
            # queued behind them and the host waits after that.
            self._settle()
        with perf.phase("llm.slots"):
            batch = [r for r in self._active if r.state == RUNNING]
        props = dict.fromkeys([r.rid for r in batch], ())
        if spec is not None:
            with perf.phase("llm.decode.build"):
                for req in batch:
                    # Proposal budget: never past max_tokens (the final
                    # token is sampled, not proposed), never past the
                    # block span the admission check guaranteed, never
                    # past max_seq positions.
                    budget = min(
                        req.max_tokens - len(req.output) - 1,
                        len(req.prompt) + req.max_tokens
                        - req.context_len - 1,
                        self.model.max_seq - req.context_len - 1)
                    props[req.rid] = spec.propose(
                        req.rid, req.prompt + req.output, budget)
        with perf.phase("llm.slots"):
            for req in list(batch):
                if req.state == RUNNING:
                    self._ensure_slots(req, 1 + len(props[req.rid]))
            # An ensure call may have preempted requests anywhere in the
            # batch (LIFO victims) — only still-RUNNING sequences decode.
            batch = [r for r in batch if r.state == RUNNING]
        if not batch:
            self._settle()
            return
        with perf.phase("llm.decode.build"):
            t0 = time.time()
            B, n_live = self.max_batch, len(batch)
            Q = self._q_rows
            bs = self.kv.block_size
            kvw = self.kv_window
            if kvw is not None:
                self._window_live = kvw.capacity - kvw.num_free
            # What a step writes of the kept array: each live lane's
            # ``head`` columns (``step_columns``), in one assignment.
            # Its tables are in its row already (``_take_lane``,
            # ``_ensure_slots``); the other lanes' rows, and a lane's
            # rows past its q_len, are the scratch lane's values
            # (block 0, offset 0, position 0; a padded lane is one row
            # of context 1, whose attention over the scratch block's
            # garbage is masked-in but whose logits are never read).
            lanes, heads, ctx, rows_per_lane = [], [], [], []
            for req in batch:
                slot = req.context_len
                table = req.block_table
                p = props[req.rid]
                n = 1 + len(p)
                pad = (0,) * (Q - n)
                rows = range(slot, slot + n)
                # Row 0: steady-state lanes feed their last sampled
                # token; a FULL prefix-cache hit enters decode holding
                # the last sequence position back (nothing was computed
                # at admission), so its first step re-feeds that token —
                # write-then-attend then recomputes its logits for the
                # first sample. A lane whose prompt ended in a chunk
                # of this step has its token on the device (``firsts``
                # puts it in the row's place; the host has not seen
                # it, so the row says 0). Rows 1..n-1 feed the lane's
                # proposals (the proposal budget keeps them inside
                # max_seq).
                fed = slot - len(req.prompt)
                head = [req.prompt[slot] if fed < 0 else
                        req.output[fed] if fed < len(req.output) else 0,
                        *p, *pad,
                        *rows, *pad,
                        *[table[s // bs] for s in rows], *pad,
                        *[s % bs for s in rows], *pad]
                if kvw is not None:
                    wt, first = req.window_table, req.window_first
                    head += [*[wt[s // bs - first] for s in rows], *pad]
                head += [slot + n, n]
                lanes.append(req.lane)
                heads.append(head)
                ctx.append(slot + n)
                rows_per_lane.append(n)
                if spec is not None:
                    spec.verify(req.rid, len(p))
            self._inputs[lanes, :self._cols.head] = heads
            self._inputs_written += n_live * self._cols.head
            if spec is not None:
                spec.verify_steps += 1
            # Priced honestly about speculation's bet: every scored row
            # burns its FLOPs whether or not its token is accepted.
            cost = perfmodel.decode_step_cost(self.cfg, ctx, rows_per_lane)
            greedy = [r.greedy for r in batch]
            on_device = sum(greedy)
            self._counts = (n_live, sum(ctx), sum(rows_per_lane), on_device)
            # The ids of the prompts that end in a chunk still in
            # flight go to their lanes on the device.
            firsts = self._no_firsts
            for chunk in self._pending:
                if chunk.handed and chunk.req is not None:
                    firsts = _place_first(
                        firsts, self._lane_ids[chunk.req.lane],
                        chunk.result, *(() if chunk.index is None
                                        else (self._span_ids[chunk.index],)))
        # ONE host array goes in beside the parameters and the pools:
        # the kept one, copied in the call (a write after it, a lane
        # given back while the chunks are settled, is not seen), and
        # ``firsts`` from the device. With the call back, every program
        # of the step is in the device's queue, and only now does the
        # host wait: for each chunk in turn, then for the ids.
        # block_until_ready on them bounds the DEVICE span (they are
        # the program's last output: the argmax of its logits); the
        # fetch that follows is then a copy of max_batch x Q ints,
        # charged to the host, and the logits stay where they are unless
        # a lane samples with a temperature.
        with perf.dispatch("llm.decode.device") as span:
            window = kvw.pools if kvw is not None else \
                self.states.pools if self.states is not None else ()
            logits, ids, *pools = self._decode(
                self.params, self._inputs, *self.kv.pools, *window, q=Q,
                firsts=firsts)
        with perf.phase("llm.pools"):
            self._take_back(pools)
        self._settle()
        with span.waiting():
            jax.block_until_ready(ids)
        device_s = span.seconds
        perf.add_cost(cost)
        sampling, emitting = perf.phase("llm.sample"), perf.phase("llm.emit")
        with sampling:
            ids, rows = self._fetch_decisions(logits, ids,
                                              on_device == n_live)
            # The program's own counters ride in that fetch, as the
            # rows after the lanes'.
            self._counters = {name: ids[B + i][0] for i, name
                              in enumerate(self.model.counters)}
        # Lane by lane, each token out the moment it is decided: for a
        # greedy lane that is now, so for an all-greedy batch this loop
        # is emission alone (~1 ms for 64 lanes on the chip) and every
        # finish leaves in the step's last milliseconds; a closed-loop
        # caller's next request then waits out the coming step, which
        # handing finishing lanes their token first did not change
        # (PERF.md section 6, PR 29). A lane with a temperature is
        # sampled in its turn, so its draw delays only the lanes after
        # it.
        emitted_total = 0
        decided = [0, 0]                # by the host, by the device
        for i, req in enumerate(batch):
            if req.state != RUNNING:
                continue    # its first token, settled above, ended it
            p = props[req.rid]
            slot = req.context_len
            lane = req.lane             # a finish below gives it back
            if greedy[i]:
                # The target's greedy draw at row j is the id the
                # program returned for it: acceptance is the same
                # equality check, on integers.
                n_acc, emitted = accept_draws(ids[lane].__getitem__, p)
            else:
                with sampling:
                    n_acc, emitted = verify_tokens(
                        rows[lane, :1 + len(p)], p,
                        temperature=req.temperature, top_k=req.top_k,
                        seed=req.seed,
                        start_pos=len(req.prompt) + len(req.output))
            with emitting:
                if spec is not None:
                    spec.accept(req.rid, n_acc, len(p), len(emitted))
                    emitted_total += len(emitted)
                for idx, tok in enumerate(emitted):
                    # Bookkeeping BEFORE emitting: an accepted token IS
                    # resident (its slot was written this step), the
                    # final corrected/bonus token is NOT (its draw
                    # replaced a rejected row / was never written) — so
                    # a mid-stream finish registers exactly the resident
                    # span.
                    if idx < n_acc:
                        req.context_len = slot + 2 + idx
                    else:
                        req.context_len = slot + 1 + n_acc
                    decided[greedy[i]] += 1
                    if self._emit_token(req, tok):
                        break       # a stop token: the rest is dropped
                if len(p) > n_acc:
                    # Rejected slots past the accept cursor: any whole
                    # blocks they spilled into go back to the pool (a
                    # finished lane already released everything).
                    freed = (self.kv.truncate(req.block_table,
                                              req.context_len)
                             if req.block_table else [])
                    self._blocks_left(req, self._cols.table,
                                      req.block_table, len(freed))
                    if kvw is not None and req.window_table:
                        self._blocks_left(
                            req, self._cols.win_table, req.window_table,
                            len(kvw.truncate(
                                req.window_table, req.context_len,
                                req.window_first)))
                    spec.rollback(req.rid, len(p) - n_acc, len(freed))
        with emitting:
            self._hand_over()
        self._decided["host"] += decided[0]
        self._decided["device"] += decided[1]
        dur = time.time() - t0
        extra = {} if spec is None else {
            "spec_proposed": sum(len(props[r.rid]) for r in batch),
            "spec_emitted": emitted_total}
        with perf.phase("llm.trace"):
            self._trace_decode_step(batch, t0, dur, cost, device_s, **extra)

    def _trace_decode_step(self, batch, t0, dur, cost, device_s, **extra):
        """One decode-step slice per TRACED sequence in the batch: the
        request's waterfall shows its token cadence, and every slice
        carries the step's batch composition + pool pressure + the
        device-vs-host split and roofline verdict for THIS step."""
        traced = [r for r in batch if r.trace_ctx is not None]
        if not traced:
            return
        breakdown = {
            "step": self._steps + 1,
            "prefill": self._last_prefill_count,
            "decode": len(batch), "kv_util": self.kv.utilization(),
            **extra,
            "device_ms": round(device_s * 1e3, 3),
            "host_ms": round(max(dur - device_s, 0.0) * 1e3, 3),
            **self._roofline_attrs(cost, device_s, dur),
        }
        for req in traced:
            tracing.emit("llm.decode_step", req.trace_ctx, t0, dur,
                         dict(breakdown, rid=req.rid))

    def step(self) -> int:
        """One scheduler iteration: admit -> prefill -> one decode step
        for every running sequence (one token each; with speculation on
        it may emit several). The step's programs, its chunks and (behind
        two chunks or more) the decode program, are dispatched back to
        back and only then awaited, in that order (``_run_prefills``,
        ``_settle``).
        Returns the number of in-flight sequences after the step."""
        perf = self._step_perf
        t_lock = time.perf_counter()
        with self._lock:
            perf.lock_waited(t_lock)
            perf.begin()
            self._chunk_log, self._span_log = [], []
            self._counts = (0, 0, 0, 0)
            self._counters = {}
            self._inputs_written = 0
            self._handovers = self._tokens_handed = 0
            preempted0 = self._preempt_count
            with perf.step("llm.step", self._steps + 1):
                with perf.phase("llm.admit"):
                    self._admit()
                # High-water utilization INSIDE the step: post-admission
                # and post-decode, before finishes drain it — the
                # end-of-run stats() reading alone always relaxes back
                # to ~0 (every block freed), which is why an end-of-run
                # reader saw 0.0 for years.
                util_hw = self.kv.utilization()
                self._run_prefills()
                self._run_decode()
                self._kv_util_peak = max(self._kv_util_peak, util_hw,
                                         self.kv.utilization())
                window = {}
                if self.kv_window is not None:
                    # Taken before the step's finishes release theirs.
                    self._kv_window_util_peak = max(
                        self._kv_window_util_peak, self._window_live
                        / max(1, self.kv_window.capacity))
                    window["window_blocks_live"] = self._window_live
                if self.states is not None:
                    # The state pools' counters, as they stand at the
                    # step's end (``StatePool.stats``).
                    window.update(self.states.stats())
                self._steps += 1
                with perf.phase("llm.publish"):
                    self.step_log.append(
                        (self._steps, tuple(r.rid for r in self._active)))
                    # The step-derived gauges carry the last CLOSED
                    # step: publishing is part of the step it ends.
                    self._publish_gauges()
            # Finalize the step breakdown (None on a no-work step) into
            # the process-local device-step ring, where the benchmark
            # and the gang profiler (`rtpu profile --device`) read it.
            # Counts are the scheduler's own, taken where it has them.
            lanes, context_tokens, decode_tokens, on_device = self._counts
            chunks = self._chunk_log
            entry = perf.finish(
                record_as="llm.step",
                attrs={"deployment": self.name, "step": self._steps,
                       "arrived": self._arrived,
                       "lanes": lanes, "max_batch": self.max_batch,
                       perfmodel.DEVICE_SAMPLED: on_device,
                       "context_tokens": context_tokens,
                       "decode_tokens": decode_tokens,
                       "prefill_tokens": sum(c[0] for c in chunks),
                       # A row a chunk PROGRAM, and beside it the
                       # rows of each span the program carried.
                       "prefill_chunks": chunks,
                       "prefill_spans": self._span_log,
                       "waiting": len(self._waiting),
                       "preempted": self._preempt_count - preempted0,
                       # Elements of the decode program's kept array
                       # that the host wrote in this step, and the
                       # array's size: the step's host side costs what
                       # changed, not what is live.
                       "inputs_written": self._inputs_written,
                       "inputs_size": self._inputs.size,
                       # Calls of the sink in this step and the tokens
                       # they carried: how many tokens leave the engine
                       # for one lock and one wake-up behind it (both 0
                       # where no request has a consumer).
                       "handovers": self._handovers,
                       "tokens_handed": self._tokens_handed,
                       **window, **self._step_counters()})
            if entry is not None:
                self._arrived = 0       # callers wait for this lock
            return len(self._active)

    def _step_counters(self) -> dict:
        """The step program's counters as the ring entry carries them:
        a name that ends in ``_x1000`` is a ratio, sent as an integer."""
        return {(k[:-6] if k.endswith("_x1000") else k):
                (v / 1000.0 if k.endswith("_x1000") else v)
                for k, v in self._counters.items()}

    # -- introspection / telemetry ----------------------------------------

    def tokens_per_s(self, window: float = 5.0) -> float:
        """Tokens emitted in the last ``window`` seconds over the time
        since the oldest of them: the deque's sum is kept beside it
        (added on append, taken off on popleft), so a reading costs
        what fell out of the window, not what is in it."""
        now = time.time()
        times = self._token_times
        while times and times[0][0] < now - window:
            self._tokens_in_window -= times.popleft()[1]
        if not times:
            return 0.0
        return self._tokens_in_window / max(now - times[0][0], 1e-3)

    def _program_specs(self):
        """What both programs take, as shapes: (the parameters, the
        window kind's pools or the state's, or none where the model has
        neither)."""
        params = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=x.sharding),
            self.params)
        more = self.kv_window or self.states
        if more is None:
            return params, ()
        return params, tuple(jax.ShapeDtypeStruct(p.shape, p.dtype)
                             for p in more.pools)

    def _paged_kernel_mode(self) -> str:
        """"compiled" if the decode program this engine steps with
        carries the Mosaic kernel, "interpret" if the Pallas
        interpreter's plain ops stand in for it. Observed, not inferred
        from the backend: the program is lowered once at this engine's
        own shapes and its text searched for the kernel's custom
        call."""
        B, Q = self.max_batch, self._q_rows
        key = (self._decode, self._pool_specs, B, Q)
        mode = _KERNEL_MODES.get(key)
        if mode is None:
            params, window = self._program_specs()
            text = self._decode.lower(
                params, _i32(*self._inputs.shape), *self._pool_specs,
                *window, q=Q, firsts=_i32(B)).as_text()
            mode = _KERNEL_MODES[key] = (
                "compiled" if "tpu_custom_call" in text else "interpret")
        return mode

    def _chunk_attention_mode(self) -> str:
        """What a prefill chunk's attention runs as: "compiled" if the
        chunk program this engine dispatches carries the ``chunk_attn``
        Mosaic kernel (ops/pallas/chunk_attention.py), "interpreted" if
        the Pallas interpreter's plain ops stand in for it (CPU tests
        only), "xla" if the model's chunk calls no such kernel
        (models/gpt.py). Observed as ``_paged_kernel_mode`` observes:
        the program is traced and lowered once, at this engine's
        shapes and its shortest chunk (one block)."""
        chunk = _jit_programs(self.cfg)[1]
        key = (chunk, self._pool_specs, self.max_nb)
        mode = _KERNEL_MODES.get(key)
        if mode is None:
            params, window = self._program_specs()
            if self.kv_window is not None:
                # the kind's array: its table, first, one block
                window += (_i32(self._win_len + 2),)
            # the table, one block written, ctx_len, last (and a state's
            # two slots); or four numbers a span (``pack_spans``)
            table = self.max_nb + 1 + (
                4 * self._chunk_spans if self._chunk_spans > 1
                else 2 + 2 * (self.states is not None))
            traced = chunk.trace(
                params, _i32(1, self.kv.block_size), *self._pool_specs,
                _i32(table), *window)
            if "name=chunk_attn" not in str(traced.jaxpr):
                mode = "xla"
            elif 'kernel_name = "chunk_attn"' in traced.lower().as_text():
                mode = "compiled"
            else:
                mode = "interpreted"
            _KERNEL_MODES[key] = mode
        return mode

    def stats(self) -> dict:
        dev = self._device
        out = {
            # Where the pools live, and whether the paged kernel is
            # compiled for that device or run by the Pallas interpreter
            # (CPU tests only) — so a caller can see a CPU-served model.
            "platform": dev.platform,
            "device_kind": dev.device_kind,
            "paged_kernel": self._paged_kernel_mode(),
            # And a prefill chunk's attention: the ``chunk_attn`` kernel
            # compiled or interpreted, or "xla" where the model's chunk
            # has none.
            "chunk_attention": self._chunk_attention_mode(),
            # The parameters at rest on the device, and how many leaves
            # of the given tree the seam cast to get them (0: served as
            # given).
            "param_bytes_at_rest": sum(
                x.nbytes for x in jax.tree_util.tree_leaves(self.params)),
            "param_leaves_cast": self._params_cast,
            "steps": self._steps,
            "waiting": len(self._waiting),
            "in_flight": len(self._active),
            "finished": self._finished_count,
            "kv_utilization": self.kv.utilization(),
            "kv_util_peak": self._kv_util_peak,
            "kv_free_blocks": self.kv.num_free,
            **({} if self.kv_window is None else {
                # The kind of layer with a window: its own pool.
                "kv_window_utilization": self.kv_window.utilization(),
                "kv_window_util_peak": self._kv_window_util_peak,
                "kv_window_blocks_slid": self.kv_window.slid_blocks}),
            # What the model's sequences keep: the slots' counters.
            **({} if self.states is None else self.states.stats()),
            "tokens_per_s": self.tokens_per_s(),
            # Chunk programs dispatched, and the spans they carried
            # (equal where the family's program takes one span).
            "prefill_chunks": self._prefill_chunks,
            "prefill_spans": self._prefill_spans,
            # The step program's own counters (``kv_pages_in_runs``, a
            # model's expert counts) where the newest step decoded.
            **self._step_counters(),
            # Output tokens by where they were decided (see __init__).
            "tokens_decided_on_device": self._decided["device"],
            "tokens_decided_on_host": self._decided["host"],
            # Cumulative: how long, and how often, the loop slept on an
            # empty engine; the process's collector, {generation:
            # [passes, seconds]}; the process's interpreter probe
            # (samples, their lateness, how many found it held, long
            # ones by standstill and held). Two readings give a window's.
            "idle_s": self._step_perf.idle_total_s,
            "idle_waits": self._step_perf.idle_waits,
            "gc": perfmodel.gc_totals(),
            "interp": perfmodel.interp_totals(),
        }
        if self._prefix:
            ps = self.kv.prefix_stats()
            out["kv_cache_hit_rate"] = ps["hit_rate"]
            out["kv_shared_blocks"] = ps["shared_blocks"]
            out["prefix"] = ps
        if self._spec is not None:
            ss = self._spec.stats()
            out["spec_accept_rate"] = ss["accept_rate"]
            out["spec_tokens_per_step"] = ss["tokens_per_step"]
            out["spec"] = ss
        if self._step_perf.last is not None:
            out["last_step"] = dict(self._step_perf.last)
        return out

    def _publish_gauges(self):
        """Gauge writes onto the telemetry plane (ride the worker 1s
        flusher -> node user_metrics -> head sampler series
        llm_tokens_per_s:<dep>, llm_mfu:<dep>, llm_host_gap_ms:<dep>,
        ...). Called per step AND from the background loop's idle ticks,
        so a drained engine's series fall to zero instead of freezing at
        their last busy value."""
        try:
            if self._gauges is None:
                from ray_tpu.util.metrics import Gauge

                keys = ("deployment",)
                self._gauges = (
                    Gauge("rtpu_llm_tokens_per_s",
                          "Generated tokens/s (5s window)", tag_keys=keys),
                    Gauge("rtpu_llm_kv_util",
                          "Paged KV pool utilization [0,1]", tag_keys=keys),
                    Gauge("rtpu_llm_batch_size",
                          "Sequences in the in-flight batch", tag_keys=keys),
                    Gauge("rtpu_llm_step_ms",
                          "Last step wall time (ms)", tag_keys=keys),
                    Gauge("rtpu_llm_device_ms",
                          "Last step device time, dispatch to "
                          "block_until_ready (ms)", tag_keys=keys),
                    Gauge("rtpu_llm_host_gap_ms",
                          "Last step host time around the device span "
                          "(ms)", tag_keys=keys),
                    Gauge("rtpu_llm_mfu",
                          "Model FLOPs utilization of the last step's "
                          "device span [0,1]", tag_keys=keys),
                    Gauge("rtpu_llm_hbm_util",
                          "HBM-bandwidth utilization of the last step's "
                          "device span [0,1]", tag_keys=keys),
                    Gauge("rtpu_llm_kv_hit_rate",
                          "Prefix-cache hit rate (cached / looked-up "
                          "tokens) [0,1]", tag_keys=keys),
                    Gauge("rtpu_llm_kv_shared_blocks",
                          "KV blocks referenced by >1 sequence",
                          tag_keys=keys),
                    Gauge("rtpu_llm_prefill_chunks",
                          "Cumulative prefill chunk dispatches",
                          tag_keys=keys),
                    Gauge("rtpu_llm_spec_accept_rate",
                          "Speculative-decode proposal acceptance rate "
                          "[0,1]", tag_keys=keys),
                    Gauge("rtpu_llm_spec_tokens_per_step",
                          "Output tokens per verify step per lane "
                          "(1.0 = plain decode, up to k+1)",
                          tag_keys=keys),
                    Gauge("rtpu_llm_roofline_verdict",
                          "Coded roofline verdict of the last step "
                          "(1=compute, 2=hbm, 3=host; 0=idle)",
                          tag_keys=keys),
                    Gauge("rtpu_llm_state_slots_live",
                          "State slots that lanes hold (0: the model's "
                          "sequences keep no state)", tag_keys=keys),
                    Gauge("rtpu_llm_state_snapshots_parked",
                          "Parked state snapshots the prefix index can "
                          "hand on", tag_keys=keys),
                )
            tags = {"deployment": self.name}
            (tps, util, bsz, step_ms, dev_ms, gap_ms, mfu,
             hbm, hitr, shared, chunks, s_acc, s_tps,
             verd, st_live, st_parked) = self._gauges
            # Shared idle-decay clock: a busy publish touches it; idle
            # ticks keep the last busy values until the window lapses,
            # then every step-derived series reads zero.
            busy = bool(self._active)
            if busy:
                self._idle_decay.touch("gauges")
            live = busy or not self._idle_decay.expired("gauges")
            tps.set(self.tokens_per_s(), tags=tags)
            util.set(self.kv.utilization(), tags=tags)
            bsz.set(float(len(self._active)), tags=tags)
            if self.states is not None:
                st = self.states.stats()
                st_live.set(float(st["state_slots_live"]), tags=tags)
                st_parked.set(float(st["state_snapshots_parked"]),
                              tags=tags)
            if live:
                hitr.set(self.kv.hit_rate() if self._prefix else 0.0,
                         tags=tags)
                shared.set(float(self.kv.shared_blocks())
                           if self._prefix else 0.0, tags=tags)
                chunks.set(float(self._prefill_chunks), tags=tags)
                s_acc.set(self._spec.accept_rate()
                          if self._spec is not None else 0.0, tags=tags)
                s_tps.set(self._spec.tokens_per_step()
                          if self._spec is not None else 0.0, tags=tags)
            else:
                hitr.set(0.0, tags=tags)
                shared.set(0.0, tags=tags)
                chunks.set(0.0, tags=tags)
                s_acc.set(0.0, tags=tags)
                s_tps.set(0.0, tags=tags)
            perf = self._step_perf.last if live else None
            if perf is None:
                # Idle past the decay window (or no accounted step
                # yet): the breakdown series decay to zero with the
                # engine, mirroring tokens_per_s.
                perf = {"step_ms": 0.0, "device_ms": 0.0,
                        "host_gap_ms": 0.0, "mfu": 0.0, "hbm_util": 0.0}
            step_ms.set(perf["step_ms"], tags=tags)
            dev_ms.set(perf["device_ms"], tags=tags)
            gap_ms.set(perf["host_gap_ms"], tags=tags)
            if self._step_perf.hw is not None:
                # Utilization and verdict need a peak: the CPU backend
                # has none and publishes counts and times only.
                mfu.set(perf["mfu"], tags=tags)
                hbm.set(perf["hbm_util"], tags=tags)
                verd.set(_VERDICT_CODE.get(perf.get("verdict"), 0.0),
                         tags=tags)
        except Exception:  # noqa: BLE001 - telemetry is best-effort
            pass

    # -- background loop ---------------------------------------------------

    def start(self):
        if self._thread is not None:
            return
        self._stop = False
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name=f"llm-engine-{self.name}")
        self._thread.start()

    def _loop(self):
        perf = self._step_perf
        while True:
            t_lock = time.perf_counter()
            with self._cond:
                perf.lock_waited(t_lock)
                while not self._stop and not self._waiting \
                        and not self._active:
                    with perf.idle("llm.idle"):
                        self._cond.wait(timeout=0.5)
                    # Idle tick: keep publishing so the telemetry series
                    # (tokens/s, batch size, step breakdown) fall to
                    # zero when the engine drains instead of freezing at
                    # their last busy values.
                    if not self._stop and not self._waiting \
                            and not self._active:
                        self._publish_gauges()
                if self._stop:
                    return
            try:
                self.step()
            except BaseException as e:
                # A step that raises (a kernel that fails to compile, a
                # device out of memory) must not leave the consumers
                # parked on their queues with a dead loop behind them:
                # every request ends with finish_reason "error", new
                # ones are refused, and the exception propagates.
                self._fatal = e
                self._release_consumers("error")
                raise

    def stop(self):
        with self._cond:
            self._stop = True
            self._cond.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=10)
            self._thread = None
        self._release_consumers("aborted")

    def _release_consumers(self, reason: str):
        """End every in-flight and waiting request so no consumer stays
        parked on its queue or its stream (shutdown, or a step loop
        that died)."""
        with self._lock:
            for req in list(self._active) + list(self._waiting):
                self._finish(req, reason)
            self._waiting.clear()
            self._hand_over()
