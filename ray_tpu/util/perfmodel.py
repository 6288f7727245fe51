"""Analytic device-step cost model: FLOPs, HBM bytes, MFU, roofline.

The runtime's single source of FLOP/byte truth. Two consumers share
it so they can never disagree:

  * the LLM engine (llm/engine.py) prices every prefill/decode step it
    dispatches and publishes continuous ``llm_mfu`` / ``llm_hbm_util``
    telemetry series,
  * the train session (train/session.py) prices wrapped train steps
    into ``train_*`` equivalents.

Cost formulas (decoder-only transformer, GPTConfig shapes):

  matmul weights  W  = L*(wq + wk + wv + wo + wi + wm) + unembed
                     = L*(m*h*d + 2*m*hk*d + h*d*m + 2*m*f) + V*m
  forward/token   2*W + 4*m*L*C          (C = attention context length;
                                          q@K^T and attn@V are 2*m*C
                                          MACs/layer each)
  prefill(T)      2*W*T + 2*m*L*T*(T+1)  (causal: position i attends
                                          i+1 keys; sum -> T*(T+1)/2)
  train/token     6*N + 12*L*m*T         (the classic 6N fwd+bwd rule
                                          over ALL params N, plus the
                                          quadratic attention term —
                                          unchanged from the original
                                          GPTConfig.flops_per_token)

HBM traffic (the decode roofline's denominator — decode is weight- and
KV-bound, not compute-bound):

  decode step     W reads (weights stream once per step, amortized over
                  the whole batch) + KV reads (2*L*C_i*hk*d per lane) +
                  KV writes (2*L*hk*d per lane), at the pool dtype width
  prefill(T)      weight read + 2x KV write for T tokens (activations
                  ignored: they stay resident in VMEM at these shapes)
  train step      ~(fwd read + bwd read + grad write + adam m/v
                  read+write + param write) = 8 passes over N params
                  (f32) + 2 bytes/activation element saved for the
                  backward (bf16, ~14*m per token per layer without
                  remat) — a documented approximation, good to the
                  factor-of-two a roofline verdict needs.

Hardware peaks are per chip and keyed by the ``device_kind`` jax
reports. An accelerator that is not in the table is an error, and the
CPU backend has no peak at all: a CPU run publishes counts and times,
never an MFU, an HBM utilization or a roofline verdict.
"""

from __future__ import annotations

import collections
import contextlib
import gc
import sys
import threading
import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence

# ---------------------------------------------------------------------------
# Hardware peak table
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class HardwarePeak:
    name: str
    flops_per_s: float       # dense bf16 peak, per chip
    hbm_bytes_per_s: float   # HBM bandwidth, per chip


# Keyed by ``jax.devices()[0].device_kind``. Source: Google Cloud
# documentation, "TPU v5e" (197 TFLOP/s bf16, 16 GB of HBM at 819 GB/s).
V5E = "TPU v5 lite"
HARDWARE_PEAKS: Dict[str, HardwarePeak] = {
    V5E: HardwarePeak(V5E, 197e12, 819e9),
}


def detect_hardware(device=None) -> Optional[HardwarePeak]:
    """Peak entry for ``device`` (default: the first local jax device).

    None on the CPU backend — there is no peak to price a CPU run
    against. An accelerator whose ``device_kind`` is not in the table
    raises: pricing it as some other chip would publish wrong numbers.
    """
    if device is None:
        import jax

        device = jax.devices()[0]
    if device.platform == "cpu":
        return None
    try:
        return HARDWARE_PEAKS[device.device_kind]
    except KeyError:
        raise ValueError(
            f"unknown accelerator {device.device_kind!r} (platform "
            f"{device.platform!r}): add its published peaks to "
            f"perfmodel.HARDWARE_PEAKS") from None


# ---------------------------------------------------------------------------
# Model-shape constants (cached per config — the decode hot path calls
# these every step)
# ---------------------------------------------------------------------------

def _shape(cfg) -> dict:
    """The model's cost description, from the serving seam
    (models/__init__.py: ``serving(cfg).cost``, cached there per
    configuration): matmul weights a token passes, the attention
    coefficient a context position of a decode row and of a chunk's
    row (``attn_per_ctx``, ``chunk_attn_per_ctx``: a model with latent
    attention serves the two in different forms) and what a chunk pays
    once a context token (``chunk_ctx_ops``: taking a latent row up to
    keys and values; 0 where the cache holds keys), for layers with a
    window ``attn_windows`` ((coefficient, window) pairs), parameters in
    all, parameters a step of n rows streams, bytes a parameter, cache
    elements a token; and, only where the model's sequences keep a
    state, ``state_bytes_per_seq`` (a slot's bytes, moved in and out a
    lane a step), ``state_ops_per_row`` (a decode row's state update)
    and ``scan_ops_per_row`` (a chunk's row in the chunked scan); and,
    only where the residual path is more than an addition,
    ``stream_bytes_per_row`` and ``stream_ops_per_row`` (what a row
    moves and computes on that path in all layers: models/xing4.py)."""
    from ..models import serving

    return serving(cfg).cost


def _attn_flops(s: dict, q: float, ctx: float) -> float:
    """Attention FLOPs of q rows that end a context of ctx tokens
    (causal within the rows): layers that keep every token see
    q*ctx - q*(q-1)/2 contexts, a layer with a window at most its
    window a row."""
    seen = q * ctx - q * (q - 1) / 2.0
    return s["attn_per_ctx"] * seen + sum(
        coef * min(seen, q * w) for coef, w in s["attn_windows"])


# ---------------------------------------------------------------------------
# Step costs
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class StepCost:
    flops: float
    hbm_bytes: float
    tokens: int = 0

    def __add__(self, other: "StepCost") -> "StepCost":
        return StepCost(self.flops + other.flops,
                        self.hbm_bytes + other.hbm_bytes,
                        self.tokens + other.tokens)


ZERO_COST = StepCost(0.0, 0.0, 0)


def train_flops_per_token(cfg, seq: Optional[int] = None) -> float:
    """fwd+bwd training FLOPs per token: 6*N + 12*L*m*seq (the formula
    GPTConfig.flops_per_token has always used, seq defaulting to the
    config's max_seq)."""
    s = _shape(cfg)
    if seq is None:
        seq = cfg.max_seq
    return 6.0 * s["num_params"] + 12.0 * s["L"] * s["m"] * seq


def decode_step_cost(cfg, context_lens: Sequence[int],
                     q_lens: Optional[Sequence[int]] = None, *,
                     kv_dtype_bytes: int = 2,
                     param_bytes: Optional[int] = None) -> StepCost:
    """One decode step over a batch of lanes: lane i scores
    ``q_lens[i]`` rows (its current token, plus its proposals under
    speculation; one row a lane when omitted) against
    ``context_lens[i]`` resident tokens (INCLUDING those rows). Weights
    stream from HBM once for the whole batch — this is why batching
    lifts decode MFU. Priced honestly: every scored row costs full
    matmul + attention FLOPs whether its proposal is later accepted or
    rolled back — speculation buys steps, not FLOPs. Row j of lane i
    attends ctx - q + 1 + j keys (causal within the span), so the
    per-lane attention term is q*ctx - q*(q-1)/2 contexts. HBM: one
    weight stream for the batch, one read of each lane's context KV
    (the kernel's block gather serves all rows in a lane), one write
    per scored row."""
    s = _shape(cfg)
    if q_lens is None:
        q_lens = [1] * len(context_lens)
    n_rows = float(sum(q_lens))
    attn = 0.0
    total_ctx = 0.0
    for ctx, q in zip(context_lens, q_lens):
        attn += _attn_flops(s, q, ctx)
        total_ctx += ctx
    # A layer that keeps a state a sequence costs a row the same at any
    # context and moves the lane's whole state in and out.
    flops = (2.0 * s["matmul_weights"] * n_rows + attn
             + (s.get("state_ops_per_row", 0.0)
                + s.get("stream_ops_per_row", 0.0)) * n_rows)
    kvb = s["kv_bytes_per_token"] * kv_dtype_bytes
    if param_bytes is None:
        param_bytes = s["param_bytes"]
    # Weights stream once a step: all of a dense model's, and of a
    # routed layer the experts the step's rows are expected to hit.
    hbm = (s["streamed_params"](n_rows) * param_bytes
           + total_ctx * kvb                 # context KV read per lane
           + n_rows * kvb                    # KV write per scored row
           + n_rows * s.get("stream_bytes_per_row", 0)
           + 2.0 * len(context_lens) * s.get("state_bytes_per_seq", 0))
    return StepCost(flops, hbm, int(n_rows))


def prefill_cost(cfg, n_tokens: int, *, ctx_tokens: int = 0,
                 kv_dtype_bytes: int = 2,
                 param_bytes: Optional[int] = None,
                 weight_rows: Optional[int] = None) -> StepCost:
    """Prefill of a T-token span whose first ``ctx_tokens`` of context
    already sit in the KV pool (prefix-cache hit or an earlier chunk of
    a chunked prefill — those spans are NOT priced here, so MFU stays
    honest when cached work is skipped).

    Causal attention: span position i attends ctx + i + 1 keys, so the
    attention term is ctx*T + T*(T+1)/2 contexts. The vocabulary head
    runs on ONE row, the span's last token (the chunk program hands
    back that row alone). HBM adds one read of the resident context's
    KV on top of the span's own write+read. A program that carries
    several spans reads the weights once for all their rows: the span
    that is charged them says how many rows that is (``weight_rows``;
    None: its own), the others 0."""
    s = _shape(cfg)
    T = int(n_tokens)
    ctx = int(ctx_tokens)
    seen = ctx * T + T * (T + 1) / 2.0
    flops = (2.0 * (s["matmul_weights"] - s["head_weights"]) * T
             + 2.0 * s["head_weights"] + s["chunk_attn_per_ctx"] * seen
             + s["chunk_ctx_ops"] * ctx
             + sum(coef * min(seen, T * w) for coef, w in s["attn_windows"])
             + (s.get("scan_ops_per_row", 0.0)
                + s.get("stream_ops_per_row", 0.0)) * T)
    kvb = s["kv_bytes_per_token"] * kv_dtype_bytes
    if param_bytes is None:
        param_bytes = s["param_bytes"]
    # A span reads the sequence's state once and writes it once.
    weights = 0.0 if weight_rows == 0 else s["streamed_params"](
        T if weight_rows is None else int(weight_rows))
    hbm = (weights * param_bytes + (2.0 * T + ctx) * kvb
           + T * s.get("stream_bytes_per_row", 0)
           + 2.0 * s.get("state_bytes_per_seq", 0))
    return StepCost(flops, hbm, T)


def train_step_cost(cfg, batch: int, seq: Optional[int] = None, *,
                    param_bytes: int = 4,
                    act_bytes: int = 2) -> StepCost:
    """One optimizer step at (batch, seq): 6N-rule FLOPs plus an
    HBM-traffic approximation — 8 full passes over the params (fwd read,
    bwd read, grad write, adam m/v read+write, param write) + saved
    activations (~14*m elements per token per layer)."""
    s = _shape(cfg)
    if seq is None:
        seq = cfg.max_seq
    tokens = int(batch) * int(seq)
    flops = train_flops_per_token(cfg, seq) * tokens
    hbm = (8.0 * s["num_params"] * param_bytes
           + 14.0 * s["m"] * s["L"] * tokens * act_bytes)
    return StepCost(flops, hbm, tokens)


# ---------------------------------------------------------------------------
# Roofline verdicts
# ---------------------------------------------------------------------------


def roofline(cost: StepCost, device_s: float, host_gap_s: float = 0.0,
             *, hw: Optional[HardwarePeak], n_chips: int = 1) -> dict:
    """Classify where a step's wall time went; ``{}`` when ``hw`` is
    None (a CPU run has no peak, so no utilization and no verdict).

    mfu       achieved / peak FLOP rate over the DEVICE span
    hbm_util  achieved / peak HBM bandwidth over the device span
    verdict   'host'    if the host gap around the device span exceeds
                        the device span itself (the device idles more
                        than it runs),
              'compute' if mfu >= hbm_util (closer to the compute roof),
              'hbm'     otherwise (bandwidth is the binding roof).
    """
    if hw is None:
        return {}
    device_s = max(float(device_s), 1e-9)
    chips = max(int(n_chips), 1)
    mfu = cost.flops / (device_s * hw.flops_per_s * chips)
    hbm_util = cost.hbm_bytes / (device_s * hw.hbm_bytes_per_s * chips)
    if host_gap_s > device_s:
        verdict = "host"
    elif mfu >= hbm_util:
        verdict = "compute"
    else:
        verdict = "hbm"
    return {"mfu": mfu, "hbm_util": hbm_util, "verdict": verdict,
            "hardware": hw.name}


# ---------------------------------------------------------------------------
# Per-step accounting (the engine/train instrumentation hook)
# ---------------------------------------------------------------------------

#: The registry of span names on the two step paths: name -> (kind,
#: what it covers). ``kind`` None is a HOST phase (``phase()``: its
#: milliseconds land in ``phases_ms``); any other kind is a DEVICE span
#: (``device()``: dispatch to ready; ``dispatch()``: a program's
#: dispatch and, later, the host's wait for it, two halves with host
#: work and other programs' halves between them; its milliseconds land
#: in ``device_ms`` and ``device_ms_by[kind]``). Host phases of one step
#: never nest in one another or in a device span's half, so they
#: partition the step's host time. While a ``jax.profiler`` session is
#: open every name is also a ``TraceAnnotation`` for the same interval,
#: so the span lies on the trace's host plane beside the device ops. A
#: name that is not here is an error. The names in ``ANNOTATIONS`` are the
#: accounting's own: an interval it times itself (the gap between two
#: steps, the two halves of a device span, a collection) and annotates
#: while a session is open; they lie over or inside the phases and
#: spans, so none is a key of ``phases_ms`` or ``device_ms_by``.
PHASES: Dict[str, tuple] = {
    "llm.admit": (None, "admission of waiting requests: prefix lookup, "
                        "block grants"),
    "llm.prefill.host": (None, "a prefill chunk's host side: input "
                               "arrays, prefix register, the lane of a "
                               "prompt that ends here"),
    "llm.prefill.device": ("prefill", "one prefill chunk (the pool write "
                                      "is inside its program): its "
                                      "dispatch, and the host's wait for "
                                      "its result once the step's "
                                      "programs are queued"),
    "llm.state_restore": (None, "a parked state snapshot handed to the "
                                "first span of the sequence that took "
                                "it up"),
    "llm.state_snapshot": (None, "a sequence's state parked at its last "
                                 "block boundary: the prefix index's "
                                 "entry and the dispatch of the slot's "
                                 "copy"),
    "llm.pools": (None, "the pools a program was donated taken back as it "
                        "returned them, right after its dispatch: the step "
                        "drops its references to the arrays it gave away, "
                        "which frees them while that program is in flight"),
    "llm.slots": (None, "writable KV slots for every decode lane: block "
                        "grants, copy-on-write, preemption"),
    "llm.decode.build": (None, "the decode (or verify) program's input "
                               "arrays for max_batch lanes; proposals "
                               "under speculation"),
    "llm.decode.device": ("decode", "the decode or verify program: its "
                                    "dispatch, and the wait for its "
                                    "argmax ids behind the chunks'"),
    "llm.sample": (None, "device_get of the program's ids, which greedy "
                         "lanes take; for lanes with a temperature also "
                         "of the logits, and the sampler (or "
                         "verify_tokens) on their rows, a prompt's "
                         "first token among them"),
    "llm.emit": (None, "tokens onto the request queues (a prompt's "
                       "first when its chunk is seen done), finishes, "
                       "block release, speculative rollback"),
    "llm.trace": (None, "the per-request llm.decode_step span copies, "
                        "one a traced lane (util/tracing ring)"),
    "llm.publish": (None, "step_log and the rtpu_llm_* gauge writes"),
    "train.dispatch": ("dispatch", "the jitted train step's call, until "
                                   "it returns its futures"),
    "train.wait": ("wait", "block_until_ready on the step's outputs"),
    "train.report": (None, "train.report(): metrics onto the session's "
                           "queue, gauge writes"),
    "data.next_batch": (None, "one next() of the dataset shard's batch "
                              "iterator inside a training loop"),
}
_OWN_INTERVALS = {
    "llm.between": "one step's finish() to the next one's begin(): the "
                   "loop's turn-around and its wait for the engine's lock "
                   "while work was waiting",
    "llm.idle": "the loop asleep on an empty engine, inside llm.between",
    "llm.decode.dispatch": "llm.decode.device until the jitted call has "
                           "returned its futures: argument hand-over and "
                           "enqueue",
    "llm.decode.wait": "the other half of llm.decode.device: the host "
                       "blocked on the ids, and the wake-up after it",
    "llm.prefill.dispatch": "llm.prefill.device until the chunk program's "
                            "call has returned",
    "llm.prefill.wait": "the other half of llm.prefill.device, after the "
                        "step's last dispatch: the host blocked on the "
                        "chunk's result, and the wake-up",
    "py.gc": "one pass of the interpreter's collector, on the thread that "
             "ran it (gc.callbacks)",
    "serve.handle_request": "a replica's call slot in user code for one "
                            "request (Replica.handle_request): for a "
                            "generation deployment, add_request",
    "serve.stream_poll": "a stream poll's reply being made "
                         "(Replica.stream_poll), from its wake with "
                         "something to carry to its return",
    "serve.flush": "one callback of a reader's loop (Router._flush): "
                   "every stream of a poll's reply has its share encoded "
                   "and written to its socket",
}
PHASES.update((name, (None, what)) for name, what in _OWN_INTERVALS.items())
ANNOTATIONS = frozenset(_OWN_INTERVALS)
#: Annotated whole steps (``StepAccounting.step``): ring entry names.
STEPS = ("llm.step", "train.step")
#: Key of an ``llm.step`` ring entry beside ``lanes``: how many of the
#: step's decode lanes took their token from the program's own argmax
#: (greedy requests) and not from a logits row sampled on the host.
DEVICE_SAMPLED = "device_sampled"
_DEVICE_KINDS = frozenset(k for k, _ in PHASES.values() if k) | {"device"}
_UNDETECTED = object()      # StepAccounting's peaks, before first use


def _session_open() -> bool:
    """Is a ``jax.profiler`` session open in this process? False where
    jax is not imported: a CPU-lane worker must not pay the import for
    a span nobody reads."""
    # No attribute yet while jax is half imported on some thread (a
    # collection inside the import runs the collector's hook).
    profiler = getattr(sys.modules.get("jax"), "profiler", None)
    return profiler is not None and profiler.TraceAnnotation.is_enabled()


def _annotation(name: str):
    """A ``TraceAnnotation`` that starts now (it starts when it is
    built); its ``__exit__`` ends it."""
    return sys.modules["jax"].profiler.TraceAnnotation(name)


class _Collector:
    """The interpreter's collector, process-wide: one ``gc.callbacks``
    hook, put in by the first step any StepAccounting begins. A pass
    costs two clock readings, and nothing between passes. Passes do not
    nest and run with the interpreter lock held, so one open pass is
    all there is."""

    def __init__(self):
        # {generation: [passes, seconds]} since the hook went in.
        self.totals: Dict[int, list] = {0: [0, 0.0], 1: [0, 0.0],
                                        2: [0, 0.0]}
        # The last passes, (count so far, generation, seconds, objects
        # collected), for the step that ends after them: more than an
        # interval holds.
        self.passes: collections.deque = collections.deque(maxlen=128)
        self.count = 0
        self._t0 = 0.0      # the running pass's start
        self._ann = None    # and, under a session, its annotation

    def hook(self, phase: str, info: dict):
        if phase == "start":
            self._ann = _annotation("py.gc") if _session_open() else None
            self._t0 = time.perf_counter()
            return
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        if not self._t0:
            return      # a pass that began before the hook went in
        s = time.perf_counter() - self._t0
        self._t0 = 0.0
        self.count += 1
        total = self.totals[info["generation"]]
        total[0] += 1
        total[1] += s
        self.passes.append((self.count, info["generation"], s,
                            info["collected"]))

    def seconds(self) -> float:
        return sum(s for _, s in self.totals.values())

    def since(self, seen: int) -> tuple:
        """(longest seconds, oldest generation or None, passes so far)
        of the passes after the ``seen``-th, from the last 128."""
        longest, oldest = 0.0, None
        if self.count != seen:
            for count, gen, s, _ in list(self.passes):
                if count > seen:
                    longest = max(longest, s)
                    oldest = gen if oldest is None else max(oldest, gen)
        return longest, oldest, self.count


_COLLECTOR = _Collector()


def gc_totals() -> Dict[int, list]:
    """{generation: [passes, seconds]} of the collector since this
    process's first accounted step (cumulative: two readings give a
    window's)."""
    return {gen: list(t) for gen, t in _COLLECTOR.totals.items()}


def session_annotation(name: str):
    """A ``TraceAnnotation`` under a name of ``ANNOTATIONS`` that starts
    now, for a block of code on a thread no StepAccounting drives (the
    serving threads' bodies); None, and nothing built, while no
    ``jax.profiler`` session is open. The caller ends it with
    ``__exit__``."""
    if name not in ANNOTATIONS:
        raise KeyError(f"{name!r} is not in perfmodel.ANNOTATIONS")
    return _annotation(name) if _session_open() else None


#: The interpreter probe's period: it asks to sleep this long, and what
#: it sleeps longer is what it waited for the interpreter (or a core).
INTERP_PERIOD_S = 0.02
#: A sample later than this was HELD: somebody had the interpreter when
#: the probe woke. Set from an idle process's lateness on the
#: benchmark's host, whose timer alone wakes a sleeper 0.7 ms late
#: (PERF.md section 6, PR 60: 3,000 idle samples, mean 0.68-0.71 ms,
#: 99th percentile 1.05, one in 125 over 1.0, the largest 1.14 but for
#: one of 90.7).
INTERP_HELD_FLOOR_S = 0.0015
#: A sample this late is a pause somebody will look for by name: its
#: lateness goes to ``standstill_ms`` where the PROCESS's CPU clock
#: advanced by under ``_STANDSTILL_CPU_SHARE`` of the sample's wall time
#: (nobody in the process ran: the OS or the machine had it), else to
#: ``held_long_ms`` (a thread of the process kept the interpreter).
INTERP_LONG_S = 0.05
_STANDSTILL_CPU_SHARE = 0.1


class _InterpreterProbe:
    """Whether a thread that wakes with work to do could have the
    interpreter, process-wide: one daemon thread, started by the first
    step any StepAccounting begins, sleeps ``INTERP_PERIOD_S`` and reads
    how much later than asked it runs again. Asleep it holds nothing;
    to run again it needs the interpreter like a poll with a reply to
    carry, a request's executor hand-off or the engine after the
    device, so its lateness is what any of them pays at that instant. A
    sample costs two clock readings (the second the process's CPU
    clock, a system call). Counts are cumulative; a reader takes two
    readings (``StepAccounting.finish`` does, an interval)."""

    def __init__(self):
        self._start_lock = threading.Lock()
        self.thread: Optional[threading.Thread] = None
        # (samples, late s, held samples, standstill s, held-long s):
        # replaced whole by the probe's thread once a sample, so ONE
        # read gives a reader a consistent set.
        self.totals = (0, 0.0, 0, 0.0, 0.0)
        # The last samples' (number, late s), for an interval's longest.
        self.recent: collections.deque = collections.deque(maxlen=32)

    def start(self):
        with self._start_lock:
            if self.thread is None:
                self.thread = threading.Thread(
                    target=self._run, daemon=True, name="interp-probe")
                self.thread.start()

    def _run(self):
        period = INTERP_PERIOD_S
        n = held = 0
        late_s = standstill_s = held_long_s = 0.0
        cpu = time.process_time()
        t = time.perf_counter()
        while True:
            time.sleep(period)
            now = time.perf_counter()
            cpu_now = time.process_time()
            late = max(0.0, now - t - period)
            n += 1
            late_s += late
            if late > INTERP_HELD_FLOOR_S:
                held += 1
                if late >= INTERP_LONG_S:
                    if cpu_now - cpu < _STANDSTILL_CPU_SHARE * (now - t):
                        standstill_s += late
                    else:
                        held_long_s += late
            self.recent.append((n, late))
            self.totals = (n, late_s, held, standstill_s, held_long_s)
            # Read again: what the bookkeeping above took (or waited
            # for) is not the next sample's lateness.
            cpu, t = time.process_time(), time.perf_counter()


_PROBE = _InterpreterProbe()


def interp_totals() -> dict:
    """The interpreter probe's cumulative counts since this process's
    first accounted step (two readings give a window's): ``n`` samples,
    their summed lateness ``late_s``, ``held_n`` of them later than
    ``INTERP_HELD_FLOOR_S``, and of those at least ``INTERP_LONG_S``
    late ``standstill_s`` (the process's CPU clock stood still) and
    ``held_long_s`` (it ran)."""
    n, late_s, held, standstill_s, held_long_s = _PROBE.totals
    return {"n": n, "late_s": late_s, "held_n": held,
            "standstill_s": standstill_s, "held_long_s": held_long_s,
            "period_s": INTERP_PERIOD_S}


class _Span:
    """One registry name's reusable context manager: host clock and,
    in a step that began with a profiler session open, a
    TraceAnnotation over the same interval (the annotation starts when
    it is built, so each interval builds its own). Not re-entrant; one
    thread drives a StepAccounting (the engine under its lock, a
    training loop's thread)."""

    __slots__ = ("_acc", "name", "_kind", "_t0", "_ann", "seconds")

    def __init__(self, acc: "StepAccounting", name: str):
        self._acc = acc
        self.name = name
        self._kind = PHASES[name][0]
        self._t0 = 0.0
        self._ann = None
        self.seconds = 0.0

    def __enter__(self):
        if self._acc._traced:
            self._ann = _annotation(self.name)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.seconds = s = time.perf_counter() - self._t0
        if self._ann is not None:
            self._ann.__exit__(*exc)
            self._ann = None
        acc = self._acc
        if self._kind is None:
            name = self.name
            acc._phase_s[name] = acc._phase_s.get(name, 0.0) + s
        else:
            acc.add_device(s, kind=self._kind)
        return False


class _Program:
    """One dispatched program's device span ``<stem>.device``, open
    from its dispatch until the host has seen its result, so several
    can be open at once: a step queues all its programs and then
    collects them in the device's order. The span is its two halves,
    each a ``with`` block on the host's clock (and, under a profiler
    session, the span's name with ``<stem>.dispatch`` or ``<stem>.wait``
    inside it over the same interval):

      with acc.dispatch(name) as prog:   # the jitted call, until it
          out = program(...)             # has returned its futures
      ...                                # host phases, more dispatches
      with prog.waiting():               # the host blocked on ``out``,
          jax.block_until_ready(out)     # and the wake-up after it

    What the host does between the two is not in the span: the halves
    of a step's spans are disjoint from one another and from its host
    phases, whatever the device ran meanwhile. ``dispatch_seconds`` and
    ``seconds`` (both halves) can be read once the wait has closed."""

    __slots__ = ("_acc", "_kind", "_names", "_anns", "_t0", "_waiting",
                 "seconds", "dispatch_seconds")

    def __init__(self, acc: "StepAccounting", name: str, kind: str):
        self._acc = acc
        self._kind = kind
        stem = name[:-len("device")]
        self._names = (name, stem + "dispatch", stem + "wait")
        self._anns = ()
        self._t0 = 0.0
        self._waiting = False
        self.seconds = self.dispatch_seconds = 0.0

    def waiting(self) -> "_Program":
        """The span's second ``with`` block (itself, for the name at
        the call site)."""
        return self

    def __enter__(self):
        acc = self._acc
        if self._waiting:
            # From the step's first blocking fetch on, a program that
            # is dispatched was not queued before the host waited.
            acc._waited = True
        if acc._traced:
            self._anns = (_annotation(self._names[0]),
                          _annotation(self._names[1 + self._waiting]))
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        s = time.perf_counter() - self._t0
        for ann in reversed(self._anns):
            ann.__exit__(*exc)
        self._anns = ()
        acc, kind = self._acc, self._kind
        acc.add_device(s, kind=kind)
        self.seconds += s
        if not self._waiting:
            # The dispatch half is the host's work; the wait is not.
            self.dispatch_seconds = s
            acc._dispatch_by[kind] = acc._dispatch_by.get(kind, 0.0) + s
            acc._dispatch_s += s
            acc._programs += 1
            acc._programs_queued += not acc._waited
            self._waiting = True
        return False


class StepAccounting:
    """Accumulates one scheduler step's device spans, named host phases
    and priced costs, and folds them into a breakdown dict on finish().
    Cheap enough for the per-decode-step hot path (see the perf gate):
    a begin/add/finish cycle is plain float arithmetic, no locks, no
    allocation beyond the result dicts and one float a name.

    The breakdown (one entry of the device-step ring):
      step_ms / device_ms / host_gap_ms   begin() to finish(), the sum
                    of the device spans, and the rest
      device_ms_by  {kind: ms}; sums to device_ms
      dispatch_ms_by  {kind: ms} of the device spans opened by
                    dispatch(): the half before the jitted call
                    returned; each at most its device_ms_by value
      programs / programs_queued   (a step that used dispatch()) the
                    programs it dispatched, and those of them that
                    were dispatched before its first waiting(): equal
                    where the host blocked on nothing until the
                    step's whole work was in the device's queue
      phases_ms     {host phase: ms}; with other_ms, what no phase
                    names, they sum to host_gap_ms
      between_ms    the previous finish() to this begin() (absent on
                    the first step); of it lock_wait_ms was spent
                    acquiring the owner's lock (lock_waited) and
                    idle_ms asleep on an empty queue (idle), which
                    idle_wait says happened at all
      interval_ms   between_ms + step_ms: one finish() to the next
      cpu_ms        the thread's CPU time over that interval: ONE
                    reading of time.thread_time() a step, in finish()
                    (a reading is a system call, 6 us on the benchmark's
                    host, where the clock also ticks at 10 ms: no span
                    reads it, and a single entry is good to a tick)
      stall_ms      interval_ms - idle_ms - the device spans' waits
                    (a span less its dispatch half; a span that was not
                    cut, whole) - cpu_ms: the thread had work (host
                    phases, the gap, argument hand-over) and was not
                    running: it waited for a lock or the interpreter,
                    another thread ran a collection, or the OS held
                    it. Not clamped: ticks cancel over a window's sum,
                    so a window's mean is exact where one entry is not
      gc_ms / gc_max_ms / gc_gen   the collector's passes that ended
                    inside the interval, whichever thread ran them:
                    their sum, the longest, the oldest generation
                    (None: no pass)
      interp_n / interp_late_ms / interp_late_max_ms / interp_held_n
                    the interpreter probe's samples that ended inside
                    the interval (``_InterpreterProbe``): how many,
                    their summed and their longest lateness, and how
                    many were later than INTERP_HELD_FLOOR_S: what a
                    thread of this process that woke with work to do
                    waited for the interpreter
      standstill_ms / held_long_ms   the lateness of the samples at
                    least INTERP_LONG_S late, by whether the PROCESS's
                    CPU clock stood still meanwhile (the OS or the
                    machine had the process) or ran (a thread kept the
                    interpreter: a collection's pass, which gc_max_ms
                    of the same entry then matches)
      tokens / flops / hbm_bytes and, with a peak, mfu / hbm_util /
      verdict / hardware
    """

    __slots__ = ("_hw", "n_chips", "_wall0", "_traced",
                 "_device_s", "_dispatch_s", "_device_by", "_dispatch_by",
                 "_phase_s", "_spans", "_flops", "_hbm_bytes",
                 "_tokens", "_finish_t", "_finish_cpu", "_between_s",
                 "_between", "_gap_ann", "_idle_s", "_lock_wait_s",
                 "_gc_seen", "_gc_s", "_interp_seen", "_programs",
                 "_programs_queued",
                 "_waited", "idle_total_s", "idle_waits", "last")

    def __init__(self, hw: Optional[HardwarePeak] = None,
                 n_chips: int = 1, between: Optional[str] = None):
        # Resolved on first use: detect_hardware() brings the backend
        # up, which a session that never dispatches must not do.
        self._hw = hw if hw is not None else _UNDETECTED
        self.n_chips = max(int(n_chips), 1)
        # The owner's name for the gap between two of its steps, an
        # annotation from finish() to the next begin() under a session.
        if between is not None and between not in ANNOTATIONS:
            raise KeyError(f"{between!r} is not in perfmodel.ANNOTATIONS")
        self._between = between
        self._gap_ann = None
        self._wall0 = 0.0
        self._traced = False    # a profiler session was open at begin()
        self._device_s = self._dispatch_s = 0.0
        self._device_by: Dict[str, float] = {}
        self._dispatch_by: Dict[str, float] = {}
        self._phase_s: Dict[str, float] = {}
        self._spans: Dict[str, _Span] = {}
        self._flops = 0.0
        self._hbm_bytes = 0.0
        self._tokens = 0
        # dispatch() spans this step, those before its first waiting().
        self._programs = self._programs_queued = 0
        self._waited = False
        self._finish_t: Optional[float] = None
        # The thread's CPU clock at the last finish() (at the first
        # begin(), before there was one).
        self._finish_cpu: Optional[float] = None
        self._between_s: Optional[float] = None
        # Since the last finish(): asleep, and acquiring the lock.
        self._idle_s = self._lock_wait_s = 0.0
        self._gc_seen = _COLLECTOR.count
        self._gc_s = _COLLECTOR.seconds()
        self._interp_seen = _PROBE.totals
        # Cumulative, so a window's share is a difference of two
        # readings whatever the ring still holds.
        self.idle_total_s = 0.0
        self.idle_waits = 0
        self.last: Optional[dict] = None

    @property
    def hw(self) -> Optional[HardwarePeak]:
        """The chip's peaks; None on the CPU backend: the breakdown
        then carries counts and times only."""
        if self._hw is _UNDETECTED:
            self._hw = detect_hardware()
        return self._hw

    @contextlib.contextmanager
    def idle(self, name: str):
        """Around the owner's sleep on an empty queue: the next step's
        between_ms holds a wait for work (idle_ms of it, idle_wait)."""
        if name not in ANNOTATIONS:
            raise KeyError(f"{name!r} is not in perfmodel.ANNOTATIONS")
        ann = _annotation(name) if _session_open() else None
        t0 = time.perf_counter()
        try:
            yield
        finally:
            s = time.perf_counter() - t0
            if ann is not None:
                ann.__exit__(None, None, None)
            self._idle_s += s
            self.idle_total_s += s
            self.idle_waits += 1

    def lock_waited(self, t0: float):
        """The owner has its lock, which it set out to take at ``t0``
        (time.perf_counter()), between two steps."""
        self._lock_wait_s += time.perf_counter() - t0

    def begin(self):
        if _COLLECTOR.hook not in gc.callbacks:
            gc.callbacks.append(_COLLECTOR.hook)
        if _PROBE.thread is None:
            _PROBE.start()
        if self._finish_cpu is None:
            self._finish_cpu = time.thread_time()
        self._wall0 = now = time.perf_counter()
        if self._gap_ann is not None:
            self._gap_ann.__exit__(None, None, None)
            self._gap_ann = None
        self._traced = _session_open()
        self._between_s = (None if self._finish_t is None
                           else now - self._finish_t)
        self._device_s = self._dispatch_s = 0.0
        self._device_by.clear()
        self._dispatch_by.clear()
        self._phase_s.clear()
        self._flops = 0.0
        self._hbm_bytes = 0.0
        self._tokens = 0
        self._programs = self._programs_queued = 0
        self._waited = False

    def _span(self, name: str) -> _Span:
        span = self._spans.get(name)
        if span is None:
            if name not in PHASES or name in ANNOTATIONS:
                raise KeyError(
                    f"{name!r} is not a phase or a device span of "
                    f"perfmodel.PHASES, the registry of span names")
            span = self._spans[name] = _Span(self, name)
        return span

    def phase(self, name: str) -> _Span:
        """Context manager over a HOST phase of the step."""
        span = self._span(name)
        if span._kind is not None:
            raise KeyError(f"{name!r} is a device span: use device()")
        return span

    def device(self, name: str) -> _Span:
        """Context manager over a DEVICE span, dispatch to ready; its
        ``seconds`` can be read after it closes. Price the work with
        add_cost()."""
        span = self._span(name)
        if span._kind is None:
            raise KeyError(f"{name!r} is a host phase: use phase()")
        return span

    def dispatch(self, name: str) -> _Program:
        """A new DEVICE span ``<stem>.device`` that stays open past
        its ``with`` block, which is its dispatch half; its
        ``waiting()`` block closes it (``_Program``)."""
        kind = PHASES.get(name, (None,))[0]
        if kind is None or not name.endswith(".device"):
            raise KeyError(f"{name!r} is not a device span that "
                           f"perfmodel.PHASES cuts in two")
        return _Program(self, name, kind)

    def step(self, name: str, step_num: int):
        """``jax.profiler.StepTraceAnnotation`` around the whole step
        while a profiler session is open, else a null context."""
        if name not in STEPS:
            raise KeyError(f"{name!r} is not in perfmodel.STEPS")
        # Looked up again here: a training step begins at the report
        # before it, which may lie before the session's start.
        self._traced = _session_open()
        if not self._traced:
            return contextlib.nullcontext()
        return sys.modules["jax"].profiler.StepTraceAnnotation(
            name, step_num=int(step_num))

    def add_cost(self, cost: StepCost):
        self._flops += cost.flops
        self._hbm_bytes += cost.hbm_bytes
        self._tokens += cost.tokens

    def add_device(self, seconds: float, cost: StepCost = ZERO_COST,
                   kind: str = "device"):
        if kind not in _DEVICE_KINDS:
            raise KeyError(f"{kind!r} is not a device kind of "
                           f"perfmodel.PHASES")
        self._device_s += seconds
        self._device_by[kind] = self._device_by.get(kind, 0.0) + seconds
        if cost is not ZERO_COST:
            self.add_cost(cost)

    def _gc_since_last(self) -> tuple:
        """(ms, longest ms, oldest generation or None) of the passes
        that ended since the previous finish()."""
        total = _COLLECTOR.seconds()
        gc_s, self._gc_s = total - self._gc_s, total
        longest, oldest, self._gc_seen = _COLLECTOR.since(self._gc_seen)
        return gc_s * 1e3, longest * 1e3, oldest

    def finish(self, *, record_as: Optional[str] = None,
               attrs: Optional[dict] = None) -> Optional[dict]:
        """Close the step. Returns None (and records nothing) if no
        device work ran — an idle scheduler tick is not a step."""
        now = time.perf_counter()
        cpu = time.thread_time()
        cpu_s = cpu - self._finish_cpu
        self._finish_t, self._finish_cpu = now, cpu
        idle_s, lock_s = self._idle_s, self._lock_wait_s
        self._idle_s = self._lock_wait_s = 0.0
        gc_ms, gc_max_ms, gc_gen = self._gc_since_last()
        # The probe's samples since the previous finish(): one read of
        # its totals, and of its last samples for the longest.
        interp = _PROBE.totals
        seen, self._interp_seen = self._interp_seen, interp
        late_max = max([late for k, late in tuple(_PROBE.recent)
                        if seen[0] < k <= interp[0]], default=0.0) \
            if interp[0] != seen[0] else 0.0
        if self._traced and self._between is not None:
            self._gap_ann = _annotation(self._between)
        if self._device_s <= 0.0 and self._flops <= 0.0:
            self.last = None
            return None
        wall_s = max(now - self._wall0, self._device_s)
        host_gap_s = wall_s - self._device_s
        host_gap_ms = host_gap_s * 1e3
        phases_ms = {k: v * 1e3 for k, v in self._phase_s.items()}
        between_s = self._between_s or 0.0
        interval_s = wall_s + between_s
        out = {
            "step_ms": wall_s * 1e3,
            "device_ms": self._device_s * 1e3,
            "host_gap_ms": host_gap_ms,
            "device_ms_by": {k: v * 1e3
                             for k, v in self._device_by.items()},
            "dispatch_ms_by": {k: v * 1e3
                               for k, v in self._dispatch_by.items()},
            "phases_ms": phases_ms,
            "other_ms": host_gap_ms - sum(phases_ms.values()),
            "idle_wait": idle_s > 0.0,
            "idle_ms": idle_s * 1e3,
            "lock_wait_ms": lock_s * 1e3,
            "interval_ms": interval_s * 1e3,
            "cpu_ms": cpu_s * 1e3,
            "stall_ms": (interval_s - min(idle_s, between_s) - cpu_s
                         - (self._device_s - self._dispatch_s)) * 1e3,
            "gc_ms": gc_ms,
            "gc_max_ms": gc_max_ms,
            "gc_gen": gc_gen,
            "interp_n": interp[0] - seen[0],
            "interp_late_ms": (interp[1] - seen[1]) * 1e3,
            "interp_late_max_ms": late_max * 1e3,
            "interp_held_n": interp[2] - seen[2],
            "standstill_ms": (interp[3] - seen[3]) * 1e3,
            "held_long_ms": (interp[4] - seen[4]) * 1e3,
            "tokens": self._tokens,
            "flops": self._flops,
            "hbm_bytes": self._hbm_bytes,
            # mfu / hbm_util / verdict / hardware: only with a peak.
            **roofline(
                StepCost(self._flops, self._hbm_bytes, self._tokens),
                self._device_s, host_gap_s, hw=self.hw,
                n_chips=self.n_chips),
        }
        if self._between_s is not None:
            out["between_ms"] = self._between_s * 1e3
        if self._programs:
            out["programs"] = self._programs
            out["programs_queued"] = self._programs_queued
        self.last = out
        if record_as is not None:
            record_device_step(record_as, time.time() - wall_s, out,
                              attrs)
        return out


# The StepAccounting of the training loop running on this thread, for
# code that sits under the loop without knowing the session (Data's
# batch iterator). None outside a training loop.
_tls = threading.local()


def bind_accounting(acc: Optional[StepAccounting]):
    _tls.acc = acc


def bound_accounting() -> Optional[StepAccounting]:
    return getattr(_tls, "acc", None)


# ---------------------------------------------------------------------------
# Process-local device-step ring (the gang profiler's deterministic
# capture source: every accounted step lands here; ``rtpu profile
# --device`` drains it per process alongside the jax trace artifacts)
# ---------------------------------------------------------------------------

_ring_lock = threading.Lock()
_STEP_RING: collections.deque = collections.deque(maxlen=4096)


def record_device_step(name: str, t_wall: float, breakdown: dict,
                       attrs: Optional[dict] = None):
    ev = {"name": name, "t_wall": float(t_wall)}
    ev.update(breakdown)
    if attrs:
        ev.update(attrs)
    with _ring_lock:
        _STEP_RING.append(ev)


def device_step_events(since: float = 0.0,
                       limit: int = 4096) -> List[dict]:
    """Recorded device steps with t_wall >= since, oldest first."""
    with _ring_lock:
        evs = [e for e in _STEP_RING if e["t_wall"] >= since]
    return evs[-limit:]


def clear_device_steps():
    with _ring_lock:
        _STEP_RING.clear()
