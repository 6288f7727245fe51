"""Sampling CPU profiler + heap snapshots, py-spy/memray-shaped.

Capability parity target: the reference's on-demand profiling surface
(/root/reference/dashboard/modules/reporter/profile_manager.py:79
CpuProfilingManager — py-spy flamegraphs of a live worker — and :188
MemoryProfilingManager — memray heap). Neither tool ships in this
image, and both need ptrace; instead processes SELF-sample:

  * CPU: a daemon thread walks ``sys._current_frames()`` at ``hz`` for
    ``duration_s`` and aggregates FOLDED stacks ("a;b;c count" — the
    flamegraph interchange format Brendan Gregg's tooling and
    speedscope read). The in-process sampler sees exactly what py-spy
    would, minus native frames — the right trade for a pure-asyncio
    runtime where the question is "which Python path is hot/stuck".
  * Flamegraph: folded stacks render to a self-contained SVG here — no
    external tooling on the box.
  * Heap: tracemalloc top allocation sites (started on first request;
    subsequent snapshots see everything allocated since).
"""

from __future__ import annotations

import sys
import threading
import time
from collections import Counter


def sample_profile(duration_s: float = 5.0, hz: float = 99.0,
                   include_idle: bool = False,
                   timeline: bool = False) -> dict:
    """Self-sample every thread of THIS process. Returns
    {"folded": str, "samples": int, "duration_s": float}; with
    ``timeline=True`` also {"timeline": [[t_wall, leaf_frame], ...]}
    (bounded) — timestamped leaf frames the merged device-trace export
    renders as a host-CPU track alongside device events."""
    interval = 1.0 / max(1.0, hz)
    counts: Counter = Counter()
    me = threading.get_ident()
    samples = 0
    tl: list = []
    deadline = time.monotonic() + duration_s
    while time.monotonic() < deadline:
        t_wall = time.time()
        for tid, frame in sys._current_frames().items():
            if tid == me:
                continue
            stack = []
            f = frame
            while f is not None:
                code = f.f_code
                stack.append(f"{code.co_name} "
                             f"({code.co_filename.rsplit('/', 1)[-1]}"
                             f":{f.f_lineno})")
                f = f.f_back
            if not stack:
                continue
            folded = ";".join(reversed(stack))
            idle = not include_idle and (
                    "wait (threading.py" in stack[0]
                    or "select (selectors.py" in stack[0]
                    or "_recv (" in stack[0]
                    or "accept (socket.py" in stack[0])
            if idle:
                folded = "[idle];" + folded
            counts[folded] += 1
            if timeline and not idle and len(tl) < 4000:
                tl.append([t_wall, stack[0]])
        samples += 1
        time.sleep(interval)
    lines = [f"{k} {v}" for k, v in counts.most_common()]
    out = {"folded": "\n".join(lines), "samples": samples,
           "duration_s": duration_s}
    if timeline:
        out["timeline"] = tl
    return out


def merge_folded(parts: list[str]) -> str:
    counts: Counter = Counter()
    for text in parts:
        for line in text.splitlines():
            if not line.strip():
                continue
            stack, _, n = line.rpartition(" ")
            try:
                counts[stack] += int(n)
            except ValueError:
                continue
    return "\n".join(f"{k} {v}" for k, v in counts.most_common())


# ---------------------------------------------------------------------------
# Flamegraph SVG (self-contained renderer for folded stacks)
# ---------------------------------------------------------------------------
_PALETTE = ["#d97757", "#e0906f", "#c96442", "#e8a87c", "#b85c3e",
            "#d4845f", "#cc7352"]


def render_flamegraph_svg(folded: str, title: str = "rtpu flamegraph",
                          width: int = 1200) -> str:
    """Folded stacks -> a self-contained SVG flamegraph (hover shows the
    frame + sample share)."""
    root: dict = {"children": {}, "value": 0}
    for line in folded.splitlines():
        stack, _, n = line.rpartition(" ")
        try:
            n = int(n)
        except ValueError:
            continue
        node = root
        node["value"] += n
        for frame in stack.split(";"):
            child = node["children"].setdefault(
                frame, {"children": {}, "value": 0})
            child["value"] += n
            node = child

    total = root["value"] or 1
    row_h, font = 17, 11
    rects: list[str] = []
    max_depth = [0]

    def esc(s: str) -> str:
        return (s.replace("&", "&amp;").replace("<", "&lt;")
                .replace(">", "&gt;").replace('"', "&quot;"))

    def layout(node, x0: float, depth: int):
        x = x0
        for i, (name, child) in enumerate(sorted(node["children"].items())):
            w = width * child["value"] / total
            if w < 0.5:
                continue
            y = depth * row_h
            max_depth[0] = max(max_depth[0], depth + 1)
            color = _PALETTE[(hash(name) ^ depth) % len(_PALETTE)]
            pct = 100.0 * child["value"] / total
            label = esc(name) if w > 40 else ""
            rects.append(
                f'<g><title>{esc(name)} — {child["value"]} samples '
                f'({pct:.1f}%)</title>'
                f'<rect x="{x:.1f}" y="{y}" width="{max(w - 0.5, 0.5):.1f}"'
                f' height="{row_h - 1}" fill="{color}" rx="1"/>'
                f'<text x="{x + 3:.1f}" y="{y + row_h - 5}" '
                f'font-size="{font}" font-family="monospace" '
                f'clip-path="inset(0)" fill="#1a1a18">'
                f'{label[:int(w // 7)]}</text></g>')
            layout(child, x, depth + 1)
            x += w

    layout(root, 0.0, 1)
    height = (max_depth[0] + 1) * row_h + 24
    header = (f'<text x="4" y="14" font-size="13" font-family="monospace" '
              f'fill="#3d3d3a">{esc(title)} — {total} samples</text>')
    return (f'<svg xmlns="http://www.w3.org/2000/svg" width="{width}" '
            f'height="{height}" viewBox="0 0 {width} {height}" '
            f'style="background:#faf9f5">{header}'
            + "".join(rects) + "</svg>")


# ---------------------------------------------------------------------------
# Gang-coordinated device capture (the `rtpu profile --device` unit)
# ---------------------------------------------------------------------------
# Each process answers a ``device_profile`` RPC with three layers for
# the window:
#   * device_steps — the deterministic spine: every accounted engine /
#     train step from the perfmodel ring (name, wall time, device/host
#     split, MFU, verdict). Always present, backend or not.
#   * host.timeline — sampling-profiler leaf frames with timestamps
#     (what the host was doing between device spans) + folded stacks.
#   * jax_trace — raw Chrome events from a ``jax.profiler`` trace
#     session when the backend supports it (best-effort: interpret-mode
#     CPU runs and jax-less workers degrade to the layers above).
# The driver merges windows from every process into one Chrome/Perfetto
# export, aligning each host's wall clock by RPC-measured RTT offsets.

_MAX_JAX_EVENTS = 20000


def _collect_jax_trace(tmpdir: str) -> dict:
    """Locate + parse the Chrome-format artifact a jax.profiler trace
    session left under ``tmpdir`` (perfetto_trace.json.gz or
    *.trace.json.gz). Returns {"events": [...]} or {"error": ...}."""
    import glob
    import gzip
    import json as _json
    import os

    paths = sorted(
        glob.glob(os.path.join(tmpdir, "**", "*.json.gz"), recursive=True),
        key=lambda p: ("perfetto" not in p, p))
    for path in paths:
        try:
            with gzip.open(path, "rt") as f:
                data = _json.load(f)
        except Exception:  # noqa: BLE001 - partial/foreign artifact
            continue
        events = (data.get("traceEvents", [])
                  if isinstance(data, dict) else data)
        if isinstance(events, list):
            return {"events": events[:_MAX_JAX_EVENTS],
                    "file": os.path.basename(path)}
    return {"error": "no chrome-format trace artifact produced"}


def _start_xla_trace(log_dir: str) -> None:
    """Start a jax.profiler trace into ``log_dir`` with the PYTHON
    tracer OFF. The default python tracer (PEP 523 eval hook)
    permanently hides threads that were alive during the session from
    ``sys._current_frames()`` — which would blind the host sampling
    profiler (`rtpu stack --flame`, the ``profile`` RPC) for the rest of
    the worker's life after one device capture. We carry our own host
    timeline anyway, so only the C++ host/device tracers run. Only the
    process that holds the chip can trace it."""
    import jax

    jax.devices()  # the backend must be up before the tracer starts
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, create_perfetto_trace=True,
                             profiler_options=opts)


def device_profile(duration_s: float = 2.0, hz: float = 99.0,
                   include_jax: bool = True) -> dict:
    """One capture window for THIS process: start an XLA profiler trace
    session, run the host sampling profiler for the window, stop the
    trace, and return all three layers plus the process's wall clock at
    the window edges (the driver's clock-alignment anchors)."""
    import shutil
    import tempfile

    from ray_tpu.util import perfmodel

    t0_wall = time.time()
    tmpdir = None
    jax_err = None
    if include_jax:
        tmpdir = tempfile.mkdtemp(prefix="rtpu-devprof-")
        try:
            _start_xla_trace(tmpdir)
        except Exception as e:  # noqa: BLE001 - capture must not kill
            jax_err = f"xla trace unavailable: {e!r}"
            shutil.rmtree(tmpdir, ignore_errors=True)
            tmpdir = None
    host = sample_profile(duration_s, hz, timeline=True)
    jax_trace: dict = {"error": jax_err or "jax trace disabled"}
    if tmpdir is not None:
        try:
            import jax

            jax.profiler.stop_trace()
            jax_trace = _collect_jax_trace(tmpdir)
        except Exception as e:  # noqa: BLE001
            jax_trace = {"error": f"trace export failed: {e!r}"}
        finally:
            shutil.rmtree(tmpdir, ignore_errors=True)
    return {
        "t0_wall": t0_wall,
        "t1_wall": time.time(),
        "host": host,
        "device_steps": perfmodel.device_step_events(since=t0_wall - 1.0),
        "jax_trace": jax_trace,
    }


def build_merged_trace(profiles: dict, offsets: dict | None = None,
                       spans: list | None = None) -> dict:
    """One Chrome/Perfetto trace from per-process capture windows.

    ``profiles``: {source_key: device_profile() result} as returned by
    cluster_device_profile (keys ``node:<id12>`` / ``worker:<node8>:<pid>``).
    ``offsets``: {node8_or_node12_prefix: seconds} to ADD to a host's
    wall timestamps to land on the driver's clock (from
    Runtime.clock_offsets(), RTT-midpoint estimates). ``spans``: request
    spans (tracing-ring dicts with start/duration/name/trace_id) merged
    onto their own track.

    Tracks per process: ``device-steps`` (accounted engine/train steps,
    colored by roofline verdict), ``host-cpu`` (sampling-profiler leaf
    frames), and the raw jax trace events re-based onto the aligned
    clock. Times are Chrome-trace microseconds."""
    offsets = offsets or {}
    events: list = []
    pids: dict = {}

    def pid_for(source: str) -> int:
        if source not in pids:
            pids[source] = len(pids) + 1
            events.append({"ph": "M", "pid": pids[source], "tid": 0,
                           "name": "process_name",
                           "args": {"name": source}})
        return pids[source]

    def offset_for(source: str) -> float:
        # source keys carry the node id prefix: node:<id12> or
        # worker:<node8>:<pid> — match either prefix length.
        for key, off in offsets.items():
            if key and key in source:
                return off
        return 0.0

    for source, prof in sorted(profiles.items()):
        if not isinstance(prof, dict) or "t0_wall" not in prof:
            continue
        pid = pid_for(source)
        shift_us = offset_for(source) * 1e6

        for ev in prof.get("device_steps", []):
            dur_ms = float(ev.get("step_ms", 0.0))
            dev_ms = float(ev.get("device_ms", 0.0))
            t_us = ev["t_wall"] * 1e6 + shift_us
            args = {k: v for k, v in ev.items()
                    if k not in ("name", "t_wall")}
            events.append({"ph": "X", "pid": pid, "tid": 1,
                           "name": ev.get("name", "step"),
                           "ts": t_us, "dur": max(dur_ms * 1e3, 1.0),
                           "args": args,
                           "cname": {"host": "terrible_input_latency",
                                     "hbm": "thread_state_iowait",
                                     }.get(ev.get("verdict"),
                                           "thread_state_running")})
            if 0.0 < dev_ms < dur_ms:
                events.append({"ph": "X", "pid": pid, "tid": 1,
                               "name": "device", "ts": t_us,
                               "dur": dev_ms * 1e3,
                               "args": {"device_ms": dev_ms}})
        host = prof.get("host", {})
        tl = host.get("timeline", [])
        # Leaf-frame samples render as fixed-width slices at the sample
        # cadence — a poor man's timeline flamegraph next to the device
        # track.
        interval_us = (prof["t1_wall"] - prof["t0_wall"]) * 1e6 \
            / max(len(tl), 1)
        for t_wall, leaf in tl:
            events.append({"ph": "X", "pid": pid, "tid": 2,
                           "name": leaf, "ts": t_wall * 1e6 + shift_us,
                           "dur": max(min(interval_us, 20000.0), 1.0)})
        events.append({"ph": "M", "pid": pid, "tid": 1,
                       "name": "thread_name",
                       "args": {"name": "device-steps"}})
        events.append({"ph": "M", "pid": pid, "tid": 2,
                       "name": "thread_name",
                       "args": {"name": "host-cpu"}})
        for ev in prof.get("jax_trace", {}).get("events", []):
            if not isinstance(ev, dict):
                continue
            ev = dict(ev)
            # Re-namespace jax pids under this process and shift onto
            # the aligned clock.
            ev["pid"] = pid * 1000 + int(ev.get("pid", 0)) % 1000
            if "ts" in ev:
                ev["ts"] = float(ev["ts"]) + shift_us
            events.append(ev)

    if spans:
        pid = pid_for("requests")
        tids: dict = {}
        for sp in spans:
            trace = sp.get("trace_id", "?")[:8]
            if trace not in tids:
                tids[trace] = len(tids) + 1
                events.append({"ph": "M", "pid": pid, "tid": tids[trace],
                               "name": "thread_name",
                               "args": {"name": f"trace {trace}"}})
            start = float(sp.get("start", 0.0))
            dur_s = float(sp.get("duration",
                                 float(sp.get("end", start)) - start))
            events.append({
                "ph": "X", "pid": pid, "tid": tids[trace],
                "name": sp.get("name", "span"),
                "ts": start * 1e6,
                "dur": max(dur_s * 1e6, 1.0),
                "args": dict(sp.get("attributes") or {},
                             trace_id=sp.get("trace_id")),
            })
    return {"traceEvents": events, "displayTimeUnit": "ms"}


# ---------------------------------------------------------------------------
# Heap snapshots (tracemalloc)
# ---------------------------------------------------------------------------
def heap_snapshot(top_n: int = 25) -> dict:
    """Top allocation sites of THIS process. tracemalloc starts on the
    first call (a second snapshot sees allocations since then; the
    reference's memray attach has the same 'from now on' semantics)."""
    import tracemalloc

    if not tracemalloc.is_tracing():
        tracemalloc.start(10)
        return {"started": True, "top": [],
                "note": "tracemalloc just started — snapshot again to "
                        "see allocations from this point on"}
    snap = tracemalloc.take_snapshot()
    stats = snap.statistics("traceback")[:top_n]
    top = []
    for st in stats:
        frames = [f"{f.filename.rsplit('/', 1)[-1]}:{f.lineno}"
                  for f in st.traceback[-4:]]
        top.append({"size_kb": round(st.size / 1024, 1),
                    "count": st.count, "trace": " < ".join(frames)})
    current, peak = tracemalloc.get_traced_memory()
    return {"started": False, "top": top,
            "current_kb": round(current / 1024, 1),
            "peak_kb": round(peak / 1024, 1)}
