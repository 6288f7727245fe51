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

import os
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
# The process's CPU by thread
# ---------------------------------------------------------------------------
# The threads a serving or training process is known to run, by the
# prefix of their names; a Python thread of any other name is in
# ``other``, a task of the process that is no Python thread (the XLA
# and TPU runtimes', a native store's) in ``native``.
THREAD_GROUPS = ("serve-http", "serve-stream-poll", "llm-engine",
                 "rt-core-loop", "actor", "device-exec", "asyncio",
                 "MainThread", "train-loop")
_threads_lock = threading.Lock()
_threads_seen: dict = {}     # tid -> (group, cpu ns, wait ns)
_threads_gone: dict = {}     # group -> [cpu ns, wait ns] of ended threads


def _task_times(tid: str):
    """(on-CPU ns, run-queue wait ns or None) of one task of this
    process: the scheduler's own record where the kernel keeps it
    (``schedstat``), else ``utime + stime`` in clock ticks and no wait;
    None for a task that ended meanwhile."""
    try:
        with open(f"/proc/self/task/{tid}/schedstat") as f:
            run_ns, wait_ns, _ = f.read().split()
        return int(run_ns), int(wait_ns)
    except (OSError, ValueError):
        pass
    try:
        with open(f"/proc/self/task/{tid}/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        ticks = int(fields[11]) + int(fields[12])
        return ticks * 10**9 // os.sysconf("SC_CLK_TCK"), None
    except (OSError, ValueError, IndexError):
        return None


def thread_cpu() -> dict:
    """The process's CPU time by thread group, cumulative: two readings
    give a window's, and who had the cores in it.

      by_group       {group: {"threads": live threads in it now,
                     "cpu_s": on-CPU seconds, "wait_s": seconds
                     runnable and waiting for a core (None where the
                     kernel keeps no ``schedstat``)}}; a group is a
                     name of ``THREAD_GROUPS`` that the thread's name
                     starts with, else ``other``; ``native`` for tasks
                     that are no Python thread
      process_cpu_s  ``time.process_time()`` at the reading, which the
                     groups' growth should add up to

    A thread that has ended keeps what it had used when it was last
    seen, so a group only grows. Read where somebody asks
    (``engine_stats()``, a ``device_profile`` capture), never in a
    step: a reading opens a file a task."""
    names = {str(t.native_id): t.name for t in threading.enumerate()
             if t.native_id is not None}
    try:
        tids = os.listdir("/proc/self/task")
    except OSError:
        tids = []
    live: dict = {}
    for tid in tids:
        times = _task_times(tid)
        if times is None:
            continue
        name = names.get(tid)
        group = "native" if name is None else next(
            (g for g in THREAD_GROUPS if name.startswith(g)), "other")
        live[tid] = (group, *times)
    # One kernel, one answer: a wait for every task or for none.
    waits = all(wait is not None for _, _, wait in live.values())
    totals: dict = {}           # group -> [live threads, cpu ns, wait ns]
    with _threads_lock:
        for tid, (group, cpu, wait) in _threads_seen.items():
            now = live.get(tid)
            if now is None or now[0] != group or now[1] < cpu:
                # Ended (or its id is another thread's now): what it
                # had used stays in its group.
                gone = _threads_gone.setdefault(group, [0, 0])
                gone[0] += cpu
                gone[1] += wait or 0
        _threads_seen.clear()
        _threads_seen.update(live)
        for group, (cpu, wait) in _threads_gone.items():
            totals[group] = [0, cpu, wait]
    for group, cpu, wait in live.values():
        total = totals.setdefault(group, [0, 0, 0])
        total[0] += 1
        total[1] += cpu
        total[2] += wait or 0
    return {"by_group": {
                group: {"threads": n, "cpu_s": cpu / 1e9,
                        "wait_s": wait / 1e9 if waits else None}
                for group, (n, cpu, wait) in totals.items()},
            "process_cpu_s": time.process_time()}


def format_thread_cpu(a: dict, b: dict) -> str:
    """Two ``thread_cpu`` readings of one process as `rtpu profile
    --device` prints them: CPU seconds a second of the window by group,
    busiest first, and the same for the wait for a core."""
    span = b["process_cpu_s"] - a["process_cpu_s"]
    rows = []
    for group, now in b["by_group"].items():
        was = a["by_group"].get(group, {"cpu_s": 0.0, "wait_s": 0.0})
        wait = (None if now["wait_s"] is None or was["wait_s"] is None
                else now["wait_s"] - was["wait_s"])
        rows.append((now["cpu_s"] - was["cpu_s"], group, now["threads"],
                     wait))
    rows.sort(key=lambda r: -r[0])
    total = sum(r[0] for r in rows)
    lines = [f"  CPU by thread: {total:.3f} s on the cores (the process's "
             f"CPU clock: {span:.3f} s), by group (threads, CPU s, s "
             f"runnable and waiting for a core):"]
    for cpu, group, n, wait in rows:
        lines.append(f"    {group:<20} {n:4d} {cpu:9.3f} "
                     + ("        -" if wait is None else f"{wait:9.3f}"))
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# Gang-coordinated device capture (the `rtpu profile --device` unit)
# ---------------------------------------------------------------------------
# Each process answers a ``device_profile`` RPC with five layers for
# the window:
#   * threads — the process's CPU by thread group at the window's two
#     edges (``thread_cpu``): who had the cores while the device idled.
#   * device_steps — the deterministic spine: every accounted engine /
#     train step from the perfmodel ring (name, wall time, device/host
#     split, phases, counts, MFU, verdict). Always present, backend or
#     not.
#   * host.timeline — sampling-profiler leaf frames with timestamps
#     (what the host was doing between device spans) + folded stacks.
#   * idle_gaps — the device's idle intervals put down to the host
#     phase (util/perfmodel.PHASES) that covers them, read from ALL
#     rows of the ``jax.profiler`` session's ``.xplane.pb``: device ops
#     and the program's TraceAnnotations lie on one clock there.
#   * jax_trace — the same rows as Chrome events for the merged export
#     (best-effort: interpret-mode CPU runs and jax-less workers degrade
#     to the layers above).
# The driver merges windows from every process into one Chrome/Perfetto
# export, aligning each host's wall clock by RPC-measured RTT offsets.

# Lines of a device plane that do not hold operations (the TPU's op
# line is ``XLA Ops``; other backends' are whatever is not one of
# these).
_NOT_OP_LINES = ("Steps", "XLA Modules", "XLA TraceMe", "Framework Ops",
                 "Framework Name Scope", "Source code", "Sparse Core Steps")
# The merged Chrome export keeps this many device ops a process, the
# longest ones; every reduction reads all rows.
_EXPORT_OPS = 20000


def _is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CUSTOM" not in name \
        and "host" not in name.lower()


def read_xplane(trace_dir: str) -> tuple:
    """``(rows, start_wall)`` of the newest ``.xplane.pb`` under
    ``trace_dir``. ``rows``: every event as ``(plane, line, name,
    start_ns, duration_ns)``, host planes and device planes alike, never
    truncated; times are the profiler's own, nanoseconds from the
    session's start, one clock for all planes. ``start_wall``: that
    start on the wall clock (seconds; the session's own record of it),
    None where the file does not say."""
    import glob
    import os

    from jax.profiler import ProfileData

    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    rows, start_wall = [], None
    for plane in ProfileData.from_file(found[-1]).planes:
        if plane.name == "Task Environment":
            start_ns = dict(plane.stats).get("profile_start_time")
            if start_ns:
                start_wall = float(start_ns) / 1e9
        for line in plane.lines:
            for ev in line.events:
                rows.append((plane.name, line.name, ev.name,
                             float(ev.start_ns), float(ev.duration_ns)))
    return rows, start_wall


def _op_intervals(rows: list) -> dict:
    """{device plane: [(start_ns, end_ns)] of its op lines' events}."""
    lines: dict = {}
    for plane, line, _, start, dur in rows:
        if _is_device_plane(plane) and dur > 0:
            lines.setdefault(plane, {}).setdefault(line, []).append(
                (start, start + dur))
    out = {}
    for plane, by_line in lines.items():
        keep = (["XLA Ops"] if "XLA Ops" in by_line
                else [n for n in by_line if n not in _NOT_OP_LINES])
        out[plane] = sorted(iv for n in keep for iv in by_line[n])
    return out


def _gaps(intervals: list) -> list:
    """The complement of the union of sorted (start, end) intervals,
    from the first start to the last end."""
    gaps, reach = [], None
    for a, b in intervals:
        if reach is not None and a > reach:
            gaps.append((reach, a))
        reach = b if reach is None else max(reach, b)
    return gaps


def _cover(a: float, b: float, spans: list, max_end: list) -> dict:
    """{name: ns} of the gap (a, b) under ``spans`` (sorted (start, end,
    name); ``max_end`` their running maximum end). Where spans overlap
    one another the one that started last, the innermost, has the
    instant."""
    from bisect import bisect_left, bisect_right

    lo = bisect_right(max_end, a)
    hi = bisect_left(spans, (b,))
    hits = [(max(s, a), min(e, b), s, name)
            for s, e, name in spans[lo:hi] if e > a]
    if len(hits) == 1:          # the common case: one phase holds it
        return {hits[0][3]: hits[0][1] - hits[0][0]}
    cuts = sorted({t for h in hits for t in h[:2]})
    out: dict = {}
    for x, y in zip(cuts, cuts[1:]):
        over = [h for h in hits if h[0] <= x and h[1] >= y]
        if over:
            name = max(over, key=lambda h: h[2])[3]
            out[name] = out.get(name, 0.0) + (y - x)
    return out


def idle_gaps(rows: list, names=None, top: int = 10) -> dict:
    """The device's idle time by what the host was doing in it.

    ``rows`` are ``read_xplane``'s. An idle interval is a gap in
    the union of a device plane's op intervals, first to last op (with
    several device planes, every plane's gaps: idle chip-seconds). The
    host events named in ``names`` (default: the span registry,
    util/perfmodel.PHASES, and behind it the steps, perfmodel.STEPS)
    cover it or do not:

      idle_s       all gaps
      by_phase_s   {name: seconds of gaps lying under that span}
      uncovered_s  seconds of gaps under no named span
      longest      the ``top`` longest gaps as [gap_ms, the name that
                   covers most of it (None: none does), that name's
                   share of the gap]

    A device span's name (``llm.decode.device``, ``train.wait``) over a
    gap says the host was only waiting there; a host phase's says the
    device waited for that work; a step's own name (``llm.step``) has
    what lies inside a step under none of its spans: the innermost
    span has the instant, so that is the step's ``other_ms``."""
    if names is None:
        from ray_tpu.util import perfmodel

        names = set(perfmodel.PHASES) | set(perfmodel.STEPS)
    spans = sorted((start, start + dur, name)
                   for plane, _, name, start, dur in rows
                   if name in names and dur > 0
                   and not _is_device_plane(plane))
    max_end, reach = [], float("-inf")
    for _, e, _ in spans:
        reach = max(reach, e)
        max_end.append(reach)
    by_phase: dict = {}
    idle = uncovered = 0.0
    longest = []
    for intervals in _op_intervals(rows).values():
        for a, b in _gaps(intervals):
            cover = _cover(a, b, spans, max_end)
            for name, ns in cover.items():
                by_phase[name] = by_phase.get(name, 0.0) + ns
            idle += b - a
            uncovered += (b - a) - sum(cover.values())
            name = max(cover, key=cover.get) if cover else None
            longest.append([(b - a) / 1e6, name,
                            cover[name] / (b - a) if cover else 0.0])
    longest.sort(key=lambda g: -g[0])
    return {"idle_s": idle / 1e9,
            "by_phase_s": {k: v / 1e9 for k, v in sorted(
                by_phase.items(), key=lambda kv: -kv[1])},
            "uncovered_s": uncovered / 1e9,
            "longest": longest[:top]}


def format_idle_gaps(table: dict) -> str:
    """The idle-gap table as `rtpu profile --device` prints it."""
    from ray_tpu.util import perfmodel

    idle = table["idle_s"]
    if idle <= 0:
        return "  no idle gap between device ops in this window"
    lines = [f"  device idle {idle * 1e3:.1f} ms between its first and "
             f"last op, by the host span over each gap:"]
    for name, s in list(table["by_phase_s"].items()) + [
            ("(no named span)", table["uncovered_s"])]:
        if name in perfmodel.STEPS:
            name += " (no phase)"
        lines.append(f"    {name:<22} {s * 1e3:9.2f} ms  "
                     f"{100 * s / idle:5.1f}%")
    lines.append("  longest gaps (ms, span over most of it, its share):")
    for gap_ms, name, share in table["longest"]:
        lines.append(f"    {gap_ms:9.3f}  {name or '-':<22} "
                     f"{100 * share:5.1f}%")
    return "\n".join(lines)


def _interval_line(e: dict) -> str:
    """One step interval (previous finish() to this one) by its parts,
    all ms: the gap before the step, its device spans by kind
    (dispatch / wait where the span was cut), its host phases over
    1 ms, the collector's passes that ended in it, the thread's CPU
    time and the time it had work and was not running, the interpreter
    probe's long samples in it (the process stood still, or a thread
    kept the interpreter), and the step's load."""
    parts = [f"between {e.get('between_ms', 0.0):.1f} (lock "
             f"{e['lock_wait_ms']:.1f}, idle {e['idle_ms']:.1f})"]
    dispatch = e["dispatch_ms_by"]
    for kind, ms in sorted(e["device_ms_by"].items(), key=lambda kv: -kv[1]):
        cut = (f" ({dispatch[kind]:.1f}/{ms - dispatch[kind]:.1f})"
               if kind in dispatch else "")
        parts.append(f"{kind} {ms:.1f}{cut}")
    parts += [f"{k} {ms:.1f}" for k, ms in sorted(
        e["phases_ms"].items(), key=lambda kv: -kv[1]) if ms > 1.0]
    line = f"      {e['interval_ms']:8.1f} = {' + '.join(parts)}"
    if e.get("gc_gen") is not None:
        line += f"; gc {e['gc_ms']:.1f} (gen {e['gc_gen']})"
    line += f"; cpu {e['cpu_ms']:.1f}, stall {e['stall_ms']:.1f}"
    if "standstill_ms" in e:
        line += (f"; standstill {e['standstill_ms']:.1f}, held long "
                 f"{e['held_long_ms']:.1f}")
    if "lanes" in e:
        line += (f"; lanes {e['lanes']}, chunk tokens "
                 f"{e['prefill_tokens']}, arrived {e.get('arrived', 0)}")
    return line


def format_device_steps(steps: list) -> str:
    """A window's accounted steps (``device_steps``: the perfmodel
    ring's entries) as `rtpu profile --device` prints them, one block a
    step name and owner: the mean step split into its device spans by
    kind (and the dispatch inside each) and its host phases by name,
    an engine's own counts (``programs`` / ``programs_queued`` among
    them), the interpreter probe's totals over the window
    (``perfmodel._InterpreterProbe``), what lies between steps, and the
    window's five longest step intervals, each by its parts
    (``_interval_line``)."""
    from ray_tpu.util import perfmodel

    groups: dict = {}
    for ev in steps:
        owner = ev.get("deployment") or ev.get("trial") or ""
        groups.setdefault((ev["name"], owner), []).append(ev)
    lines = []
    for (name, owner), evs in sorted(groups.items()):
        n = len(evs)

        def mean(key, sub=None):
            vals = [(e.get(key) or {}).get(sub, 0.0) if sub else
                    e.get(key, 0.0) for e in evs]
            return sum(vals) / n

        def names(key):
            return sorted({k for e in evs for k in e.get(key) or {}},
                          key=lambda k: -mean(key, k))

        cut = set(names("dispatch_ms_by"))
        by = ", ".join(
            f"{k} {mean('device_ms_by', k):.2f}"
            + (f" [dispatch {mean('dispatch_ms_by', k):.2f}]"
               if k in cut else "")
            for k in names("device_ms_by"))
        lines.append(
            f"  {name} x {n}{' (' + owner + ')' if owner else ''}: "
            f"{mean('step_ms'):.2f} ms a step = device "
            f"{mean('device_ms'):.2f}{' (' + by + ')' if by else ''} + "
            f"host {mean('host_gap_ms'):.2f}")
        phases = [f"{k} {mean('phases_ms', k):.2f}"
                  for k in names("phases_ms")]
        if phases:
            lines.append(f"    host by phase: {', '.join(phases)}, "
                         f"other {mean('other_ms'):.2f}")
        if "lanes" in evs[0]:
            on_device = mean(perfmodel.DEVICE_SAMPLED)
            lines.append(
                f"    lanes {mean('lanes'):.1f} of {evs[0]['max_batch']}"
                f" ({on_device:.1f} decided on the device)"
                f" over {mean('context_tokens'):.0f} context tokens; "
                f"tokens computed: decode "
                f"{sum(e['decode_tokens'] for e in evs)}, prefill "
                f"{sum(e['prefill_tokens'] for e in evs)} in "
                f"{sum(len(e['prefill_chunks']) for e in evs)} chunk(s); "
                f"waiting {max(e['waiting'] for e in evs)} at most; "
                f"preempted {sum(e['preempted'] for e in evs)}")
            if any("programs" in e for e in evs):
                lines[-1] += (
                    f"; programs {sum(e.get('programs', 0) for e in evs)}"
                    f", {sum(e.get('programs_queued', 0) for e in evs)} "
                    f"queued before their step's first wait")
        timed = [e for e in evs if "interval_ms" in e]
        probed = [e for e in timed if e.get("interp_n")]
        if probed:
            n = sum(e["interp_n"] for e in probed)

            def total(key):
                return sum(e[key] for e in probed)

            lines.append(
                f"    interpreter probe: {n} samples, "
                f"{total('interp_late_ms') / n:.2f} ms late a sample "
                f"(longest "
                f"{max(e['interp_late_max_ms'] for e in probed):.1f}), "
                f"{100.0 * total('interp_held_n') / n:.1f}% found it "
                f"held; the process stood still "
                f"{total('standstill_ms'):.1f} ms, a thread kept the "
                f"interpreter long {total('held_long_ms'):.1f} ms")
        if timed:
            lines.append(
                f"    between steps {mean('between_ms'):.2f} (lock "
                f"{mean('lock_wait_ms'):.2f}, idle {mean('idle_ms'):.2f}),"
                f" cpu {mean('cpu_ms'):.2f}, stall {mean('stall_ms'):.2f},"
                f" gc {mean('gc_ms'):.2f} ms a step; the longest intervals, "
                f"finish to finish (ms):")
            lines += [_interval_line(e) for e in sorted(
                timed, key=lambda e: -e["interval_ms"])[:5]]
    return "\n".join(lines)


def _trace_events(rows: list, t0_wall: float) -> list:
    """``rows`` as Chrome "X" events on the wall clock (the session
    started at ``t0_wall``): the program's named spans and steps from
    the host planes, and the device planes' modules, steps and ops (the
    ``_EXPORT_OPS`` longest, names cut to a line)."""
    from ray_tpu.util import perfmodel

    named = set(perfmodel.PHASES) | set(perfmodel.STEPS)
    ops = [r for r in rows if _is_device_plane(r[0])
           and r[1] not in _NOT_OP_LINES]
    if len(ops) > _EXPORT_OPS:
        ops = sorted(ops, key=lambda r: -r[4])[:_EXPORT_OPS]
    keep = ops + [r for r in rows
                  if (_is_device_plane(r[0])
                      and r[1] in ("XLA Modules", "Steps"))
                  or (not _is_device_plane(r[0]) and r[2] in named)]
    pids: dict = {}
    tids: dict = {}
    events = []
    for plane, line, name, start, dur in keep:
        if plane not in pids:
            pids[plane] = len(pids) + 1
            events.append({"ph": "M", "pid": pids[plane], "tid": 0,
                           "name": "process_name",
                           "args": {"name": plane}})
        if (plane, line) not in tids:
            tids[plane, line] = len(tids) + 1
            events.append({"ph": "M", "pid": pids[plane],
                           "tid": tids[plane, line],
                           "name": "thread_name", "args": {"name": line}})
        events.append({"ph": "X", "pid": pids[plane],
                       "tid": tids[plane, line], "name": name[:120],
                       "ts": t0_wall * 1e6 + start / 1e3,
                       "dur": max(dur / 1e3, 0.001)})
    return events


def _start_xla_trace(log_dir: str) -> float:
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
    # The wall clock just before the session starts: the anchor of its
    # rows where the trace does not record its own start.
    t_wall = time.time()
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    return t_wall


def device_profile(duration_s: float = 2.0, hz: float = 99.0,
                   include_jax: bool = True) -> dict:
    """One capture window for THIS process: start an XLA profiler trace
    session, run the host sampling profiler for the window, stop the
    trace, and return all five layers plus the process's wall clock at
    the window edges (the driver's clock-alignment anchors)."""
    import shutil
    import tempfile

    from ray_tpu.util import perfmodel

    t0_wall = time.time()
    threads0 = thread_cpu()
    tmpdir = None
    jax_err = None
    if include_jax:
        tmpdir = tempfile.mkdtemp(prefix="rtpu-devprof-")
        try:
            t_trace_wall = _start_xla_trace(tmpdir)
        except Exception as e:  # noqa: BLE001 - capture must not kill
            jax_err = f"xla trace unavailable: {e!r}"
            shutil.rmtree(tmpdir, ignore_errors=True)
            tmpdir = None
    host = sample_profile(duration_s, hz, timeline=True)
    jax_trace: dict = {"error": jax_err or "jax trace disabled"}
    gaps = None
    if tmpdir is not None:
        try:
            import jax

            jax.profiler.stop_trace()
            rows, start_wall = read_xplane(tmpdir)
            gaps = idle_gaps(rows)
            jax_trace = {"events": _trace_events(
                             rows, start_wall or t_trace_wall),
                         "rows": len(rows)}
        except Exception as e:  # noqa: BLE001
            jax_trace = {"error": f"trace export failed: {e!r}"}
        finally:
            shutil.rmtree(tmpdir, ignore_errors=True)
    return {
        "t0_wall": t0_wall,
        "t1_wall": time.time(),
        "host": host,
        "threads": (threads0, thread_cpu()),
        "device_steps": perfmodel.device_step_events(since=t0_wall - 1.0),
        "idle_gaps": gaps,
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
    frames), and the jax trace events (device ops, programs and the
    program's named host spans) re-based onto the aligned clock. Times
    are Chrome-trace microseconds."""
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
