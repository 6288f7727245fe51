"""From a profiler trace to the few numbers the benchmark reports.

``read_xplane`` turns the ``.xplane.pb`` file that ``jax.profiler``
writes into plain rows ``(plane, line, name, start_ns,
duration_ns)``, read with ``jax.profiler.ProfileData`` and nothing else, and
never truncated. ``reduce_rows`` works on such rows only, so it is
checked in ``benchmark/tests`` on a small trace kept as JSON.

What the reduction takes from a trace:
  window_s    the trace's own span: from the first device operation's
              start to the last one's end, over all device planes (the
              device's clock; the host's clock is not read)
  busy_s      per device plane, the union of the intervals of every
              event on its op lines; the mean over the device planes
  op_self_s   per event name, its self time: its duration minus what
              the events nested inside it on the same line cover (an
              XLA ``while`` or ``fusion`` parent holds its children)
  op_calls    per event name, how many events
  modules     per compiled program (``XLA Modules`` line), executions
              and seconds
On the TPU an op event's name is the instruction's whole HLO text, and
the events carry nothing else worth keeping: a kernel is found by
what that text holds (``custom_call_target="tpu_custom_call"`` and, at
its head, the instruction's name: ``named_kernels.py``). ``short_name``
cuts a name down for the breakdown.
A device plane is one whose name starts with ``/device:`` and is not a
``/device:CUSTOM`` or host plane; an op line is one named ``XLA Ops``
(TPU) or, failing that, any line of a device plane that is not a
``Steps``, ``XLA Modules`` or ``XLA TraceMe`` line.
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict

NOT_OP_LINES = ("Steps", "XLA Modules", "XLA TraceMe", "Framework Ops",
                "Framework Name Scope", "Source code", "Sparse Core Steps")


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def read_xplane(path: str) -> list:
    """Rows of every event of the device planes."""
    from jax.profiler import ProfileData

    rows = []
    for plane in ProfileData.from_file(path).planes:
        if not is_device_plane(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                rows.append((plane.name, line.name, ev.name,
                             float(ev.start_ns), float(ev.duration_ns)))
    return rows


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CUSTOM" not in name \
        and "host" not in name.lower()


def _op_lines(rows: list) -> dict:
    """{plane: [rows of its op lines]} for the device planes."""
    by_plane = defaultdict(lambda: defaultdict(list))
    for row in rows:
        if is_device_plane(row[0]):
            by_plane[row[0]][row[1]].append(row)
    out = {}
    for plane, lines in by_plane.items():
        if "XLA Ops" in lines:
            out[plane] = {"XLA Ops": lines["XLA Ops"]}
        else:
            out[plane] = {k: v for k, v in lines.items()
                          if k not in NOT_OP_LINES}
    return out


def union_s(intervals: list) -> float:
    """Total length of the union of (start_ns, end_ns) intervals, in
    seconds."""
    total, cur_a, cur_b = 0.0, None, None
    for a, b in sorted(intervals):
        if cur_b is None or a > cur_b:
            if cur_b is not None:
                total += cur_b - cur_a
            cur_a, cur_b = a, b
        elif b > cur_b:
            cur_b = b
    if cur_b is not None:
        total += cur_b - cur_a
    return total / 1e9


def self_times(line_rows: list) -> list:
    """[(name, self_ns)] for the events of ONE line. An event
    that lies inside another on the same line is its child; a parent's
    self time is its duration less its direct children's."""
    evs = sorted(line_rows, key=lambda r: (r[3], -r[4]))
    out = []
    stack = []      # [end_ns, index into out]
    for _, _, name, start, dur in evs:
        end = start + dur
        while stack and start >= stack[-1][0]:
            stack.pop()
        if stack:
            parent = out[stack[-1][1]]
            parent[1] -= min(dur, stack[-1][0] - start)
        out.append([name, dur])
        stack.append((end, len(out) - 1))
    return [(n, max(s, 0.0)) for n, s in out]


def short_name(name: str) -> str:
    """A TPU op event is named by its whole HLO text. Keep the
    instruction's name, its opcode, its first result shape and, for a
    custom call, its target: ``%copy.61 copy bf16[1,12,8192,16,64]``."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name[:120]
    shape = rest
    if rest.startswith("("):        # a tuple of results: skip to its end
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                shape, rest = rest[1:i], rest[i + 1:].lstrip()
                break
    else:
        shape, _, rest = rest.partition(" ")
    first = shape.split("{")[0].split(", ")[0]
    opcode = rest.split("(")[0].strip()
    target = ""
    if "custom_call_target=" in name:
        target = " " + name.split('custom_call_target="')[1].split('"')[0]
    return f"{head} {opcode}{target} {first}"[:120]


def reduce_rows(rows: list) -> dict:
    """The reduced trace the per-layer readers see."""
    planes = _op_lines(rows)
    spans = [(r[3], r[3] + r[4]) for lines in planes.values()
             for line_rows in lines.values() for r in line_rows if r[4] > 0]
    window_s = (max(b for _, b in spans) - min(a for a, _ in spans)) / 1e9 \
        if spans else 0.0
    modules = defaultdict(lambda: [0, 0.0])
    for plane, line, name, _, dur in rows:
        if is_device_plane(plane) and line == "XLA Modules":
            modules[name][0] += 1
            modules[name][1] += dur / 1e9
    busy = []
    op_self = defaultdict(float)
    op_calls = defaultdict(int)
    for plane, lines in planes.items():
        intervals = []
        for line_rows in lines.values():
            intervals += [(r[3], r[3] + r[4]) for r in line_rows if r[4] > 0]
            for name, self_ns in self_times(line_rows):
                op_self[name] += self_ns / 1e9
                op_calls[name] += 1
        busy.append(union_s(intervals))
    n = max(len(planes), 1)
    return {
        "device_planes": sorted(planes),
        "window_s": window_s,
        "busy_s": sum(busy) / n,
        # Per-op figures are sums over the device planes, divided by
        # their number: seconds on the average chip.
        "op_self_s": {k: v / n for k, v in op_self.items()},
        "op_calls": {k: v / n for k, v in op_calls.items()},
        # Executions of each compiled program: {name: [calls, seconds]}.
        "modules": {k: [c / n, t / n] for k, (c, t) in modules.items()},
    }


def top_ops(reduced: dict, n: int = 10) -> list:
    ops = sorted(reduced["op_self_s"].items(), key=lambda kv: -kv[1])
    return [[short_name(name), secs] for name, secs in ops[:n]]


def idle_pct(reduced: dict) -> float:
    """Share of the traced span in which no operation ran on the
    device: 1 - (union of the device's op intervals) / span, both on
    the device's clock."""
    return 100.0 * (1.0 - reduced["busy_s"] / reduced["window_s"])
