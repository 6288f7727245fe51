"""The closed-loop callers of the serving cells, in generator
subprocesses of their own.

A caller is a client: in a deployment it is never a thread of the
server. So the cells' callers run in ``python -m
benchmark.drivers.callers`` processes that share with the served
process a loopback socket and the machine's monotonic clock, and
nothing else: a generator imports neither ``jax`` nor ``ray_tpu``
(it says so in its report), takes no device and no share of the
server's interpreter.

This file holds both ends. The generator's: ``stream`` (one streaming
request, every frame stamped as it is read; the drivers' set-up
requests use it too, in their own process, before the window),
``requests_of`` (caller ``c``'s requests, built from
``traffic.closed_loop_plan`` by caller index, so a generator needs the
cell's traffic, ``--seed`` and the vocabulary and no list of prompts),
``_Caller`` (one closed-loop caller on its own thread and connection)
and ``main``. The driver's: ``Fleet``, which starts the generators,
hands them the instant the first caller is due, and at the window's
close tells them to cut their connections and takes their records.

What crosses between the two processes, as JSON lines on the
generator's stdin and stdout: the cell's parameters and ``ready`` in
set-up, one ``ping`` (the generator answers with its clock's reading,
which has to lie between the driver's two readings around it), the
start instant, and AFTER the close the window's edges one way and the
records and the report the other. During the window nothing but the
sockets.
"""

from __future__ import annotations

import http.client
import json
import os
import socket
import subprocess
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_CPU_SAMPLE_S = 0.25


def same_clock() -> None:
    """``time.perf_counter()`` has to be ``CLOCK_MONOTONIC``, the one
    clock every process of a machine reads alike: the generators' stamps
    and the driver's window edges are compared as they are."""
    how = time.get_clock_info("perf_counter").implementation
    if "CLOCK_MONOTONIC" not in how:
        raise SystemExit(f"benchmark: time.perf_counter() is {how!r}, not "
                         f"CLOCK_MONOTONIC: processes do not share it")


class _Caller:
    """One closed-loop caller on its own thread and connection. It
    sends its first request at ``due`` on the shared clock."""

    def __init__(self, host, port, index, requests, records, stop, due):
        self.host, self.port, self.index = host, port, index
        self.requests = requests        # iterator of (meta, payload)
        self.records = records
        self.stop = stop
        self.due = due
        self.conn = None
        self.thread = threading.Thread(target=self._run, daemon=True,
                                       name=f"caller-{index}")

    def _run(self):
        self.stop.wait(max(0.0, self.due - time.perf_counter()))
        for meta, payload in self.requests:
            if self.stop.is_set():
                return
            self.records.append(stream(self.host, self.port, payload, meta,
                                       holder=self))

    def cut(self):
        conn = self.conn
        if conn is not None and conn.sock is not None:
            try:
                conn.sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass


def stream(host, port, payload, meta=None, holder=None) -> dict:
    """One streaming request. Every token frame is stamped as it is
    read; a request that errors, is refused or ends with
    ``finish_reason: "error"`` is ``failed``."""
    rec = dict(meta or {}, t_send=None, t_tokens=[], tokens=[], done=None,
               t_done=None, error=None)
    body = json.dumps(payload).encode()
    conn = http.client.HTTPConnection(host, port, timeout=300)
    if holder is not None:
        holder.conn = conn
    try:
        rec["t_send"] = time.perf_counter()
        conn.request("POST", "/", body,
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            rec["error"] = f"HTTP {resp.status}: {resp.read()[:200]!r}"
        else:
            for line in resp:
                now = time.perf_counter()
                if not line.strip():
                    continue
                frame = json.loads(line)
                if "token" in frame:
                    rec["t_tokens"].append(now)
                    rec["tokens"].append(frame["token"])
                elif frame.get("done"):
                    rec["done"], rec["t_done"] = frame, now
                elif "error" in frame:
                    rec["error"] = str(frame["error"])[:200]
    except (OSError, http.client.HTTPException, ValueError) as e:
        rec["error"] = f"{type(e).__name__}: {e}"
    finally:
        if holder is not None:
            holder.conn = None
        conn.close()
    rec["t_end"] = time.perf_counter()
    if rec["error"] is None and rec["done"] is None:
        rec["error"] = "stream ended without a done frame"
    if rec["done"] is not None and \
            rec["done"].get("finish_reason") == "error":
        rec["error"] = "finish_reason: error"
    rec["failed"] = rec["error"] is not None
    return rec


def requests_of(c: int, plan: dict):
    """Caller ``c``'s requests, for ever: (what the record keeps, what
    is sent). Its share of the cell's sizes round and round, each
    request's body drawn anew from (seed, caller, index)."""
    who, prefixes = plan["callers"][c], plan["prefixes"]
    index = 0
    while True:
        for body, answer in who["sizes"]:
            if index == 0:
                # Callers start at mixed phases of their answers.
                answer = max(1, round(answer * who["first_share"]))
            pre = prefixes[who["prefix"]] if who["prefix"] is not None \
                else []
            prompt = pre + plan["tokens"](c, index, body)
            yield ({"caller": c, "index": index, "prefix": who["prefix"],
                    "body": body, "prompt_len": len(prompt),
                    "max_tokens": answer},
                   {"prompt": prompt, "max_tokens": answer})
            index += 1


# -- the generator's end ----------------------------------------------------

class _CpuClock:
    """This process's CPU seconds (user + system, all threads) against
    the shared clock, sampled by a thread of its own, so that the CPU
    spent between two instants named AFTER the fact can be read off."""

    def __init__(self):
        self.samples = []
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="cpu-clock")

    def sample(self):
        self.samples.append((time.perf_counter(), time.process_time()))

    def _run(self):
        while not self._stop.wait(_CPU_SAMPLE_S):
            self.sample()

    def start(self):
        self.sample()
        self._thread.start()

    def stop(self):
        self._stop.set()
        self._thread.join(timeout=5)
        self.sample()

    def at(self, t: float) -> float:
        """CPU seconds spent by instant ``t``, between samples by a
        straight line."""
        s = self.samples
        if t <= s[0][0]:
            return s[0][1]
        for (t0, c0), (t1, c1) in zip(s, s[1:]):
            if t <= t1:
                return c0 + (c1 - c0) * (t - t0) / max(t1 - t0, 1e-9)
        return s[-1][1]


def _say(obj) -> None:
    sys.stdout.write(json.dumps(obj) + "\n")
    sys.stdout.flush()


def main() -> int:
    """One generator: the cell's parameters on the first line of stdin,
    then ``ping`` / ``start`` / ``stop`` lines. End of input without a
    ``stop`` (the driver died) cuts every connection and leaves."""
    from benchmark import traffic

    same_clock()
    cell = json.loads(sys.stdin.readline())
    plan = traffic.closed_loop_plan(cell["traffic"], cell["seed"],
                                    cell["vocab"])
    host, port = cell["host"], cell["port"]
    # The port answers before any caller is due.
    socket.create_connection((host, port), timeout=30).close()
    records, stop, cpu = [], threading.Event(), _CpuClock()
    callers, edges = [], None
    cpu.start()
    _say({"ready": True})
    for line in sys.stdin:
        msg = json.loads(line)
        if msg["op"] == "ping":
            _say({"pong": time.perf_counter()})
        elif msg["op"] == "start":
            # Caller c of n is due c / n of the stagger after the first.
            n = len(plan["callers"])
            callers = [_Caller(host, port, c, requests_of(c, plan), records,
                               stop, msg["t_first"]
                               + msg["stagger_s"] * c / n)
                       for c in cell["callers"]]
            for caller in callers:
                caller.thread.start()
        elif msg["op"] == "stop":
            edges = msg
            break
    stop.set()
    # A request that was just connecting when the word came has no
    # socket to cut yet: cut until every caller has ended.
    deadline = time.perf_counter() + 30.0
    while any(c.thread.is_alive() for c in callers) \
            and time.perf_counter() < deadline:
        for caller in callers:
            caller.cut()
        time.sleep(0.01)
    in_flight = sum(c.thread.is_alive() for c in callers)
    cpu.stop()
    if edges is None:
        return 1
    loaded = sorted(m for m in ("jax", "jaxlib", "ray_tpu")
                    if m in sys.modules)
    _say({"report": {
        "generator": cell["generator"], "pid": os.getpid(),
        "callers": cell["callers"], "in_flight": in_flight,
        "requests": len(records),
        "frames": sum(len(r["t_tokens"]) for r in records),
        "cpu_window_s": cpu.at(edges["t_close"]) - cpu.at(edges["t_open"]),
        "cpu_s": cpu.samples[-1][1],
        "loaded": loaded},
        "records": records})
    assert "jax" not in sys.modules, "a generator imported jax"
    return 0


# -- the driver's end -------------------------------------------------------

def generators_for(callers: int, cores) -> int:
    """Four generators for the cells' 64 callers (one interpreter
    parsing 64 streams is what saturated); two on a machine with fewer
    than six cores; never more than there are callers."""
    return max(1, min(4 if (cores or 1) >= 6 else 2, callers))


class Fleet:
    """The driver's handle on the generators of one run."""

    def __init__(self, host, port, traffic_spec, seed, vocab, log):
        same_clock()
        self.log = log
        self.n_callers = int(traffic_spec["callers"])
        cores = os.cpu_count()
        n = generators_for(self.n_callers, cores)
        log(f"callers: {self.n_callers} in {n} generator processes "
            f"(os.cpu_count() = {cores})")
        env = dict(os.environ)
        env["PYTHONPATH"] = os.pathsep.join(
            p for p in (ROOT, env.get("PYTHONPATH")) if p)
        self.procs = []
        for g in range(n):
            proc = subprocess.Popen(
                [sys.executable, "-m", "benchmark.drivers.callers"],
                stdin=subprocess.PIPE, stdout=subprocess.PIPE, cwd=ROOT,
                env=env, text=True)
            self.procs.append(proc)
            self._tell(proc, {
                "generator": g, "host": host, "port": port,
                "traffic": traffic_spec, "seed": int(seed),
                "vocab": int(vocab),
                "callers": list(range(g, self.n_callers, n))})

    @staticmethod
    def _tell(proc, msg) -> None:
        proc.stdin.write(json.dumps(msg) + "\n")
        proc.stdin.flush()

    def _hear(self, proc) -> dict:
        line = proc.stdout.readline()
        if not line:
            self.kill()
            raise SystemExit(f"benchmark: generator {proc.pid} ended "
                             f"without a word (exit code {proc.poll()})")
        return json.loads(line)

    def ready(self) -> list:
        """Wait for every generator; returns the checks of their
        clocks: a generator's reading between the driver's two."""
        checks = []
        for proc in self.procs:
            self._hear(proc)
            t0 = time.perf_counter()
            self._tell(proc, {"op": "ping"})
            theirs = self._hear(proc)["pong"]
            t1 = time.perf_counter()
            checks.append((t0 - 1e-3 <= theirs <= t1 + 1e-3,
                           f"generator {proc.pid} reads the driver's clock "
                           f"({(theirs - t0) * 1e3:.3f} ms after the ping "
                           f"left, {(t1 - theirs) * 1e3:.3f} ms before its "
                           f"answer was read)"))
        return checks

    def start(self, t_first: float, stagger_s: float) -> None:
        """Caller ``c`` is due at ``t_first + stagger_s * c / callers``."""
        for proc in self.procs:
            self._tell(proc, {"op": "start", "t_first": t_first,
                              "stagger_s": stagger_s})

    def close(self, t_open: float, t_close: float) -> dict:
        """Every generator cuts its connections; then their records,
        their reports and the callers still alive after the cut."""
        for proc in self.procs:
            self._tell(proc, {"op": "stop", "t_open": t_open,
                              "t_close": t_close})
        records, reports = [], []
        for proc in self.procs:
            said = self._hear(proc)
            records += said["records"]
            reports.append(said["report"])
            proc.stdin.close()
            proc.wait(timeout=60)
        for r in reports:
            self.log(f"  generator {r['generator']} (pid {r['pid']}): "
                     f"{len(r['callers'])} callers, {r['requests']} requests, "
                     f"{r['frames']} token frames, "
                     f"{r['cpu_window_s']:.3f} CPU s inside the window, "
                     f"{r['cpu_s']:.3f} in all, loaded {r['loaded'] or 'no'} "
                     f"module of the program")
        return {"records": records, "reports": reports,
                "in_flight": sum(r["in_flight"] for r in reports)}

    def checks(self, reports) -> list:
        return [(all(not r["loaded"] for r in reports),
                 f"no generator imported jax or ray_tpu "
                 f"({[r['loaded'] for r in reports]})"),
                (sorted(c for r in reports for c in r["callers"])
                 == list(range(self.n_callers)),
                 f"the generators' callers are the cell's "
                 f"{self.n_callers}")]

    def kill(self) -> None:
        """Whatever is still running goes, and is waited for."""
        for proc in self.procs:
            if proc.poll() is None:
                proc.kill()
            proc.wait()


def server_threads(log) -> tuple:
    """A check: the served process has no caller thread."""
    names = sorted(t.name for t in threading.enumerate())
    log(f"threads of the served process at the close: {names}")
    mine = [n for n in names if n.startswith("caller-")]
    return (not mine, f"no caller is a thread of the served process "
                      f"({len(names)} threads, {len(mine)} named caller-)")


if __name__ == "__main__":
    sys.exit(main())
