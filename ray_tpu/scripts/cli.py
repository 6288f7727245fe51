"""``rtpu`` CLI: cluster lifecycle, state inspection, job submission.

Parity targets:
  * ``rtpu start/stop/status`` — /root/reference/python/ray/scripts/
    scripts.py (``ray start --head``, ``ray stop``, ``ray status``)
  * ``rtpu list/summary/timeline`` — the state CLI
    (python/ray/util/state/state_cli.py)
  * ``rtpu job submit/status/stop/logs/list`` —
    dashboard/modules/job/cli.py

Cluster files (address, pids) live under ``--temp-dir`` (default
``/tmp/rtpu``), so ``stop``/``status`` find the cluster without flags,
like the reference's ``/tmp/ray`` session files.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import time

DEFAULT_TEMP_DIR = "/tmp/rtpu"


def _temp_dir(args) -> str:
    d = getattr(args, "temp_dir", None) or DEFAULT_TEMP_DIR
    os.makedirs(d, exist_ok=True)
    return d


def _address_file(args) -> str:
    return os.path.join(_temp_dir(args), "head_address")


def _token_file(args) -> str:
    return os.path.join(_temp_dir(args), "session_token")


def _load_token(args):
    """Session token for attaching to a local cluster: env wins, else the
    head's token file (0600) under the temp dir."""
    if os.environ.get("RT_SESSION_TOKEN"):
        return
    try:
        with open(_token_file(args)) as f:
            tok = f.read().strip()
        if tok:
            os.environ["RT_SESSION_TOKEN"] = tok
            from ray_tpu._private import rpc

            rpc.set_session_token(tok)
    except FileNotFoundError:
        pass


def _pids_file(args) -> str:
    return os.path.join(_temp_dir(args), "pids")


def _record_pid(args, pid: int):
    with open(_pids_file(args), "a") as f:
        f.write(f"{pid}\n")


def _resolve_address(args) -> str:
    addr = getattr(args, "address", None) or os.environ.get("RT_ADDRESS")
    if addr:
        return addr
    try:
        with open(_address_file(args)) as f:
            return f.read().strip()
    except FileNotFoundError:
        sys.exit("error: no cluster address (pass --address, set "
                 "RT_ADDRESS, or `rtpu start --head` first)")


def _attach(args):
    import jax

    jax.config.update("jax_platforms", "cpu")
    import ray_tpu

    if not ray_tpu.is_initialized():
        _load_token(args)
        ray_tpu.init(address=_resolve_address(args))
    return ray_tpu


# ---------------------------------------------------------------------------
# rtpu start / stop / status
# ---------------------------------------------------------------------------
def cmd_start(args):
    if args.head:
        return _start_head(args)
    return _start_worker_node(args)


def _start_head(args):
    """Bring up a DETACHED control plane: the head is its own minimal
    process (head_main: no node service, no driver, no jax) plus a node
    daemon contributing this machine's resources. Driver death can no
    longer take the cluster down, and the head can be killed/restarted
    on the same port + persist path with nodes resyncing (reference:
    `ray start --head` starting gcs_server as a separate process,
    services.py:1421)."""
    addr_file = _address_file(args)
    try:
        os.unlink(addr_file)
    except FileNotFoundError:
        pass
    env = dict(os.environ)
    env["RT_HEAD_PORT"] = str(args.port)
    env.setdefault(
        "RT_HEAD_PERSIST", os.path.join(_temp_dir(args), "head_state.bin"))
    env["RT_ADDR_FILE"] = addr_file
    env["RT_TOKEN_FILE"] = _token_file(args)
    env.setdefault("RT_SESSION_ID", f"cli-{os.getpid():x}")
    log = open(os.path.join(_temp_dir(args), "head.log"), "ab")
    head_proc = subprocess.Popen(
        [sys.executable, "-m", "ray_tpu._private.head_main"],
        env=env, stdout=log, stderr=log, start_new_session=True)
    _record_pid(args, head_proc.pid)  # first pid == the head

    addr = None
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        if os.path.exists(addr_file):
            with open(addr_file) as f:
                addr = f.read().strip()
            if addr:
                break
        if head_proc.poll() is not None:
            sys.exit(f"head process exited rc={head_proc.returncode}; "
                     f"see {log.name}")
        time.sleep(0.1)
    if not addr:
        sys.exit("timed out waiting for the head to come up")

    # The local node daemon (this machine's capacity), attached like any
    # worker node. Session token comes from the head's token file.
    with open(_token_file(args)) as f:
        env["RT_SESSION_TOKEN"] = f.read().strip()
    env["RT_NODE_IS_HEAD"] = "1"
    node_args = argparse.Namespace(**vars(args))
    node_args.address = addr
    _start_worker_node(node_args, env=env)

    # rtpu:// client proxy (reference: the Ray Client server on 10001).
    cenv = dict(env)
    cenv["RT_ADDRESS"] = addr
    cenv["RT_CLIENT_PORT"] = str(getattr(args, "client_port", 0) or 0)
    cenv["RT_CLIENT_ADDR_FILE"] = os.path.join(_temp_dir(args),
                                               "client_address")
    clog = open(os.path.join(_temp_dir(args), "client_server.log"), "ab")
    cproc = subprocess.Popen(
        [sys.executable, "-m", "ray_tpu._private.client_server"],
        env=cenv, stdout=clog, stderr=clog, start_new_session=True)
    _record_pid(args, cproc.pid)

    print(f"head started at {addr} (pid {head_proc.pid})")
    print(f"attach with: ray_tpu.init(address=\"{addr}\") or "
          f"RT_ADDRESS={addr}")
    if args.block:
        # Foreground semantics: Ctrl-C / SIGTERM stops the WHOLE cluster
        # (the daemons run in their own sessions and would otherwise
        # survive as orphans — e.g. outliving a container's PID 1).
        def bye(*_):
            cmd_stop(args)
            sys.exit(0)

        signal.signal(signal.SIGTERM, bye)
        signal.signal(signal.SIGINT, bye)
        head_proc.wait()


def _start_worker_node(args, env=None):
    if env is None:
        _load_token(args)
        env = dict(os.environ)
    addr = _resolve_address(args)
    resources = json.loads(args.resources) if args.resources else {}
    resources.setdefault("CPU", args.num_cpus)
    if args.num_tpus is not None:
        resources.setdefault("TPU", args.num_tpus)
    # No chip counting here: the node daemon hosts the device lane, so it
    # is the one process of a detached cluster that touches jax. It
    # counts its own chips when RT_NODE_RESOURCES names no TPU
    # (node_main.py) and runs on the platform its environment selects.
    env = dict(env)
    env["RT_HEAD_ADDR"] = addr
    env["RT_SESSION_ID"] = env.get("RT_SESSION_ID", "cli")
    env["RT_NODE_RESOURCES"] = json.dumps(resources)
    log = open(os.path.join(_temp_dir(args), "node.log"), "ab")
    proc = subprocess.Popen(
        [sys.executable, "-m", "ray_tpu._private.node_main"],
        env=env, stdout=log, stderr=log, start_new_session=True)
    _record_pid(args, proc.pid)
    print(f"worker node started (pid {proc.pid}) -> head {addr}")


def cmd_head_replica(args):
    os.environ["RT_REPLICA_PORT"] = str(args.port)
    os.environ["RT_REPLICA_DIR"] = args.dir
    from ray_tpu._private.head_replica_main import main as replica_main

    return replica_main()


def cmd_stop(args):
    try:
        with open(_pids_file(args)) as f:
            pids = [int(line) for line in f if line.strip()]
    except FileNotFoundError:
        print("nothing to stop")
        return
    stopped = 0
    for pid in pids:
        try:
            os.killpg(pid, signal.SIGTERM)
            stopped += 1
        except (ProcessLookupError, PermissionError):
            pass
    # Give the head time to run its full shutdown (worker joins, shm
    # teardown) before escalating; SIGKILL only what remains.
    deadline = time.monotonic() + 15.0

    def _alive(pid):
        try:
            os.kill(pid, 0)
            return True
        except ProcessLookupError:
            return False
        except PermissionError:
            return True

    while time.monotonic() < deadline and any(_alive(p) for p in pids):
        time.sleep(0.2)
    for pid in pids:
        if _alive(pid):
            try:
                os.killpg(pid, signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass
    os.unlink(_pids_file(args))
    try:
        os.unlink(_address_file(args))
    except FileNotFoundError:
        pass
    print(f"stopped {stopped} process group(s)")


def _telemetry_latest(rt) -> dict:
    """{metric: {node_hex: latest_value}} from the head time-series.

    Goes through the state facade (not ``rt``): ``_attach`` hands the
    commands the ray_tpu module, which has no ``timeseries`` attribute.
    """
    from ray_tpu.util import state

    out = {}
    try:
        ts = state.timeseries()
    except Exception:  # noqa: BLE001 - old head / telemetry disabled
        return out
    for metric, by_node in ts.get("series", {}).items():
        for node, points in by_node.items():
            if points:
                out.setdefault(metric, {})[node] = points[-1][1]
    return out


def _alerts_banner():
    """One-line firing-alerts banner shared by status/top. Best-effort:
    an old head without the alerts RPC prints nothing."""
    try:
        from ray_tpu.util import state

        firing = [a for a in state.list_alerts()
                  if a.get("state") == "firing"]
    except Exception:  # noqa: BLE001 - old head / alerts unavailable
        return
    if firing:
        names = ", ".join(f"{a['name']}[{a['severity']}]"
                          for a in firing[:4])
        more = f" +{len(firing) - 4} more" if len(firing) > 4 else ""
        print(f"!! ALERTS FIRING: {names}{more}  (rtpu alerts)")


def _print_status(rt):
    from ray_tpu.util import state

    _alerts_banner()
    # Attached drivers (this CLI process included) aren't cluster capacity.
    nodes = state.list_nodes(filters=[("is_driver", "=", False)])
    latest = _telemetry_latest(rt)

    def tele(metric, node_hex, fmt="{:g}"):
        v = latest.get(metric, {}).get(node_hex)
        return "-" if v is None else fmt.format(v)

    print(f"{len(nodes)} node(s):")
    for n in nodes:
        role = "head" if n["is_head_node"] else "worker"
        nid = n["node_id"]
        print(f"  {nid[:12]}  {role:6s}  {n['state']:5s}  "
              f"{n['address'][0]}:{n['address'][1]}  "
              f"avail={_fmt_resources(n['available'])}  "
              f"tasks/s={tele('tasks_per_s', nid)} "
              f"q={tele('dispatch_queue_depth', nid)} "
              f"occ={tele('pipeline_occupancy', nid, '{:.0%}')}")
    total = rt.cluster_resources()
    avail = rt.available_resources()
    print(f"resources: total={_fmt_resources(total)} "
          f"available={_fmt_resources(avail)}")


def cmd_status(args):
    rt = _attach(args)
    if not getattr(args, "watch", False):
        _print_status(rt)
        return
    try:
        while True:
            sys.stdout.write("\x1b[2J\x1b[H")  # clear + home
            print(time.strftime("%H:%M:%S"), "(^C to exit)")
            _print_status(rt)
            sys.stdout.flush()
            time.sleep(args.interval)
    except KeyboardInterrupt:
        pass


_TOP_COLUMNS = (
    # (header, metric, format)
    ("tasks/s", "tasks_per_s", "{:.1f}"),
    ("submit/s", "tasks_submitted_per_s", "{:.1f}"),
    ("pull MB/s", "object_bytes_pulled_per_s", None),  # scaled below
    ("queue", "dispatch_queue_depth", "{:.0f}"),
    ("q-hw", "dispatch_queue_hw", "{:.0f}"),
    ("inflight", "pipeline_inflight", "{:.0f}"),
    ("occ", "pipeline_occupancy", "{:.0%}"),
    ("store MB", "store_used_bytes", None),
    ("spill MB", "store_spilled_bytes", None),
    ("restore MB", "store_restored_bytes", None),
    ("frames/fl", "writer_frames_per_flush", "{:.1f}"),
)


def _print_top(rt):
    from ray_tpu.util import state

    _alerts_banner()
    nodes = state.list_nodes(filters=[("is_driver", "=", False)])
    latest = _telemetry_latest(rt)
    hdr = "node          " + "".join(f"{h:>11}" for h, _, _ in _TOP_COLUMNS)
    print(hdr)
    for n in nodes:
        nid = n["node_id"]
        cells = []
        for _, metric, fmt in _TOP_COLUMNS:
            v = latest.get(metric, {}).get(nid)
            if v is None:
                cells.append(f"{'-':>11}")
            elif fmt is None:  # bytes -> MB
                cells.append(f"{v / 1e6:>11.1f}")
            else:
                cells.append(f"{fmt.format(v):>11}")
        print(f"{nid[:12]}  " + "".join(cells))
    serve_rows = sorted((m, by_node) for m, by_node in latest.items()
                        if m.startswith(("serve_p95_ms:",
                                         "serve_queue_depth:")))
    if serve_rows:
        print("serve:")
        for metric, by_node in serve_rows:
            val = sum(by_node.values())
            print(f"  {metric:<44} {val:10.2f}")
    # Device-step performance plane: where did my step go, live.
    perf_rows = sorted((m, by_node) for m, by_node in latest.items()
                       if m.startswith(("llm_mfu:", "llm_host_gap_ms:",
                                        "kv_cache_hit_rate:",
                                        "kv_shared_blocks:",
                                        "llm_spec_accept_rate:",
                                        "llm_spec_tokens_per_step:",
                                        "train_mfu:",
                                        "train_host_gap_ms:")))
    if perf_rows:
        print("perf:")
        for metric, by_node in perf_rows:
            val = max(by_node.values())
            if metric.startswith(("llm_mfu:", "train_mfu:",
                                  "kv_cache_hit_rate:",
                                  "llm_spec_accept_rate:")):
                print(f"  {metric:<44} {val:10.2%}")
            else:
                print(f"  {metric:<44} {val:10.2f}")
    # Gang flight-recorder plane: per-group collective latency and
    # straggler skew (a growing skew = one member stopped entering).
    coll_rows = sorted((m, by_node) for m, by_node in latest.items()
                       if m.startswith(("collective_latency_ms:",
                                        "collective_skew_ms:",
                                        "collective_last_seq:")))
    if coll_rows:
        print("collectives:")
        for metric, by_node in coll_rows:
            print(f"  {metric:<44} {max(by_node.values()):10.2f}")


def cmd_top(args):
    rt = _attach(args)
    if args.once:
        _print_top(rt)
        return
    try:
        while True:
            sys.stdout.write("\x1b[2J\x1b[H")
            print(time.strftime("%H:%M:%S"),
                  "cluster telemetry (^C to exit)")
            _print_top(rt)
            sys.stdout.flush()
            time.sleep(args.interval)
    except KeyboardInterrupt:
        pass


def _fmt_resources(res: dict) -> str:
    return "{" + ", ".join(
        f"{k}: {v:g}" for k, v in sorted(res.items()) if v) + "}"


# ---------------------------------------------------------------------------
# rtpu list / summary / timeline
# ---------------------------------------------------------------------------
def cmd_list(args):
    _attach(args)
    from ray_tpu.util import state

    fn = {"tasks": state.list_tasks, "actors": state.list_actors,
          "objects": state.list_objects, "workers": state.list_workers,
          "nodes": state.list_nodes,
          "placement-groups": state.list_placement_groups}[args.kind]
    filters = []
    for f in args.filter or []:
        if "!=" in f:
            k, v = f.split("!=", 1)
            filters.append((k.strip(), "!=", _coerce(v.strip())))
        elif "=" in f:
            k, v = f.split("=", 1)
            filters.append((k.strip(), "=", _coerce(v.strip())))
        else:
            sys.exit(f"bad --filter {f!r} (want key=value or key!=value)")
    if args.kind == "nodes" and not any(k == "is_driver"
                                        for k, _, _ in filters):
        # This CLI process attaches as a driver — hide it (and any other
        # attached drivers) unless explicitly asked for.
        filters.append(("is_driver", "=", False))
    rows = fn(filters=filters or None, limit=args.limit)
    print(json.dumps(rows, indent=2, default=str))


def _coerce(v: str):
    if v in ("true", "True"):
        return True
    if v in ("false", "False"):
        return False
    try:
        return int(v)
    except ValueError:
        return v


def cmd_summary(args):
    _attach(args)
    from ray_tpu.util import state

    print(json.dumps(state.summarize_tasks(), indent=2))


def cmd_timeline(args):
    _attach(args)
    import ray_tpu

    events = ray_tpu.timeline(args.output)
    print(f"wrote {len(events)} events to {args.output}")


def cmd_metrics(args):
    _attach(args)
    from ray_tpu.util import prometheus_text

    sys.stdout.write(prometheus_text())


def cmd_dashboard(args):
    _attach(args)
    from ray_tpu.dashboard import start_dashboard

    host, port = start_dashboard(port=args.port)
    print(f"dashboard at http://{host}:{port}/ (ctrl-c to stop)")
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        pass


def cmd_serve_deploy(args):
    _attach(args)
    # The rtpu entry point doesn't put the working directory on
    # sys.path; import_path app modules live next to the config (the
    # config's directory takes precedence over cwd).
    for p in (os.getcwd(), os.path.dirname(os.path.abspath(args.config))):
        if p not in sys.path:
            sys.path.insert(0, p)
    from ray_tpu.serve.config import deploy_config_file

    names = deploy_config_file(args.config)
    print(f"deployed: {', '.join(names)}")


def cmd_serve_status(args):
    _attach(args)
    from ray_tpu import serve

    try:
        st = serve.status()
    except RuntimeError:
        print("serve is not running")
        return
    for name, info in st.items():
        print(f"deployment {name}: replicas "
              f"{info.get('num_replicas')}/{info.get('target_replicas')}")


def cmd_serve_shutdown(args):
    _attach(args)
    from ray_tpu import serve

    serve.shutdown()
    print("serve shut down")


def cmd_trace_list(args):
    _attach(args)
    from ray_tpu.util import state

    rows = state.list_traces(deployment=args.deployment,
                             min_ms=args.min_ms,
                             errors_only=args.errors_only,
                             limit=args.limit)
    if not rows:
        print("no retained traces (the head keeps errors, the slowest "
              "p% per deployment, and a sampled rest — send traffic "
              "first, then wait one heartbeat)")
        return
    print(f"{'TRACE':<33} {'DEPLOYMENT':<16} {'MS':>9} {'SPANS':>5} "
          f"{'REASON':<7} ERR")
    for r in rows:
        print(f"{r['trace_id']:<33} {str(r['deployment'])[:16]:<16} "
              f"{r['duration_ms']:>9.1f} {r['spans']:>5} "
              f"{r['reason']:<7} {'x' if r['error'] else ''}")


def cmd_trace_show(args):
    _attach(args)
    from ray_tpu.util import state, tracing

    spans = state.get_trace(args.id)
    if not spans:
        print(f"trace {args.id} not retained (tail sampler dropped it, "
              f"or it never completed)")
        return
    sys.stdout.write(tracing.render_waterfall(spans))
    if args.output:
        tracing.export_chrome_trace(args.output, trace_id=args.id)
        print(f"chrome trace written to {args.output}")


def _tail_lines(fetch, n: int, max_bytes: int = 1 << 24) -> dict:
    """Byte-tail fetches sized to GUARANTEE n lines per source: start
    with a generous estimate and refetch with a larger window until
    every source either has >= n lines or stopped growing (file shorter
    than the window). Replaces the old fixed n*100-byte guess, which
    silently under-read logs with long lines."""
    tail_bytes = max(4096, 256 * n)
    logs = fetch(tail_bytes)
    while tail_bytes < max_bytes:
        short = [name for name, text in logs.items()
                 if isinstance(text, str) and text.count("\n") < n
                 and len(text) >= tail_bytes]
        if not short:
            break
        tail_bytes = min(tail_bytes * 4, max_bytes)
        logs = fetch(tail_bytes)
    return logs


def cmd_logs(args):
    _attach(args)
    from ray_tpu._private import context as context_mod

    rt = context_mod.require_context()
    logs = _tail_lines(lambda tb: rt.cluster_logs(tail_bytes=tb),
                       args.tail)
    for name, text in sorted(logs.items()):
        lines = text.splitlines()[-args.tail:]
        print(f"===== {name} =====")
        for line in lines:
            print(line)
        print()
    if not logs:
        print("no worker logs captured yet")


def cmd_stack(args):
    _attach(args)
    from ray_tpu._private import context as context_mod

    rt = context_mod.require_context()
    if getattr(args, "flame", False):
        # Sampling profiler -> flamegraph (reference: `ray stack` is a
        # py-spy dump; the dashboard's profile_manager adds --flame).
        from ray_tpu._private.profiler import (merge_folded,
                                               render_flamegraph_svg)

        profs = rt.cluster_profile(duration_s=args.duration)
        folded = merge_folded([p.get("folded", "") for p in profs.values()
                               if isinstance(p, dict)])
        if not folded:
            sys.exit("no samples collected (cluster idle or unreachable)")
        out = args.out or "rtpu-flame.svg"
        with open(out, "w") as f:
            f.write(render_flamegraph_svg(
                folded, title=f"rtpu cluster profile "
                              f"({args.duration:.0f}s @ 99Hz)"))
        root, _ext = os.path.splitext(out)
        folded_path = root + ".folded"
        with open(folded_path, "w") as f:
            f.write(folded)
        print(f"wrote {out} (+ {folded_path} for external tooling)")
        return
    for name, text in sorted(rt.cluster_stacks().items()):
        print(f"===== {name} =====")
        print(text)
        print()


def cmd_profile(args):
    """Cluster-wide capture. Default: host CPU sampling profile ->
    flamegraph SVG (same engine as `rtpu stack --flame`). With
    --device: gang-coordinated device-step capture — every node+worker
    records accounted engine/train steps (device-vs-host split, MFU,
    roofline verdict), a host-CPU sample timeline, and a best-effort
    jax.profiler trace for one shared window; the driver aligns each
    host's clock by RTT midpoint and merges everything, plus the
    window's request spans, into ONE chrome://tracing / Perfetto
    JSON."""
    _attach(args)
    from ray_tpu._private import context as context_mod

    rt = context_mod.require_context()
    if not getattr(args, "device", False):
        from ray_tpu._private.profiler import (merge_folded,
                                               render_flamegraph_svg)

        profs = rt.cluster_profile(duration_s=args.duration, hz=args.hz)
        folded = merge_folded([p.get("folded", "") for p in profs.values()
                               if isinstance(p, dict)])
        if not folded:
            sys.exit("no samples collected (cluster idle or unreachable)")
        out = args.out or "rtpu-profile.svg"
        with open(out, "w") as f:
            f.write(render_flamegraph_svg(
                folded, title=f"rtpu cluster profile "
                              f"({args.duration:.0f}s @ {args.hz:.0f}Hz)"))
        print(f"wrote {out}")
        return

    import json

    from ray_tpu._private.profiler import (build_merged_trace,
                                           format_device_steps,
                                           format_idle_gaps,
                                           format_thread_cpu)
    from ray_tpu.util import state

    t0 = time.time()
    print(f"capturing {args.duration:.0f}s device window across the "
          f"cluster...")
    profs = rt.cluster_device_profile(duration_s=args.duration, hz=args.hz)
    offsets = rt.clock_offsets()
    # Request spans that overlap the window ride along on their own
    # track, so a slow decode step lines up with the request above it.
    spans = []
    try:
        for tr in state.list_traces(limit=50):
            if tr.get("start", 0.0) + tr.get("duration_ms", 0.0) / 1e3 \
                    < t0 - 1.0:
                continue
            spans.extend(state.get_trace(tr["trace_id"]) or [])
    except Exception:  # noqa: BLE001 - tracing disabled is fine
        pass
    merged = build_merged_trace(profs, offsets, spans)
    captured = [k for k, v in profs.items()
                if isinstance(v, dict) and "t0_wall" in v]
    out = args.out or "rtpu-device-trace.json"
    with open(out, "w") as f:
        json.dump(merged, f)
    n_steps = sum(len(v.get("device_steps", [])) for v in profs.values()
                  if isinstance(v, dict))
    print(f"wrote {out}: {len(merged['traceEvents'])} events from "
          f"{len(captured)} process(es), {n_steps} accounted device "
          f"step(s), {len(spans)} request span(s)")
    print("open in chrome://tracing or https://ui.perfetto.dev")
    # Where the steps went and why the chip waited: each process that
    # ran steps splits them by span (util/perfmodel.PHASES), and each
    # that traced a device names the host span over its idle gaps;
    # every process says which of its threads had the cores.
    for source in captured:
        steps = profs[source].get("device_steps")
        gaps = profs[source].get("idle_gaps")
        threads = profs[source].get("threads")
        if not steps and not threads and not (gaps and gaps["idle_s"] > 0):
            continue
        print(f"{source}:")
        if threads:
            print(format_thread_cpu(*threads))
        if steps:
            print(format_device_steps(steps))
        if gaps and gaps["idle_s"] > 0:
            print(format_idle_gaps(gaps))


def cmd_heap(args):
    """Per-process tracemalloc top allocation sites (reference: memray
    heap profiles via the dashboard agent)."""
    _attach(args)
    from ray_tpu._private import context as context_mod

    rt = context_mod.require_context()
    for name, snap in sorted(rt.cluster_heap(top_n=args.top).items()):
        print(f"===== {name} =====")
        if not isinstance(snap, dict):
            print(snap)
            continue
        if snap.get("note"):
            print(snap["note"])
        if "current_kb" in snap:
            print(f"traced: current={snap['current_kb']:.0f}KB "
                  f"peak={snap['peak_kb']:.0f}KB")
        for row in snap.get("top", []):
            print(f"  {row['size_kb']:>10.1f} KB x{row['count']:<6} "
                  f"{row['trace']}")
        print()


def cmd_memory(args):
    rt = _attach(args)
    from collections import defaultdict

    from ray_tpu.util import state

    rows = state.list_objects()
    group_by = getattr(args, "group_by", "node")
    sort_by = getattr(args, "sort", "size")

    def group_key(r):
        if group_by == "owner":
            return r.get("owner") or "?"
        return r["node_id"][:12]

    groups = defaultdict(lambda: [0, 0])
    for r in rows:
        g = groups[group_key(r)]
        g[0] += 1
        g[1] += r.get("size") or 0
    print(f"{len(rows)} object(s) cluster-wide")
    # sort groups: size -> by bytes desc, count -> by count desc
    order = sorted(groups.items(),
                   key=lambda kv: kv[1][1 if sort_by == "size" else 0],
                   reverse=True)
    label = "owner" if group_by == "owner" else "node"
    for key, (count, nbytes) in order:
        print(f"  {label} {key}: {count} objects, {nbytes / 1e6:.2f} MB")
    top = sorted(rows, key=lambda r: r.get("size") or 0, reverse=True)[:20]
    if top:
        print("top objects by size:")
        for r in top:
            print(f"  {r['object_id'][:16]}  {r.get('size') or 0:>12}  "
                  f"{r['status']:<8} refs={r.get('refcount', '?')}  "
                  f"owner={r.get('owner', '?')}")
    # Spill plane: per-node store spill/restore counters off the
    # timeseries sampler (0s mean idle-decayed, not never-spilled).
    try:
        latest = _telemetry_latest(rt)
    except Exception:  # noqa: BLE001 - no head telemetry: skip the section
        latest = {}
    ev = latest.get("store_spill_events", {})
    sb = latest.get("store_spilled_bytes", {})
    rb = latest.get("store_restored_bytes", {})
    nids = sorted(set(ev) | set(sb) | set(rb))
    if nids:
        print("spill plane (idle series decay to 0):")
        for nid in nids:
            print(f"  node {nid[:12]}: events={ev.get(nid, 0):.0f} "
                  f"spilled={sb.get(nid, 0) / 1e6:.2f} MB "
                  f"restored={rb.get(nid, 0) / 1e6:.2f} MB")


# ---------------------------------------------------------------------------
# rtpu job ...
# ---------------------------------------------------------------------------
def _job_client(args):
    _attach(args)
    from ray_tpu.job_submission import JobSubmissionClient

    return JobSubmissionClient()


def cmd_job_submit(args):
    client = _job_client(args)
    runtime_env = {}
    if args.working_dir:
        runtime_env["working_dir"] = args.working_dir
    for kv in args.env or []:
        k, _, v = kv.partition("=")
        runtime_env.setdefault("env_vars", {})[k] = v
    import shlex

    resources = json.loads(args.resources) if args.resources else None
    sid = client.submit_job(
        entrypoint=shlex.join(args.entrypoint),
        submission_id=args.submission_id, runtime_env=runtime_env,
        tenant=args.tenant, weight=args.weight, resources=resources)
    info = client.get_job_info(sid)
    if info["status"] == "REJECTED":
        reason = info.get("reason") or {}
        print(f"job {sid} REJECTED: {reason.get('code', '?')} — "
              f"{reason.get('detail', info.get('message', ''))}")
        sys.exit(1)
    print(f"submitted job {sid}")
    if args.wait:
        status = client.wait_until_finish(sid, timeout=args.timeout)
        print(f"job {sid}: {status}")
        print(client.get_job_logs(sid), end="")
        sys.exit(0 if status == "SUCCEEDED" else 1)


def cmd_job_list(args):
    client = _job_client(args)
    for j in client.list_jobs():
        print(f"{j['submission_id']}  {j['status']:10s}  "
              f"{j['entrypoint'][:60]}")


def cmd_job_status(args):
    print(_job_client(args).get_job_status(args.id))


def cmd_job_stop(args):
    ok = _job_client(args).stop_job(args.id)
    print("stopped" if ok else "not running")


def cmd_job_logs(args):
    print(_job_client(args).get_job_logs(args.id), end="")


def cmd_jobs(args):
    """Multi-tenant job-plane view: per-tenant fair-share standings
    (weight, cluster share, queue depth, quota) plus the tail of the
    scheduler's decision ledger."""
    client = _job_client(args)
    stats = client.tenant_stats()
    if args.quota:
        resources = json.loads(args.resources) if args.resources else None
        q = client.set_tenant_quota(
            args.quota, max_running_jobs=args.max_running,
            max_pending_jobs=args.max_pending, resources=resources)
        print(f"quota[{args.quota}] = {q}")
        return
    if not stats:
        print("no tenants (no jobs submitted yet)")
    else:
        hdr = (f"{'TENANT':16s} {'WEIGHT':>6s} {'SHARE':>6s} "
               f"{'QUEUED':>6s} {'RUNNING':>7s} {'SERVED':>8s}  QUOTA")
        print(hdr)
        for tenant in sorted(stats):
            row = stats[tenant]
            quota = {k: v for k, v in (row.get("quota") or {}).items()
                     if v is not None}
            share = row.get("share")
            print(f"{tenant:16s} {row['weight']:6.1f} "
                  f"{(f'{share:.0%}' if share is not None else '-'):>6s} "
                  f"{row['queued']:6d} {row['running']:7d} "
                  f"{row['served_cost']:8.3f}  "
                  f"{quota if quota else '-'}")
    if args.events:
        print()
        for ev in client.list_job_events(args.events):
            extra = {k: v for k, v in ev.items()
                     if k not in ("ts", "kind", "job_id", "tenant")}
            print(f"{ev['ts']:.2f}  {ev['kind']:10s} "
                  f"{ev['job_id']:24s} {ev['tenant']:12s} "
                  f"{extra if extra else ''}")


def _print_verdict(verdict: dict, json_mode: bool = False):
    if json_mode:
        print(json.dumps(verdict, indent=2, default=str))
        return
    ts = verdict.get("ts")
    when = (time.strftime("%Y-%m-%d %H:%M:%S", time.localtime(ts))
            if ts else "?")
    print(f"gang: {verdict.get('gang') or '?'}   diagnosed: {when}")
    print(verdict.get("summary", ""))
    for lag in verdict.get("lagging", []):
        rank = lag.get("rank")
        who = f"rank {rank}" if rank is not None else "rank ?"
        print(f"\n  {who}  {lag['source']}  group={lag['group']}  "
              f"last completed seq {lag['last_seq']}/{lag['max_seq']} "
              f"(behind by {lag['gap']})")
        nxt = lag.get("next_op")
        if nxt:
            shape = f" shape={nxt['shape']}" if nxt.get("shape") else ""
            print(f"    never entered: {nxt['op']} seq={nxt['seq']} "
                  f"axis={nxt.get('axis')}{shape}")
        for e in lag.get("in_flight", []):
            print(f"    in flight: {e['op']} seq={e['seq']} "
                  f"(entered, never exited)")
        stack = lag.get("stack")
        if stack:
            print("    host stacks:")
            for line in str(stack).splitlines():
                print(f"      {line}")
    errs = verdict.get("errors") or {}
    for src, err in sorted(errs.items()):
        print(f"  (no snapshot from {src}: {err})")


def cmd_gang_doctor(args):
    """Render a gang desync verdict: the recorded one from the runtime
    KV (written by the trainer's stale-heartbeat watchdog), or — with
    --live — collect + align flight-recorder rings right now."""
    _attach(args)
    from ray_tpu.util import state

    if args.live:
        from ray_tpu._private import context as context_mod
        from ray_tpu.parallel import flightrec

        rt = context_mod.require_context()
        records = rt.cluster_flight_records()
        verdict = flightrec.diagnose(records, gang=args.name)
    elif args.name:
        verdict = state.get_gang_verdict(args.name)
        if verdict is None:
            print(f"no desync verdict recorded for gang {args.name!r} "
                  f"(use --live to diagnose the cluster now)")
            return
    else:
        verdicts = state.list_gang_verdicts()
        if not verdicts:
            print("no desync verdicts recorded (no gang watchdog has "
                  "fired; use --live to diagnose the cluster now)")
            return
        verdict = verdicts[0]
    _print_verdict(verdict, json_mode=args.json)


def cmd_collectives(args):
    """Tail of every process's flight-recorder ring: the raw eager-
    collective timeline `rtpu gang doctor` aligns."""
    _attach(args)
    from ray_tpu._private import context as context_mod

    rt = context_mod.require_context()
    records = rt.cluster_flight_records(tail=args.tail,
                                        include_stacks=False)
    now = time.time()
    shown = 0
    for src, snap in sorted(records.items()):
        if not isinstance(snap, dict) or not snap.get("entries"):
            continue
        ident = snap.get("identity") or {}
        rank = (f" rank={ident['rank']}/{ident.get('world_size', '?')}"
                if "rank" in ident else "")
        print(f"===== {src}{rank} =====")
        wall = snap.get("wall", now)
        for e in snap["entries"][-args.tail:]:
            if e.get("t1") is not None:
                dur = f"{(e['t1'] - e['t0']) * 1e3:9.2f}ms"
                status = "ok" if e.get("ok") else "FAILED"
            else:
                dur = f"{max(0.0, wall - e['w0']):8.1f}s+"
                status = "IN-FLIGHT"
            shape = f" {e['shape']}" if e.get("shape") else ""
            print(f"  {e['group']:<20} seq={e['seq']:<5} "
                  f"{e['op']:<14} axis={str(e.get('axis') or '-'):<6} "
                  f"{dur} {status}{shape}")
        shown += 1
        print()
    if not shown:
        print("no eager collectives recorded anywhere (in-graph "
              "collectives compile into the XLA step and are covered "
              "at step granularity by wrap_step entries)")


# Pinned machine-readable shape of `rtpu alerts --json`: scripts and
# the schema test key on exactly these fields, so head-side additions
# never silently change the contract.
_ALERT_FIELDS = ("name", "metric", "target", "comparison", "severity",
                 "state", "fast_burn_rate", "slow_burn_rate", "since",
                 "source")
_INCIDENT_FIELDS = ("id", "rule", "metric", "severity", "state",
                    "opened", "resolved", "refires", "summary")


def _alerts_payload(alerts: list, incidents: list) -> dict:
    """Build the `rtpu alerts --json` document from head rows. Pure —
    the pinned-schema test calls it with fabricated rows, no cluster."""
    return {
        "version": 1,
        "alerts": [{k: a.get(k) for k in _ALERT_FIELDS}
                   for a in alerts],
        "incidents": [{k: i.get(k) for k in _INCIDENT_FIELDS}
                      for i in incidents],
    }


def cmd_alerts(args):
    """Declared SLO alert rules (with live burn rates) + recent
    incidents."""
    _attach(args)
    from ray_tpu.util import state

    alerts = state.list_alerts()
    incidents = state.list_incidents(limit=args.limit)
    if args.json:
        print(json.dumps(_alerts_payload(alerts, incidents), indent=2,
                         default=str))
        return
    if not alerts:
        print("no SLO alert rules declared (state.declare_slo(...); "
              "built-in rules register once their metric first appears)")
    else:
        print(f"  {'RULE':<26} {'METRIC':<30} {'SEV':<6} {'STATE':<8} "
              f"{'FAST':>7} {'SLOW':>7}")
        for a in alerts:
            mark = "!!" if a["state"] == "firing" else "  "
            print(f"{mark}{a['name'][:26]:<26} {a['metric'][:30]:<30} "
                  f"{a['severity']:<6} {a['state']:<8} "
                  f"{a['fast_burn_rate']:>7.2f} "
                  f"{a['slow_burn_rate']:>7.2f}")
    if incidents:
        print("\nincidents (newest first):")
        for inc in incidents:
            ts = time.strftime("%H:%M:%S",
                               time.localtime(inc["opened"]))
            refires = (f" refires={inc['refires']}"
                       if inc.get("refires") else "")
            print(f"  {inc['id']}  {inc['state']:<9} {ts}  "
                  f"{inc['rule']}{refires}")
        print("  (rtpu incident show <id> for the evidence bundle)")


def cmd_incident_show(args):
    """Render one incident with its evidence bundle: metric window,
    roofline verdicts, gang-doctor verdicts, job-ledger tail, the
    transition timeline, and the exemplar trace's waterfall — the
    on-call's first page."""
    _attach(args)
    from ray_tpu.util import state

    inc = state.get_incident(args.id)
    if inc is None:
        print(f"incident {args.id} not found (the head keeps a bounded "
              f"store of recent incidents; `rtpu alerts` lists them)")
        return
    if args.json:
        print(json.dumps(inc, indent=2, default=str))
        return
    opened = time.strftime("%Y-%m-%d %H:%M:%S",
                           time.localtime(inc["opened"]))
    line = (f"incident {inc['id']}  [{inc['state']}]  "
            f"rule={inc['rule']}  severity={inc['severity']}")
    print(line)
    tail = f"opened {opened}"
    if inc.get("resolved"):
        tail += "  resolved " + time.strftime(
            "%H:%M:%S", time.localtime(inc["resolved"]))
    if inc.get("refires"):
        tail += f"  refires={inc['refires']}"
    print(tail)
    if inc.get("summary"):
        print(inc["summary"])

    ev = inc.get("evidence") or {}
    print(f"\nmetric {ev.get('metric', inc.get('metric'))}: "
          f"latest={ev.get('latest_value')}  "
          f"burn fast={ev.get('fast_burn_rate')} "
          f"slow={ev.get('slow_burn_rate')}")
    for node, pts in sorted((ev.get("window") or {}).items()):
        if pts:
            vals = [p[1] for p in pts]
            print(f"  window[{node[:12]}]: {len(pts)} pts "
                  f"min={min(vals):g} max={max(vals):g} "
                  f"last={vals[-1]:g}")

    roof = ev.get("roofline")
    if roof:
        verdicts = roof.get("verdicts") or []
        mfu = roof.get("mfu")
        print(f"\nroofline (last {len(verdicts)} step(s)): "
              f"{' '.join(verdicts) if verdicts else '-'}"
              + (f"  mfu={mfu:.1%}" if isinstance(mfu, float) else ""))

    for gv in ev.get("gang_verdicts") or []:
        print(f"\ngang verdict [{gv.get('gang', '?')}]: "
              f"{gv.get('summary', '')}")

    ledger = ev.get("job_ledger") or []
    if ledger:
        print("\njob ledger tail:")
        for e in ledger[-10:]:
            print(f"  {e.get('ts', 0):.2f}  {e.get('kind', '?'):12s} "
                  f"{e.get('job_id', '')}  {e.get('tenant', '')}")

    events = inc.get("events") or []
    if events:
        print("\ntimeline:")
        for e in events:
            ts = time.strftime("%H:%M:%S",
                               time.localtime(e.get("ts", 0)))
            extra = {k: v for k, v in e.items()
                     if k not in ("ts", "kind")}
            print(f"  {ts}  {e.get('kind', '?'):8s} "
                  f"{extra if extra else ''}")

    ex = ev.get("exemplar")
    if ex and ex.get("trace_id"):
        print(f"\nexemplar trace {ex['trace_id']} "
              f"({ex.get('duration_ms', 0):.1f}ms"
              + (", error" if ex.get("error") else "") + "):")
        try:
            from ray_tpu.util import tracing

            spans = state.get_trace(ex["trace_id"])
            if spans:
                sys.stdout.write(tracing.render_waterfall(spans))
            else:
                print("  (trace no longer retained)")
        except Exception:  # noqa: BLE001 - waterfall render is best-effort
            print("  (waterfall unavailable)")


def cmd_lint(args):
    """Static analysis over the runtime's own source. Needs no cluster."""
    from pathlib import Path

    from ray_tpu import analysis

    root = Path.cwd()
    if not (root / "ray_tpu").is_dir():
        # Running from outside a checkout: lint the installed package.
        import ray_tpu as _pkg

        root = Path(_pkg.__file__).resolve().parent.parent
    baseline_path = Path(args.baseline) if args.baseline else None
    if args.write_baseline:
        report = analysis.run_lint(root, paths=args.paths or None,
                                   select=args.select, use_baseline=False)
        from ray_tpu.analysis import baseline as baseline_mod

        if isinstance(args.write_baseline, str):
            path = Path(args.write_baseline)
        else:
            path = baseline_path or analysis.default_baseline_path(root)
        entries = baseline_mod.save(path, report.findings)
        print(f"wrote {path}: {len(entries)} entries covering "
              f"{len(report.findings)} findings")
        todo = sum(1 for v in entries.values()
                   if v["reason"].startswith("TODO"))
        if todo:
            print(f"{todo} entries need a reviewer reason "
                  f"(grep 'TODO review')")
        return
    report = analysis.run_lint(root, paths=args.paths or None,
                               select=args.select,
                               baseline_path=baseline_path,
                               use_baseline=not args.no_baseline,
                               changed_only=args.changed_only)
    if args.format == "json":
        print(analysis.format_json(report))
    else:
        print(analysis.format_text(report), end="")
    if report.findings or report.stale_baseline:
        sys.exit(1)


# ---------------------------------------------------------------------------
def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="rtpu", description="ray_tpu cluster CLI")
    p.add_argument("--temp-dir", default=None,
                   help=f"cluster files dir (default {DEFAULT_TEMP_DIR})")
    sub = p.add_subparsers(dest="cmd", required=True)

    sp = sub.add_parser("start", help="start a head or worker node")
    sp.add_argument("--head", action="store_true")
    sp.add_argument("--address", default=None,
                    help="head address (worker nodes)")
    sp.add_argument("--port", type=int, default=0, help="head port")
    sp.add_argument("--num-cpus", type=int, default=os.cpu_count() or 1)
    sp.add_argument("--num-tpus", type=int, default=None)
    sp.add_argument("--resources", default=None, help="JSON dict")
    sp.add_argument("--client-port", type=int, default=0,
                    help="rtpu:// client server port (0 = ephemeral; "
                         "written to <temp>/client_address)")
    sp.add_argument("--block", action="store_true",
                    help="run in the foreground")
    sp.set_defaults(fn=cmd_start)

    sp = sub.add_parser("head-replica",
                        help="run a head-store replica daemon (HA: "
                             "cluster metadata survives head-node loss)")
    sp.add_argument("--port", type=int, default=7380)
    sp.add_argument("--dir", default="./rtpu-head-replica")
    sp.set_defaults(fn=cmd_head_replica)

    sp = sub.add_parser("stop", help="stop everything rtpu started here")
    sp.set_defaults(fn=cmd_stop)

    sp = sub.add_parser("status", help="cluster membership + resources")
    sp.add_argument("--address", default=None)
    sp.add_argument("--watch", action="store_true",
                    help="refresh continuously (live telemetry columns)")
    sp.add_argument("--interval", type=float, default=2.0,
                    help="refresh period seconds (with --watch)")
    sp.set_defaults(fn=cmd_status)

    sp = sub.add_parser(
        "top", help="live per-node telemetry (tasks/s, queues, store)")
    sp.add_argument("--address", default=None)
    sp.add_argument("--interval", type=float, default=2.0)
    sp.add_argument("--once", action="store_true",
                    help="print one snapshot and exit")
    sp.set_defaults(fn=cmd_top)

    sp = sub.add_parser("list", help="list cluster state")
    sp.add_argument("kind", choices=["tasks", "actors", "objects",
                                     "workers", "nodes",
                                     "placement-groups"])
    sp.add_argument("--filter", action="append",
                    help="key=value or key!=value (repeatable)")
    sp.add_argument("--limit", type=int, default=None)
    sp.add_argument("--address", default=None)
    sp.set_defaults(fn=cmd_list)

    sp = sub.add_parser("summary", help="task counts by name/state")
    sp.add_argument("kind", choices=["tasks"])
    sp.add_argument("--address", default=None)
    sp.set_defaults(fn=cmd_summary)

    sp = sub.add_parser("metrics",
                        help="print cluster metrics (Prometheus format)")
    sp.add_argument("--address", default=None)
    sp.set_defaults(fn=cmd_metrics)

    sp = sub.add_parser("dashboard", help="serve the cluster web UI")
    sp.add_argument("--address", default=None)
    sp.add_argument("--port", type=int, default=8265)
    sp.set_defaults(fn=cmd_dashboard)

    sp = sub.add_parser("stack",
                        help="thread stacks of every node/worker process")
    sp.add_argument("--address", default=None)
    sp.add_argument("--flame", action="store_true",
                    help="sample a CPU profile and write a flamegraph SVG")
    sp.add_argument("--duration", type=float, default=5.0,
                    help="sampling window seconds (with --flame)")
    sp.add_argument("--out", default=None,
                    help="flamegraph output path (default rtpu-flame.svg)")
    sp.set_defaults(fn=cmd_stack)

    sp = sub.add_parser(
        "profile",
        help="cluster CPU flamegraph; --device for a merged "
             "device-step + host + request-span trace")
    sp.add_argument("--address", default=None)
    sp.add_argument("--device", action="store_true",
                    help="gang-coordinated device-step capture -> one "
                         "chrome://tracing JSON")
    sp.add_argument("--duration", type=float, default=5.0,
                    help="capture window seconds")
    sp.add_argument("--hz", type=float, default=99.0,
                    help="host sampling rate")
    sp.add_argument("--out", "-o", default=None,
                    help="output path (default rtpu-profile.svg / "
                         "rtpu-device-trace.json)")
    sp.set_defaults(fn=cmd_profile)

    sp = sub.add_parser("heap",
                        help="tracemalloc heap snapshot per process")
    sp.add_argument("--top", type=int, default=25)
    sp.add_argument("--address", default=None)
    sp.set_defaults(fn=cmd_heap)

    svp = sub.add_parser("serve", help="model serving")
    ssub = svp.add_subparsers(dest="serve_cmd", required=True)
    sp = ssub.add_parser("deploy", help="deploy apps from a YAML config")
    sp.add_argument("config")
    sp.add_argument("--address", default=None)
    sp.set_defaults(fn=cmd_serve_deploy)
    sp = ssub.add_parser("status")
    sp.add_argument("--address", default=None)
    sp.set_defaults(fn=cmd_serve_status)
    sp = ssub.add_parser("shutdown")
    sp.add_argument("--address", default=None)
    sp.set_defaults(fn=cmd_serve_shutdown)

    tp = sub.add_parser("trace",
                        help="request traces (serving-lane waterfalls)")
    tsub = tp.add_subparsers(dest="trace_cmd", required=True)
    sp = tsub.add_parser("list", help="retained traces, newest first")
    sp.add_argument("--address", default=None)
    sp.add_argument("--deployment", default=None)
    sp.add_argument("--min-ms", type=float, default=0.0, dest="min_ms")
    sp.add_argument("--errors-only", action="store_true",
                    dest="errors_only")
    sp.add_argument("--limit", type=int, default=50)
    sp.set_defaults(fn=cmd_trace_list)
    sp = tsub.add_parser("show", help="ASCII waterfall of one trace")
    sp.add_argument("id")
    sp.add_argument("--address", default=None)
    sp.add_argument("--output", "-o", default=None,
                    help="also write a chrome://tracing JSON here")
    sp.set_defaults(fn=cmd_trace_show)

    sp = sub.add_parser("logs", help="recent worker logs cluster-wide")
    sp.add_argument("--address", default=None)
    sp.add_argument("--tail", type=int, default=100,
                    help="lines per worker")
    sp.set_defaults(fn=cmd_logs)

    gp = sub.add_parser("gang",
                        help="hung-gang diagnostics (flight recorder)")
    gsub = gp.add_subparsers(dest="gang_cmd", required=True)
    sp = gsub.add_parser(
        "doctor", help="desync verdict: who desynced, at which "
                       "collective, with host stacks")
    sp.add_argument("name", nargs="?", default=None,
                    help="gang/run name (default: newest verdict)")
    sp.add_argument("--address", default=None)
    sp.add_argument("--live", action="store_true",
                    help="collect + align rings now instead of reading "
                         "the recorded verdict")
    sp.add_argument("--json", action="store_true",
                    help="machine-readable verdict")
    sp.set_defaults(fn=cmd_gang_doctor)

    sp = sub.add_parser(
        "collectives",
        help="per-process flight-recorder ring tails (eager collectives)")
    sp.add_argument("--address", default=None)
    sp.add_argument("--tail", type=int, default=20,
                    help="ring entries per process")
    sp.set_defaults(fn=cmd_collectives)

    sp = sub.add_parser(
        "alerts", help="SLO alert rules + recent incidents")
    sp.add_argument("--address", default=None)
    sp.add_argument("--json", action="store_true",
                    help="machine-readable payload (pinned schema)")
    sp.add_argument("--limit", type=int, default=20,
                    help="incidents to list")
    sp.set_defaults(fn=cmd_alerts)

    ip = sub.add_parser("incident", help="incident inspection")
    isub = ip.add_subparsers(dest="incident_cmd", required=True)
    sp = isub.add_parser(
        "show", help="one incident with its attached evidence "
                     "(waterfall, roofline, gang verdicts, ledger)")
    sp.add_argument("id")
    sp.add_argument("--address", default=None)
    sp.add_argument("--json", action="store_true")
    sp.set_defaults(fn=cmd_incident_show)

    sp = sub.add_parser("memory", help="object store usage summary")
    sp.add_argument("--address", default=None)
    sp.add_argument("--group-by", choices=["node", "owner"],
                    default="node", dest="group_by",
                    help="group the summary by node or by the task that "
                         "created each object (driver puts -> driver/put)")
    sp.add_argument("--sort", choices=["size", "count"], default="size",
                    help="order groups by total bytes or object count")
    sp.set_defaults(fn=cmd_memory)

    sp = sub.add_parser("timeline", help="dump chrome://tracing JSON")
    sp.add_argument("--output", "-o", default="timeline.json")
    sp.add_argument("--address", default=None)
    sp.set_defaults(fn=cmd_timeline)

    jp = sub.add_parser("job", help="job submission")
    jsub = jp.add_subparsers(dest="job_cmd", required=True)

    sp = jsub.add_parser("submit")
    sp.add_argument("--address", default=None)
    sp.add_argument("--submission-id", default=None)
    sp.add_argument("--working-dir", default=None)
    sp.add_argument("--tenant", default="default",
                    help="tenant the job is billed to (fair-share + "
                         "quota accounting)")
    sp.add_argument("--weight", type=float, default=1.0,
                    help="tenant fair-share weight (> 0)")
    sp.add_argument("--resources", default=None,
                    help='gang resource shape as JSON, e.g. '
                         '\'{"TPU": 8, "CPU": 16}\'')
    sp.add_argument("--env", action="append", help="KEY=VALUE (repeatable)")
    sp.add_argument("--wait", action="store_true",
                    help="block until the job finishes; exit with its "
                         "status")
    sp.add_argument("--timeout", type=float, default=600)
    sp.add_argument("entrypoint", nargs=argparse.REMAINDER,
                    help="-- command to run")
    sp.set_defaults(fn=cmd_job_submit)

    for name, fn in (("list", cmd_job_list), ("status", cmd_job_status),
                     ("stop", cmd_job_stop), ("logs", cmd_job_logs)):
        sp = jsub.add_parser(name)
        sp.add_argument("--address", default=None)
        if name != "list":
            sp.add_argument("id")
        sp.set_defaults(fn=fn)

    sp = sub.add_parser(
        "jobs", help="multi-tenant job plane: fair-share standings, "
                     "quotas, decision ledger")
    sp.add_argument("--address", default=None)
    sp.add_argument("--events", type=int, default=0, metavar="N",
                    help="also print the last N scheduler decisions")
    sp.add_argument("--quota", default=None, metavar="TENANT",
                    help="set TENANT's quota instead of viewing stats")
    sp.add_argument("--max-running", type=int, default=None)
    sp.add_argument("--max-pending", type=int, default=None)
    sp.add_argument("--resources", default=None,
                    help="aggregate resource cap as JSON "
                         '(e.g. \'{"TPU": 16}\')')
    sp.set_defaults(fn=cmd_jobs)

    sp = sub.add_parser(
        "lint", help="static analysis over the runtime source "
                     "(concurrency/exception/device/invariant checkers)")
    sp.add_argument("paths", nargs="*",
                    help="files or directories (default: ray_tpu/)")
    sp.add_argument("--format", choices=["text", "json"], default="text")
    sp.add_argument("--select", default=None,
                    help="comma-separated checker ids or families "
                         "(e.g. C101,device)")
    sp.add_argument("--baseline", default=None,
                    help="baseline file (default: "
                         "ray_tpu/analysis/baseline.json)")
    sp.add_argument("--no-baseline", action="store_true",
                    help="report baselined findings too")
    sp.add_argument("--write-baseline", nargs="?", const=True,
                    default=None, metavar="PATH",
                    help="absorb current findings into the baseline "
                         "(entries need reviewer reasons); optional "
                         "PATH writes elsewhere than --baseline")
    sp.add_argument("--changed-only", action="store_true",
                    help="only report on files with uncommitted changes "
                         "(git status)")
    sp.set_defaults(fn=cmd_lint)

    return p


def main(argv=None):
    args = build_parser().parse_args(argv)
    if getattr(args, "cmd", None) == "job" and \
            getattr(args, "job_cmd", None) == "submit":
        # strip a leading "--" separator from REMAINDER
        if args.entrypoint and args.entrypoint[0] == "--":
            args.entrypoint = args.entrypoint[1:]
        if not args.entrypoint:
            sys.exit("error: no entrypoint (rtpu job submit -- <cmd...>)")
    args.fn(args)


if __name__ == "__main__":
    main()
