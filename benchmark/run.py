"""One run of one cell of the benchmark, in one process that owns the chip
(a serving cell's callers are generator subprocesses of their own,
``drivers/callers.py``: clients, which take no chip).

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (everything up to the first timed instant: imports, ``init``,
weights from ``--seed``, deployment or trainer start, warm-up of the
shapes the cell uses, the reference comparison), then a measured window
of ``--seconds``, then drain and print. The last line of stdout is the
one JSON object the driver reads: with ``--trace 0`` the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics, read from
a run that also holds a ``jax.profiler`` session over a few seconds of
the steady window.

Everything a cell is made of is found by name: ``BENCHMARK.json`` names
the cell and its metrics, ``workloads/<cell>.json`` its configuration,
driver and traffic, ``configs/<config>.json`` the model and deployment,
``drivers/<driver>.py`` the code that drives it, and
``end_to_end/<metric>.py`` / ``layer_metrics/<metric>.py`` one reader a
metric. This file knows none of those names.

``--rehearse`` runs the same control flow at the configuration's tiny
``rehearse`` sizes on whatever backend jax has, prints counts only, no
result line, and exits 3. Without it, anything but a TPU with the
cell's number of chips ends the run non-zero with no result line.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()       # set-up starts with the process

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def metrics_of(manifest: dict, section: str, cell: str) -> list:
    """The metrics of ``section`` that this cell reports."""
    return [m for m in manifest[section]
            if cell in m.get("workloads", [cell])]


def read_metrics(folder: str, metrics: list, collected: dict,
                 rehearse: bool = False) -> dict:
    from benchmark import harness

    out = {}
    for m in metrics:
        try:
            value = harness.load_module(folder, m["name"]).read(collected)
        except KeyError as e:
            # A share of a peak has no meaning on a device without
            # published peaks (benchmark/peaks.json): an error on the
            # chip, a reader that ran as far as it can in a rehearsal.
            if not rehearse:
                raise
            harness.log(f"{m['name']}: refused here: {e}")
            continue
        if value is None:
            harness.log(f"{m['name']}: nothing to read; left out")
            continue
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on any backend; exits 3, no result")
    args = ap.parse_args()

    from benchmark import harness, named_kernels, xplane

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    workload = harness.read_json("workloads", args.workload + ".json")
    config = harness.read_json("configs", workload["config"] + ".json")
    driver = harness.load_module("drivers", workload["driver"])
    e2e = metrics_of(manifest, "end_to_end", args.workload)
    layer = metrics_of(manifest, "per_layer", args.workload)
    if not args.rehearse and len(e2e) < 2:
        raise SystemExit(f"benchmark: BENCHMARK.json gives cell "
                         f"{args.workload!r} no end-to-end metrics")
    seconds = args.seconds if args.seconds is not None \
        else manifest["run_seconds"]

    device = harness.open_device(int(config["chips"]), args.rehearse)
    compiles = harness.Compiles().install()
    import ray_tpu

    model_cfg, model_fields = harness.model_config(config, args.rehearse)
    ctx = types.SimpleNamespace(
        workload_name=args.workload, workload=workload, config=config,
        model_cfg=model_cfg, model_fields=model_fields,
        chips=int(config["chips"]), seed=args.seed, seconds=float(seconds),
        trace=bool(args.trace), rehearse=args.rehearse, device=device,
        # What a driver starts outside this process (the serving cells'
        # generators) it lists here, and it is stopped and waited for
        # whatever becomes of the run.
        cleanup=[])
    ray_tpu.init(num_cpus=2)
    try:
        collected = driver.run(ctx)
        # A driver that has more to do after its window (comparisons
        # with a reference) reads the peaks itself, as the window closes.
        memory = collected.pop("memory", None) or harness.memory_peaks()
    finally:
        for stop in ctx.cleanup:
            stop()
        ray_tpu.shutdown()

    collected.update(config=config, workload=workload, device=device,
                     rehearse=args.rehearse,
                     model_fields=model_fields,
                     set_up_seconds=collected["t_open"] - _T0)
    inside = compiles.between(collected["t_open"], collected["t_close"])
    harness.log(f"set-up {collected['set_up_seconds']:.2f} s; compile cache "
                f"{compiles.hits} hits, {compiles.misses} misses; "
                f"{len(compiles.ended)} compilations, {inside} of them "
                f"INSIDE the measured window")
    tracer = collected.pop("tracer", None)
    collected["trace"] = tracer.reduce() if tracer else None
    correct = True
    for ok, what in collected["checks"]:
        harness.log(f"[{'ok' if ok else 'FAIL'}] {what}")
        correct = correct and bool(ok)
    harness.log(f"attempted {collected['attempted']}, failed "
                f"{collected['failed']}")
    if args.rehearse:
        # Counts only: a CPU figure is never written under the name of
        # a device metric, so the readers run and their values are not
        # shown.
        for folder, ms in (("end_to_end", e2e), ("layer_metrics", layer)):
            got = read_metrics(folder, ms, collected, rehearse=True)
            harness.log(f"{folder}: {len(got)} of {len(ms)} readers "
                        f"found something to read: {sorted(got)}")
        harness.log("rehearsal finished: control flow only, not a chip "
                    "run; exiting 3 without a result")
        return 3

    line = {"correct": correct, "attempted": int(collected["attempted"]),
            "failed": int(collected["failed"]),
            "device": dict(device, memory_peak_bytes=memory["sum"],
                           memory_in_use_peak_bytes=memory["in_use"],
                           memory_reserved_peak_bytes=memory["reserved"],
                           memory_limit_bytes=memory["limit"],
                           **collected.get("device_extra", {})),
            "compiles_in_window": inside}
    if args.trace:
        trace = collected["trace"]
        if trace is None or trace["busy_s"] <= 0:
            raise SystemExit("benchmark: the traced window holds no "
                             "device operation; no result")
        line["metrics"] = read_metrics("layer_metrics", layer, collected)
        for name, secs, ops in named_kernels.unread(trace):
            harness.log(f"Mosaic kernels that no reader of this cell "
                        f"counts: {name} ({ops} ops, {secs * 1e3:.3f} ms)")
        line["device"].update(busy_s=trace["busy_s"],
                              window_s=trace["window_s"])
        # Idle gaps by host activity need host spans on the device's
        # clock, which the program does not write yet (PERF.md section 7).
        line["breakdown"] = {"device_ops": xplane.top_ops(trace, 10),
                             "idle_gaps": []}
    else:
        line["metrics"] = read_metrics("end_to_end", e2e, collected)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    # The serving engine's step loop is a daemon thread that is still
    # stepping on the chip when requests were cut off at the window's
    # close, and interpreter shutdown then hangs in the TPU runtime
    # (PERF.md section 6, PR 23). Everything the run started has been
    # shut down by now, so leave without the interpreter's teardown.
    try:
        code = main()
    except SystemExit as e:
        code = e.code if isinstance(e.code, int) else 1
        if not isinstance(e.code, int) and e.code is not None:
            print(e.code, file=sys.stderr)
    except BaseException:  # noqa: BLE001 - reported, then the exit below
        import traceback

        traceback.print_exc()
        code = 1
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code if code is not None else 0)
