"""What every cell's run shares: the way onto the chip, the count of
compilations, the profiler session, and the loading of a driver, a
reader or a configuration by the name ``BENCHMARK.json`` gives it.

Nothing here knows a cell, a configuration or a metric by name.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import os
import shutil
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
_T0 = time.perf_counter()


def log(msg: str) -> None:
    print(f"[{time.perf_counter() - _T0:7.2f}s] {msg}", flush=True)


def read_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_module(folder: str, name: str):
    """``benchmark/<folder>/<name>.py`` as a module, found by the name
    in a data file. Names may hold dots, so this goes by path. A
    quantity that the manifest splits by what it moves (``x.train``,
    ``x.serve``) may share one reader, ``x.py``."""
    path = os.path.join(HERE, folder, name + ".py")
    if not os.path.isfile(path):
        path = os.path.join(HERE, folder, name.split(".")[0] + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"benchmark/{folder}/{name}.py is missing")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{folder}_{name}".replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def model_config(config: dict, rehearse: bool):
    """The model's configuration object, built from the data in the
    configuration's file: {"module", "class", "fields"}. A rehearsal
    takes the file's ``rehearse.fields`` instead (a tiny preset)."""
    model = config["model"]
    fields = dict(model["fields"])
    if rehearse:
        fields = dict(config["rehearse"]["fields"])
    cls = getattr(importlib.import_module(model["module"]), model["class"])
    return cls(**fields), fields


def sized(section: dict, rehearse: bool) -> dict:
    """A data section with its ``rehearse`` overrides laid over it."""
    out = {k: v for k, v in section.items() if k != "rehearse"}
    if rehearse:
        out.update(section.get("rehearse", {}))
    return out


class Compiles:
    """Every backend compilation of this process, with the time it
    ended, so that those inside the measured window can be counted.
    Cache hits and misses of the persistent cache are counted too."""

    def __init__(self):
        self.ended = []
        self.hits = 0
        self.misses = 0

    def install(self):
        import jax

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.ended.append((time.perf_counter(), duration))

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)
        return self

    def between(self, t0: float, t1: float) -> int:
        return sum(1 for t, _ in self.ended if t0 <= t <= t1)


def open_device(chips: int, rehearse: bool) -> dict:
    """First jax touch: name the device, refuse anything but a TPU with
    enough chips (unless rehearsing), place the compile cache."""
    import jax

    devices = jax.devices()
    info = {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}
    log(f"device: {info}")
    if not rehearse and (info["platform"] != "tpu" or len(devices) < chips):
        raise SystemExit(
            f"benchmark: needs {chips} TPU chip(s), jax found {info} "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}); no "
            f"result")
    if not rehearse:
        from ray_tpu._private.backend_probe import enable_compile_cache

        # Every program goes to the persistent cache, the small ones
        # too: a second run in this checkout compiles nothing.
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        placed = ("placed by JAX_COMPILATION_CACHE_DIR"
                  if os.environ.get("JAX_COMPILATION_CACHE_DIR")
                  else "fixed, inside the checkout")
        log(f"compile cache: {enable_compile_cache()} ({placed})")
    return info


def memory_peaks() -> dict:
    """Peak bytes on the fullest device so far, as jax reports them:
    ``in_use`` (``peak_bytes_in_use``: live buffers, that is parameters,
    optimizer state, KV pools), ``reserved`` (``peak_bytes_reserved``:
    what the TPU runtime sets aside for the temporaries of the programs
    it runs, counted apart from live buffers and out of the same
    ``bytes_limit``) and their ``sum``, the device's peak. The sum is
    exact where the program with the largest temporaries runs while
    the live buffers are at their peak (a training step over its
    state, a decode step over its pools) and an upper bound elsewhere."""
    import jax

    best = {"in_use": 0, "reserved": 0, "sum": 0, "limit": 0}
    for d in jax.devices():
        stats = d.memory_stats() or {}
        log(f"memory_stats of {d}: {stats}")
        one = {"in_use": int(stats.get("peak_bytes_in_use", 0)),
               "reserved": int(stats.get("peak_bytes_reserved", 0)),
               "limit": int(stats.get("bytes_limit", 0))}
        one["sum"] = one["in_use"] + one["reserved"]
        if one["sum"] >= best["sum"]:
            best = one
    return best


class Tracer:
    """One ``jax.profiler`` session over a few seconds of the steady
    window, python tracer off (PR 21's ``_start_xla_trace``: the python
    tracer hides live threads from the host profiler for good). Only
    the process that holds the chip can trace it: this one."""

    def __init__(self, enabled: bool):
        self.enabled = enabled
        self.dir = None
        self.t_start = self.t_stop = None

    def start(self):
        if not self.enabled or self.dir is not None:
            return
        import jax

        self.dir = tempfile.mkdtemp(prefix="benchmark-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.t_start = time.perf_counter()

    def stop(self):
        if self.t_start is None or self.t_stop is not None:
            return
        import jax

        self.t_stop = time.perf_counter()
        jax.profiler.stop_trace()

    def state(self) -> dict:
        """Plain data that rebuilds this session in ``from_state``: a
        driver's loop may run as a copy of what the driver passed."""
        return {"dir": self.dir, "t_start": self.t_start,
                "t_stop": self.t_stop}

    @classmethod
    def from_state(cls, state: dict) -> "Tracer":
        t = cls(True)
        t.dir, t.t_start, t.t_stop = (state["dir"], state["t_start"],
                                      state["t_stop"])
        return t

    def reduce(self):
        """The reduced trace, or None where none was taken."""
        if self.t_stop is None:
            return None
        from benchmark import xplane

        try:
            return xplane.reduce_rows(
                xplane.read_xplane(xplane.find_xplane(self.dir)))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
