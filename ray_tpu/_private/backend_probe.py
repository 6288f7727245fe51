"""Accelerator detection and the compile cache for the chip-owning process.

A TPU chip belongs to ONE process at a time: the first process that
initializes jax's TPU backend holds every local chip until it exits, and
a second claimant fails or hangs. So exactly one process per host may
count chips in-process — the one that hosts the device lane (the driver
in local mode, the node daemon in a detached cluster). CLIs and
attaching drivers never call :func:`device_count`.

There is no fallback: with a local ``libtpu`` backend init either works
or raises, and the exception propagates out of ``init()``. An explicit
CPU platform (``JAX_PLATFORMS=cpu`` or an in-process
``jax.config.update("jax_platforms", "cpu")``) counts 0 without
importing jax.

Reference parity: python/ray/_private/accelerators/tpu.py (chip
counting for resource autodetection).
"""

from __future__ import annotations

import os

# Per-process cached device count (repeated init() calls in one process).
_cached: int | None = None

# True once this process is the host's chip owner (see claim_chips).
_claimed = False

# The one fixed in-checkout cache directory: the path is part of jax's
# cache key, so it must never carry a pid, a time or a temporary name.
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def _explicit_cpu() -> bool:
    """An explicit CPU platform, from the environment or in-process."""
    if os.environ.get("JAX_PLATFORMS", "") == "cpu":
        return True
    import jax

    return jax.config.jax_platforms == "cpu"


def device_count() -> int:
    """Number of non-CPU jax devices, counted in THIS process.

    Initializes the accelerator backend, so a caller that finds chips
    has become the process that owns them (:func:`claim_chips`).
    Backend-init failures raise.
    """
    global _cached
    if _cached is not None:
        return _cached
    if _explicit_cpu():
        _cached = 0
        return 0
    import jax

    _cached = sum(1 for d in jax.devices() if d.platform != "cpu")
    if _cached:
        claim_chips()
    return _cached


def claim_chips() -> bool:
    """Device-lane start-up on a chip-bearing node: this process is
    about to run jax code on the host's chips (or has just opened them
    to count them), so it is their one owner from here on.

    Turns the persistent compile cache on — before the first compile —
    and records the ownership that :func:`holds_chips` reports, however
    the node learned its chip count (``init(num_tpus=N)``,
    ``resources={"TPU": N}`` and ``rtpu start --num-tpus`` never call
    :func:`device_count`). Nothing is claimed under an explicit CPU
    platform: tests advertise TPUs they do not have, and keep the cache
    off. Returns whether the process holds the chips.
    """
    global _claimed
    if not _claimed and not _explicit_cpu():
        enable_compile_cache()
        _claimed = True
    return _claimed


def holds_chips() -> bool:
    """True once this process owns the host's chips — no other process
    on the host can open them until it exits."""
    return _claimed


def enable_compile_cache() -> str:
    """Turn on jax's persistent compilation cache for the process that
    owns the chip; returns the directory in use.

    ``JAX_COMPILATION_CACHE_DIR`` places the cache from outside: when it
    is set jax reads it itself and nothing is set in code. Otherwise the
    cache lives in one fixed directory inside the checkout.
    """
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_COMPILE_CACHE_DIR)
    return DEFAULT_COMPILE_CACHE_DIR


def reset_cache() -> None:
    """Test hook: forget the per-process device count and ownership."""
    global _cached, _claimed
    _cached = None
    _claimed = False
