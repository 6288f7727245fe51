"""Accelerator detection has no fallback (ray_tpu/_private/backend_probe.py).

The process that hosts the device lane counts the chips in-process: an
explicit CPU platform counts 0 at once, a backend that fails to
initialize raises out of `device_count()` (and so out of `init()`), and
an accelerator the cost model does not know is an error, not a default.
"""

import os
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_device_count_cpu_platform_is_instant():
    from ray_tpu._private import backend_probe

    backend_probe.reset_cache()
    old = os.environ.get("JAX_PLATFORMS")
    os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        t0 = time.time()
        assert backend_probe.device_count() == 0
        assert time.time() - t0 < 0.1  # no backend touched
    finally:
        backend_probe.reset_cache()
        if old is not None:
            os.environ["JAX_PLATFORMS"] = old
        else:
            del os.environ["JAX_PLATFORMS"]


def test_device_count_uses_initialized_backend():
    """With an in-process CPU backend already up, the count comes from
    it directly (0 accelerators on the test mesh) and this process does
    not claim to hold chips."""
    import jax

    from ray_tpu._private import backend_probe

    jax.devices()  # ensure backend is initialized
    backend_probe.reset_cache()
    old = os.environ.pop("JAX_PLATFORMS", None)
    try:
        t0 = time.time()
        assert backend_probe.device_count() == 0
        assert time.time() - t0 < 0.5
        assert not backend_probe.holds_chips()
    finally:
        backend_probe.reset_cache()
        if old is not None:
            os.environ["JAX_PLATFORMS"] = old


_CPU_INIT_DRIVER = """
import ray_tpu
ray_tpu.init(num_cpus=1)
res = ray_tpu.cluster_resources()
assert res.get("TPU", 0) == 0, res
assert res.get("TPU_HOST", 0) == 0, res
import sys
assert "jax" not in sys.modules, "an explicit CPU init() must not import jax"
ray_tpu.shutdown()
print("TPU:", int(res.get("TPU", 0)), flush=True)
"""


def test_explicit_cpu_init_advertises_no_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-c", _CPU_INIT_DRIVER], env=env, cwd=REPO,
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "TPU: 0" in proc.stdout


def test_backend_init_exception_propagates(monkeypatch):
    """A backend that cannot be opened is an error, never "0 TPUs" on a
    process quietly pinned to the CPU."""
    import jax

    from ray_tpu._private import backend_probe

    def boom():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    jax.devices()   # the CPU backend is up: the pin below changes nothing
    backend_probe.reset_cache()
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(jax, "devices", boom)
    pinned = jax.config.jax_platforms
    jax.config.update("jax_platforms", "")      # no explicit CPU platform
    try:
        with pytest.raises(RuntimeError, match="Unable to initialize"):
            backend_probe.device_count()
        assert not backend_probe.holds_chips()
    finally:
        jax.config.update("jax_platforms", pinned)
        backend_probe.reset_cache()


def test_unknown_device_kind_raises_in_perfmodel():
    from ray_tpu.util import perfmodel

    class Dev:
        platform = "tpu"
        device_kind = "TPU v99"

    with pytest.raises(ValueError, match="unknown accelerator 'TPU v99'"):
        perfmodel.detect_hardware(Dev())
    Dev.device_kind = "TPU v5 lite"     # what a v5e chip reports
    assert perfmodel.detect_hardware(Dev()).flops_per_s == 197e12


def test_compile_cache_is_placed_from_outside(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set -> nothing is set in code; unset ->
    the one fixed directory inside the checkout."""
    import jax

    from ray_tpu._private import backend_probe

    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.append((k, v)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/where")
    assert backend_probe.enable_compile_cache() == "/some/where"
    assert calls == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    fixed = os.path.join(REPO, ".jax_cache")
    assert backend_probe.enable_compile_cache() == fixed
    assert calls == [("jax_compilation_cache_dir", fixed)]


def test_claim_chips_is_a_no_op_on_an_explicit_cpu_platform(monkeypatch):
    from ray_tpu._private import backend_probe

    backend_probe.reset_cache()
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setattr(backend_probe, "enable_compile_cache",
                        lambda: pytest.fail("CPU runs keep the cache off"))
    assert backend_probe.claim_chips() is False
    assert not backend_probe.holds_chips()


def test_device_lane_startup_claims_chips_it_never_counted(monkeypatch):
    """`init(num_tpus=N)` skips the count, so the claim is made where the
    device lane first runs: the compile cache goes on before the first
    device task, and from then on a TPU_HOST gang worker is refused."""
    import ray_tpu
    from ray_tpu._private import backend_probe
    from ray_tpu._private.exceptions import ActorDiedError

    cache_on = []
    backend_probe.reset_cache()
    monkeypatch.setattr(backend_probe, "_explicit_cpu", lambda: False)
    monkeypatch.setattr(backend_probe, "enable_compile_cache",
                        lambda: cache_on.append(1))
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=1, num_tpus=1)
    try:
        assert not backend_probe.holds_chips()      # nothing counted

        @ray_tpu.remote(num_cpus=0, scheduling_strategy="device")
        def on_lane():
            from ray_tpu._private import backend_probe as bp

            return bp.holds_chips()

        assert ray_tpu.get(on_lane.remote(), timeout=60) is True
        assert ray_tpu.get(on_lane.remote(), timeout=60) is True
        assert cache_on == [1]      # once, by the first task

        @ray_tpu.remote(num_cpus=0, resources={"TPU_HOST": 1})
        class Gang:
            def ping(self):
                return 1

        with pytest.raises(ActorDiedError, match="TPU_HOST gang worker"):
            ray_tpu.get(Gang.remote().ping.remote(), timeout=60)
    finally:
        ray_tpu.shutdown()
        backend_probe.reset_cache()
