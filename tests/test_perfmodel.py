"""The analytic device-step cost model (util/perfmodel.py): FLOP/byte
formulas checked against hand-expanded arithmetic for GPT-2-small,
roofline verdict boundaries, the hardware peak table, StepAccounting's
begin/add/finish lifecycle, and the process-local device-step ring the
gang profiler drains.

The FLOP identities matter beyond this file: GPTConfig.flops_per_token
and the live llm_mfu/train_mfu telemetry series
both price against these exact formulas, so a drift here is a lie in
every MFU number the system prints.
"""

import functools
import time

import pytest

from ray_tpu.models.gpt import GPT2_SMALL, GPTConfig, TINY
from ray_tpu.util import perfmodel
from ray_tpu.util.perfmodel import (
    HARDWARE_PEAKS,
    StepAccounting,
    StepCost,
    decode_step_cost,
    detect_hardware,
    prefill_cost,
    roofline,
    train_flops_per_token,
    train_step_cost,
)


# ---------------------------------------------------------------------------
# Hand-expanded GPT-2-small constants (vocab 50304 padded, seq 1024,
# d_model 768, 12 layers, 12 heads, ff 3072). Everything below is
# written out longhand on purpose: these tests must not share the
# formulas they check.
# ---------------------------------------------------------------------------
M, F, L, V, S = 768, 3072, 12, 50304, 1024
H = HK = 12
D = 64  # head_dim
# num_params: wte + wpe + L*(wq+wk+wv+wo + wi+wm + 2 layernorms) + ln_f
N_PARAMS = (V * M + S * M
            + L * (M * M * 2 + 2 * M * HK * D + 2 * M * F + 2 * M) + M)
# matmul weights (no embeddings/layernorms): per layer
# wq (m*h*d) + wk+wv (2*m*hk*d) + wo (h*d*m) + wi+wm (2*m*f), + unembed.
W_MATMUL = L * (M * H * D + 2 * M * HK * D + H * D * M + 2 * M * F) + V * M


def test_gpt2_small_hand_constants():
    assert GPT2_SMALL.num_params() == N_PARAMS
    assert N_PARAMS == 124_373_760  # the familiar "124M"
    assert perfmodel._shape(GPT2_SMALL)["matmul_weights"] == W_MATMUL


def test_train_flops_per_token_is_6n_plus_attention():
    want = 6.0 * N_PARAMS + 12.0 * L * M * S
    assert train_flops_per_token(GPT2_SMALL) == want
    assert want == 859_488_768.0
    # GPTConfig.flops_per_token delegates here.
    assert GPT2_SMALL.flops_per_token() == want
    # Explicit shorter sequence shrinks only the quadratic term.
    assert train_flops_per_token(GPT2_SMALL, seq=256) == \
        6.0 * N_PARAMS + 12.0 * L * M * 256


def test_decode_step_cost_hand_computed():
    ctx = [100, 200, 300]
    c = decode_step_cost(GPT2_SMALL, ctx)
    # 2 MACs per weight per lane + 4*m*L per context position.
    assert c.flops == 2.0 * W_MATMUL * 3 + 4.0 * M * L * 600
    kvb = 2 * L * HK * D * 2  # k+v elements/token at bf16
    # The weights stream as they are served: cfg.dtype, two bytes each
    # (PR 61; float32 leaves priced at four are a caller's to ask for).
    assert c.hbm_bytes == N_PARAMS * 2 + 600 * kvb + 3 * kvb
    assert decode_step_cost(GPT2_SMALL, ctx, param_bytes=4).hbm_bytes \
        == N_PARAMS * 4 + 600 * kvb + 3 * kvb
    assert c.tokens == 3
    # Batching amortizes the weight read: per-token HBM must drop.
    solo = decode_step_cost(GPT2_SMALL, [200])
    assert c.hbm_bytes / 3 < solo.hbm_bytes


def test_prefill_cost_hand_computed():
    T = 128
    c = prefill_cost(GPT2_SMALL, T)
    # Causal: position i attends i+1 keys -> sum = T*(T+1)/2. The head
    # runs on the span's last row alone.
    head = GPT2_SMALL.vocab_size * M
    assert c.flops == 2.0 * (W_MATMUL - head) * T + 2.0 * head \
        + 4.0 * M * L * T * (T + 1) / 2
    kvb = 2 * L * HK * D * 2
    assert c.hbm_bytes == N_PARAMS * 2 + 2.0 * T * kvb
    assert c.tokens == T


def test_train_step_cost_hand_computed():
    c = train_step_cost(GPT2_SMALL, batch=4, seq=512)
    tokens = 4 * 512
    assert c.flops == train_flops_per_token(GPT2_SMALL, 512) * tokens
    assert c.hbm_bytes == 8.0 * N_PARAMS * 4 + 14.0 * M * L * tokens * 2
    assert c.tokens == tokens


def test_step_cost_addition():
    a = StepCost(1.0, 2.0, 3) + StepCost(10.0, 20.0, 30)
    assert (a.flops, a.hbm_bytes, a.tokens) == (11.0, 22.0, 33)


# ---------------------------------------------------------------------------
# Hardware table + roofline verdicts
# ---------------------------------------------------------------------------
class _Dev:
    def __init__(self, platform, device_kind):
        self.platform, self.device_kind = platform, device_kind


def test_hardware_table_and_detection():
    # Keyed by the device_kind jax reports for a v5e chip.
    assert HARDWARE_PEAKS["TPU v5 lite"].flops_per_s == 197e12
    assert HARDWARE_PEAKS["TPU v5 lite"].hbm_bytes_per_s == 819e9
    assert detect_hardware(_Dev("tpu", "TPU v5 lite")) \
        is HARDWARE_PEAKS[perfmodel.V5E]
    # The CPU backend (the test environment) has no peak at all.
    assert detect_hardware() is None
    assert detect_hardware(_Dev("cpu", "cpu")) is None
    # An accelerator outside the table is an error, never a default.
    with pytest.raises(ValueError, match="unknown accelerator 'TPU v9'"):
        detect_hardware(_Dev("tpu", "TPU v9"))


def test_cpu_runs_get_no_utilization():
    """Without a peak a step publishes counts and times — no MFU, no
    HBM utilization, no verdict."""
    assert roofline(StepCost(1e9, 1e6, 4), 0.01, 0.0, hw=None) == {}
    acc = StepAccounting()          # CPU backend: hw is None
    assert acc.hw is None
    acc.begin()
    acc.add_device(0.002, StepCost(1e9, 1e6, 4))
    out = acc.finish()
    assert out["tokens"] == 4 and out["flops"] == 1e9
    assert out["hbm_bytes"] == 1e6 and out["device_ms"] > 0
    assert not {"mfu", "hbm_util", "verdict", "hardware"} & set(out)


def test_roofline_verdicts():
    hw = HARDWARE_PEAKS[perfmodel.V5E]
    # Pure compute: lots of flops, no bytes.
    r = roofline(StepCost(197e12 * 0.5, 0.0), 1.0, 0.0, hw=hw)
    assert r["mfu"] == pytest.approx(0.5)
    assert r["verdict"] == "compute"
    # Bandwidth-bound: bytes dominate the roof.
    r = roofline(StepCost(197e12 * 0.01, 819e9 * 0.8), 1.0, 0.0, hw=hw)
    assert r["hbm_util"] == pytest.approx(0.8)
    assert r["verdict"] == "hbm"
    # Host-bound wins regardless of the device-side ratio.
    r = roofline(StepCost(197e12 * 0.5, 0.0), 1.0, 2.0, hw=hw)
    assert r["verdict"] == "host"
    # Multi-chip denominators scale both utilizations.
    r4 = roofline(StepCost(197e12, 0.0), 1.0, 0.0, hw=hw, n_chips=4)
    assert r4["mfu"] == pytest.approx(0.25)
    # Degenerate device span must not divide by zero.
    assert roofline(StepCost(1.0, 1.0), 0.0, hw=hw)["mfu"] > 0


# ---------------------------------------------------------------------------
# StepAccounting + the device-step ring
# ---------------------------------------------------------------------------
def test_step_accounting_lifecycle():
    acc = StepAccounting(hw=HARDWARE_PEAKS[perfmodel.V5E])
    acc.begin()
    out = acc.finish()
    assert out is None and acc.last is None  # idle tick: not a step

    acc.begin()
    acc.add_device(0.010, StepCost(197e12 * 0.010 * 0.4, 0.0, 7))
    out = acc.finish()
    assert out["mfu"] == pytest.approx(0.4, rel=1e-6)
    assert out["tokens"] == 7
    assert out["step_ms"] >= out["device_ms"] == pytest.approx(10.0)
    assert out["host_gap_ms"] == pytest.approx(
        out["step_ms"] - out["device_ms"])
    assert acc.last is out

    # Device spans accumulate across multiple dispatches in one step.
    acc.begin()
    acc.add_device(0.004, StepCost(1e9, 1e6, 2))
    acc.add_device(0.006, StepCost(1e9, 1e6, 3))
    out = acc.finish()
    assert out["device_ms"] == pytest.approx(10.0)
    assert out["tokens"] == 5


def test_spans_open_at_once_keep_the_partition():
    """A step that queues three programs and then collects them: the
    spans are open together, their halves are disjoint ``with`` blocks.
    ``device_ms_by`` still sums to ``device_ms``; a span is its two
    halves and none of the host work between them; no wait half begins
    before the one before it ended; ``stall_ms`` (the interval less the
    CPU time and the WAIT halves) is not negative beyond a tick of the
    thread's CPU clock, as it would be if a wait half held host work
    too; and the step counts its programs and those queued before its
    first wait."""
    tick_ms = 2e3 * time.get_clock_info("thread_time").resolution + 1.0

    def spin(s):
        end = time.perf_counter() + s
        while time.perf_counter() < end:
            pass

    acc = StepAccounting()
    for _ in range(2):
        acc.begin()
        programs, marks, halves = [], [], []
        for name in ("llm.prefill.device", "llm.prefill.device",
                     "llm.decode.device"):
            t0 = time.perf_counter()
            with acc.dispatch(name) as prog:
                spin(0.002)
            halves.append(time.perf_counter() - t0)
            programs.append(prog)
            with acc.phase("llm.prefill.host"):
                spin(0.003)             # the device works; the host too
        for prog in programs:
            t0 = time.perf_counter()
            with prog.waiting():
                time.sleep(0.004)       # blocked on its result
            marks.append((t0, time.perf_counter()))
            with acc.phase("llm.emit"):
                spin(0.001)
        out = acc.finish()
        assert sum(out["device_ms_by"].values()) == pytest.approx(
            out["device_ms"], abs=1e-6)
        assert set(out["device_ms_by"]) == {"prefill", "decode"}
        assert out["step_ms"] == pytest.approx(
            out["device_ms"] + out["host_gap_ms"], abs=1e-6)
        assert out["host_gap_ms"] == pytest.approx(
            sum(out["phases_ms"].values()) + out["other_ms"], abs=1e-6)
        # Nine host blocks of 3 ms and 1 ms lie between the halves,
        # in no span: each program is its dispatch half (2 ms) and its
        # wait half (4 ms), and no longer than the two ``with`` blocks
        # as this test timed them from outside, however long the box
        # let the sleep run (a bound of 12 ms on the span failed once
        # under six xdist workers).
        assert out["phases_ms"]["llm.prefill.host"] >= 9.0
        for prog, half, (w0, w1) in zip(programs, halves, marks):
            assert 0.002 <= prog.dispatch_seconds <= half
            assert prog.dispatch_seconds + 0.004 <= prog.seconds \
                <= half + (w1 - w0)
        assert out["device_ms"] == pytest.approx(
            1e3 * sum(p.seconds for p in programs), abs=1e-6)
        assert sum(out["dispatch_ms_by"].values()) == pytest.approx(
            1e3 * sum(p.dispatch_seconds for p in programs), abs=1e-6)
        assert all(a[1] <= b[0] for a, b in zip(marks, marks[1:]))
        assert out["stall_ms"] >= -tick_ms
        assert (out["programs"], out["programs_queued"]) == (3, 3)

    # A fetch between two dispatches: what follows it was not queued.
    acc.begin()
    with acc.dispatch("llm.prefill.device") as first:
        pass
    with first.waiting():
        pass
    with acc.dispatch("llm.decode.device") as second:
        pass
    with second.waiting():
        pass
    out = acc.finish()
    assert (out["programs"], out["programs_queued"]) == (2, 1)
    # A step that opened no such span carries neither key.
    acc.begin()
    acc.add_device(0.001)
    assert "programs" not in acc.finish()
    with pytest.raises(KeyError):
        acc.dispatch("llm.emit")
    with pytest.raises(KeyError):
        acc.dispatch("train.wait")      # a span that is not cut in two


def test_device_step_ring_records_and_filters():
    perfmodel.clear_device_steps()
    t0 = time.time()
    acc = StepAccounting(hw=HARDWARE_PEAKS[perfmodel.V5E])
    acc.begin()
    acc.add_device(0.001, StepCost(1e6, 1e5, 1))
    acc.finish(record_as="llm.step", attrs={"deployment": "d1"})
    perfmodel.record_device_step("train.step", time.time(),
                                 {"step_ms": 3.0}, {"trial": "t1"})
    evs = perfmodel.device_step_events(since=t0 - 1.0)
    assert [e["name"] for e in evs] == ["llm.step", "train.step"]
    assert evs[0]["deployment"] == "d1"
    assert evs[0]["mfu"] > 0
    assert evs[1]["trial"] == "t1"
    # since= filters out the past.
    assert perfmodel.device_step_events(since=time.time() + 60) == []
    perfmodel.clear_device_steps()
    assert perfmodel.device_step_events() == []


def test_shape_cache_handles_id_reuse():
    """id() reuse after GC must not serve a stale entry."""
    for _ in range(5):
        cfg = GPTConfig(d_model=128, n_layer=2, n_head=4,
                        vocab_size=512, max_seq=128)
        got = perfmodel._shape(cfg)["num_params"]
        assert got == cfg.num_params()
    assert perfmodel._shape(TINY)["num_params"] == TINY.num_params()


def test_one_step_cost_prices_decode_and_verify_as_it_always_did():
    """decode_step_cost is the one step cost. With q_lens omitted it
    gives the figures it gave before the verify step's cost was folded
    into it; with q_lens, that cost's own (both pinned from the tree
    before the merge, whose GPT weights were float32 at rest: four
    bytes a parameter, asked for here); and q_lens of ones is q_lens
    omitted. Since PR 61 the default is the served tree's two."""
    from ray_tpu.models.gpt import TINY

    def figures(c):
        return (c.flops, c.hbm_bytes, c.tokens)

    f32 = functools.partial(decode_step_cost, param_bytes=4)
    ctx = [100, 200, 300]
    assert figures(f32(GPT2_SMALL, ctx)) == (
        763527168.0, 519724032.0, 3)
    assert figures(f32(GPT2_SMALL, ctx, [1, 1, 1])) == (
        763527168.0, 519724032.0, 3)
    assert figures(f32(GPT2_SMALL, [104, 205, 301], [5, 5, 1])) == (
        2785812480.0, 520387584.0, 11)
    assert figures(f32(TINY, [7, 33])) == (
        1875968.0, 1946112.0, 2)
    assert figures(f32(TINY, [9, 36], q_lens=[3, 4])) == (
        6588416.0, 1956352.0, 7)
    assert figures(decode_step_cost(GPT2_SMALL, ctx)) == (
        763527168.0, 519724032.0 - 2 * GPT2_SMALL.num_params(), 3)
