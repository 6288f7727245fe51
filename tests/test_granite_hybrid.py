"""models/granite_hybrid.py (a Mamba-2 or attention mixer AND routed
experts with a shared MLP in every layer, under four multipliers and a
tied head) against its plain reference, at small sizes on the CPU in
float32 with ONE group of MORE heads than either state-space kernel
takes in a block: the published parameter count, the shares of the
expert block, prefill in spans then decode through keys, values and
state against the reference's full forward pass, and the engine around
them."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm.engine import FINISHED, LLMEngine
from ray_tpu.models import (granite_hybrid as gh, granite_hybrid_ref as ref,
                            layers, mamba2, nemotron_h as nh, pack_span,
                            pack_step, serving)
from ray_tpu.ops import moe, ssm
from ray_tpu.util import perfmodel

# 128 heads in ONE group: two blocks of 64 in ``ssm_update``, eight of
# 16 in ``ssd_scan``, all reading one B and one C row.
TINY = gh.GraniteHybridConfig(
    vocab_size=256, hidden_size=64, num_hidden_layers=3,
    layer_types=("mamba", "attention", "mamba"), num_attention_heads=4,
    num_key_value_heads=2, mamba_n_heads=128, mamba_d_head=2,
    mamba_d_state=16, mamba_n_groups=1, mamba_chunk_size=8,
    num_local_experts=16, num_experts_per_tok=3, intermediate_size=32,
    shared_intermediate_size=48, experts_held=16, max_seq=128,
    dtype="float32")
BS = 8


@pytest.fixture(scope="module")
def tiny():
    return TINY, gh.init(jax.random.key(0), TINY)


def test_parameter_count_reproduces_the_models_name():
    """From the published config alone: 32.21 B parameters, 8.80 B a
    token (10 experts, the tied matrix counted once); and the cell's
    share, one period of the pattern with half the experts and half the
    vocabulary."""
    cfg = gh.GraniteHybridConfig()
    assert cfg.layers_of("attention") == (5, 15, 25, 35)
    assert len(cfg.layers_of("mamba")) == 36
    assert cfg.head_dim == 128 and cfg.mamba.d_inner == 8192 \
        and cfg.mamba.conv_dim == 8448
    assert round(cfg.mixer_params("mamba") / 1e4) == 10229      # 102.29 M
    assert round(cfg.mixer_params("attention") / 1e4) == 4194   # 41.94 M
    assert cfg.expert_params == 9437184
    assert 3 * 4096 * cfg.shared_intermediate_size == 18874368
    assert round(cfg.num_params(72) / 1e7) == 3221
    assert round(cfg.num_params(10) / 1e7) == 880
    share = gh.GraniteHybridConfig(
        num_hidden_layers=10, layer_types=cfg.layer_types[10:20],
        vocab_size=50176, experts_held=36)
    assert share.layer_types == ("mamba",) * 5 + ("attention",) \
        + ("mamba",) * 4
    assert round(share.num_params() / 1e5) == 47572             # 4,757.2 M
    assert round(share.num_params(0) / 1e5) == 13598    # 1,154.3 + 205.5
    assert serving(share).state.slot_bytes == 9 * (4194304 + 50688) \
        == 38204928
    assert serving(share).kinds[0].layers == (5,)
    assert serving(share).state.layers == (0, 1, 2, 3, 4, 6, 7, 8, 9)


def test_init_makes_what_num_params_counts(tiny):
    cfg, params = tiny
    assert sum(x.size for x in jax.tree_util.tree_leaves(params)) \
        == cfg.num_params()
    assert "head" not in params                     # ONE tied matrix
    # The Mamba-2 mixer's own are models/nemotron_h.py's initialiser's.
    p = params["layers"][0]
    assert float(p["D"].min()) == 1.0 and float(p["A_log"].min()) >= 0.0
    assert 0.0009 < float(jax.nn.softplus(p["dt_bias"]).min())
    assert float(jax.nn.softplus(p["dt_bias"]).max()) < 0.11


def test_the_router_is_a_softmax_over_the_chosen_logits():
    """``moe.route`` (a softmax over all, the largest, renormalised)
    and the published form (the largest logits, a softmax over those)
    are the same numbers."""
    rng = np.random.default_rng(3)
    u = jnp.asarray(rng.normal(size=(40, 64)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(64, 16)) * 0.3, jnp.float32)
    _, experts, weights = moe.route(u, w, 3)
    with jax.default_matmul_precision("highest"):
        idx, want = ref.route(u, {"router": w}, TINY)
    assert np.array_equal(np.sort(experts, -1), np.sort(idx, -1))
    assert np.abs(np.sort(weights, -1) - np.sort(want, -1)).max() < 1e-6
    assert np.abs(np.asarray(weights).sum(-1) - 1).max() < 1e-6


def test_the_shares_routed_parts_add_up_to_the_uncut_layer(tiny):
    """Two shares of 8 experts: what each computes for its own experts,
    with the shared MLP (which both chips compute alike) counted once,
    is the uncut layer; the reference is given the same share."""
    cfg, params = tiny
    p = params["layers"][1]
    u = jnp.asarray(np.random.default_rng(2).normal(size=(10, 64)),
                    jnp.float32)
    layer = jax.jit(gh._experts, static_argnums=(2, 3))
    whole, sizes = layer(u, p, cfg, "chunk")
    assert int(sizes.sum()) == 10 * 3
    with jax.default_matmul_precision("highest"):
        assert np.abs(whole - ref.experts(u, ref._f32(p), cfg)).max() < 1e-5
        shared = ref.swiglu(u, p["s_gu"], p["s_down"])
    parts = 0
    for i in range(2):
        share = dataclasses.replace(cfg, experts_held=8, first_expert=8 * i)
        # A routed expert's weights go by its GLOBAL id: a share's
        # parameters are slices of the whole model's.
        ps = dict(p, w1=p["w1"][8 * i:8 * i + 8], w2=p["w2"][8 * i:8 * i + 8])
        made = gh.init_layer(jax.random.key(0), share, 1)
        assert all(np.array_equal(made[k], ps[k]) for k in ps)
        out, sizes = layer(u, ps, share, "chunk")
        assert sizes.shape == (8,)
        with jax.default_matmul_precision("highest"):
            want = ref.experts(u, ref._f32(ps), share)
        assert np.abs(out - want).max() < 1e-5
        parts = parts + (out - shared)
    assert np.abs(parts + shared - whole).max() < 1e-5


def _chunk(cfg, params, seq, table, upto, c, src, dst, pools, max_nb=16):
    pad = -c % BS
    toks = np.zeros((1, c + pad), np.int32)
    toks[0, :c] = seq[upto:upto + c]
    read = np.zeros((max_nb if upto else 0,), np.int32)
    if upto:
        read[:len(table)] = table
    b0 = upto // BS
    t = pack_span(read, table[b0:b0 + (c + pad) // BS], upto, c - 1, src, dst)
    row, tok, *pools = _CHUNK(params, toks, *pools[:2], t, *pools[2:], cfg)
    return np.asarray(row), pools


_CHUNK = jax.jit(gh.forward_prefill_chunk, static_argnums=(7,))
_STEP = jax.jit(gh.forward_step, static_argnames=("q", "cfg"))


@pytest.mark.parametrize("spans", [(16, 24, 3), (43,)])
def test_prefill_in_unequal_spans_then_decode_equals_the_reference(tiny,
                                                                   spans):
    """Logits, through keys, values and state: spans of unequal length
    (no multiple of the scan's block), the first from zeros WHATEVER the
    slot held, a span that reads one slot and writes another (a parked
    snapshot taken up), then one-token steps in place beside a padded
    lane. The logits are a sixteenth of an untied head's off a matrix
    drawn small (``EMBED_STD``), ~0.005, so the bound is tight."""
    cfg, params = tiny
    seq = np.random.default_rng(0).integers(0, 256, 50).tolist()
    want = np.asarray(ref.forward(params, seq, cfg))
    assert 1e-3 < np.abs(want).max() < 1.0
    pools = [jnp.zeros((1, 32, BS, 32)), jnp.zeros((1, 32, BS, 32)),
             jnp.full((2, 4, 128, 2, 16), 7.0),     # the last tenant's
             jnp.full((2, 4, 3, cfg.mamba.conv_dim), 7.0)]
    table, upto, slot = list(range(1, 9)), 0, 2
    for i, c in enumerate(spans):
        dst = 3 if i == 1 else slot         # the second span moves slots
        row, pools = _chunk(cfg, params, seq, table, upto, c, slot, dst,
                            pools)
        upto, slot = upto + c, dst
        assert np.abs(row - want[upto - 1]).max() < 2e-7, (i, c)
    assert upto == 43
    for pos in range(43, 50):
        packed = pack_step(
            [[seq[pos]], [0]], [[pos], [0]],
            np.array([table + [0] * 8, [0] * 16]), [pos + 1, 1], [1, 1],
            [[table[pos // BS]], [0]], [[pos % BS], [0]],
            state_slots=[slot, 0])
        logits, ids, *pools = _STEP(params, packed, *pools, q=1, cfg=cfg)
        assert np.abs(np.asarray(logits[0, 0]) - want[pos]).max() < 2e-7
        assert ids.shape == (2 + len(gh.COUNTERS), 1)
        assert int(ids[0, 0]) == int(want[pos].argmax())
        # Every layer has an expert block: 2 lanes x 3 experts each.
        assert int(ids[2 + gh.COUNTERS.index("moe_held_rows"), 0]) == 6


@pytest.mark.parametrize("field,value,moves", [
    ("embedding_multiplier", 1.0, True), ("residual_multiplier", 1.0, True),
    ("attention_multiplier", 4.0, True), ("logits_scaling", 1.0, True),
    ("mamba_chunk_size", 16, False)])
def test_each_multiplier_is_read_by_both_sides(tiny, field, value, moves):
    """Another value moves the served logits and the reference's alike
    (the block length of the scan moves neither)."""
    cfg, params = tiny
    other = dataclasses.replace(cfg, **{field: value})
    seq = np.random.default_rng(1).integers(0, 256, 24).tolist()
    want = np.asarray(ref.forward(params, seq, other))
    base = np.asarray(ref.forward(params, seq, cfg))
    assert (np.abs(want - base).max() > 2e-7) == moves
    pools = [jnp.zeros((1, 8, BS, 32)), jnp.zeros((1, 8, BS, 32)),
             jnp.zeros((2, 2, 128, 2, 16)),
             jnp.zeros((2, 2, 3, cfg.mamba.conv_dim))]
    row, _ = _chunk(other, params, seq, [1, 2, 3], 0, 24, 1, 1, pools)
    assert np.abs(row - want[23]).max() < 1e-5 * np.abs(want).max()


def test_the_mamba_mixer_is_nemotrons_called_not_copied():
    """The module writes no mixer, kernel call or counters of its own:
    the mixer functions it calls are models/mamba2.py's and
    models/layers.py's own objects, the ones Nemotron-H calls, and the
    two families describe their mixers to them by one record
    (``Mamba2``), each from its own published field names."""
    import inspect

    for name in ("mamba_step", "mamba_chunk", "mamba_params"):
        assert getattr(gh, name) is getattr(nh, name) \
            is getattr(mamba2, name), name
    for name in ("attention_step", "attention_chunk", "pool_index",
                 "counters", "COUNTERS", "normal", "rmsnorm"):
        assert getattr(gh, name) is getattr(nh, name) \
            is getattr(layers, name), name
    src = inspect.getsource(gh)
    for called in ("mamba_step(", "mamba_chunk(", "attention_step(",
                   "attention_chunk(", "mamba_params(", "pool_index(",
                   "counters(", "moe.route(", "moe.routed_experts("):
        assert called in src, called
    for written in ("pallas_call", "softplus", "ssd_scan", "ssm_update(",
                    "paged_attention_stored", "span_attention",
                    "def mamba_", "def attention", "def counters",
                    "def pool_index", "import nemotron_h", "nh."):
        assert written not in src, written
    ours = gh.GraniteHybridConfig().mamba
    theirs = nh.NemotronHConfig().mamba
    assert isinstance(ours, mamba2.Mamba2) and type(theirs) is type(ours)
    same = dict(hidden_size=4096, heads=128, head_dim=64, state=128,
                conv_kernel=4, eps=1e-5, dtype=jnp.dtype("bfloat16"),
                time_step=(0.001, 0.1, 1e-4))
    for name, value in same.items():
        assert getattr(ours, name) == getattr(theirs, name) == value, name
    assert (ours.d_inner, theirs.d_inner) == (8192, 8192)
    assert (ours.groups, theirs.groups) == (1, 8)
    assert (ours.chunk_size, theirs.chunk_size) == (256, 128)
    assert (ours.conv_dim, theirs.conv_dim) == (8448, 10240)
    assert ssm._head_block(ours.heads, ours.groups) == 64
    # No name on the configuration that only a mixer function read.
    # (``chunk_size`` stays: benchmark/reference_nemotron_h.py reads it.)
    for alias in ("mamba_num_heads", "mamba_head_dim", "n_groups",
                  "ssm_state_size", "conv_kernel", "layer_norm_epsilon",
                  "time_step_min", "time_step_max", "time_step_floor"):
        assert not hasattr(gh.GraniteHybridConfig, alias), alias


def test_the_seam_says_what_a_sequence_keeps():
    s = serving(TINY)
    assert s.state.layers == (0, 2) and s.kinds[0].layers == (1,)
    assert [shape for shape, _ in s.state.parts] \
        == [(128, 2, 16), (3, TINY.mamba.conv_dim)]
    assert s.state.parts[0][1] == jnp.float32       # S, whatever the dtype
    assert s.kinds[0].rows == (32, 32) and s.counters == nh.COUNTERS
    assert s.vocab_size == 256 and s.max_seq == 128 and s.at_rest is None
    cost = s.cost
    assert cost["num_params"] == TINY.num_params()
    assert cost["streamed_params"](0) == TINY.num_params(0)
    assert cost["streamed_params"](10 ** 6) == pytest.approx(
        TINY.num_params())
    assert cost["state_bytes_per_seq"] == s.state.slot_bytes
    assert cost["kv_bytes_per_token"] == 2 * 32


def test_decode_scores_one_row_a_lane():
    with pytest.raises(ValueError, match="q must be 1"):
        gh.forward_step(None, None, None, None, None, None, q=2, cfg=TINY)


@pytest.mark.parametrize("field,value", [
    ("hidden_act", "gelu"), ("position_embedding_type", "rope"),
    ("tie_word_embeddings", False), ("mamba_conv_bias", False),
    ("mamba_proj_bias", True), ("attention_bias", True),
    ("normalization_function", "layernorm"),
    ("layer_types", ("mamba", "attention")),
    ("layer_types", ("mamba", "mlp", "mamba")), ("first_expert", 9),
    ("mamba_n_groups", 3), ("num_key_value_heads", 3)])
def test_config_refuses_what_is_not_built(field, value):
    with pytest.raises(ValueError):
        dataclasses.replace(TINY, **{field: value})


# -- through the engine -------------------------------------------------------

RNG = np.random.default_rng(7)
PREFIX = RNG.integers(0, 256, 32).tolist()
BODY_A = RNG.integers(0, 256, 13).tolist()      # ragged: 45 tokens
BODY_B = RNG.integers(0, 256, 16).tolist()      # whole blocks: 48


@pytest.fixture(scope="module")
def greedy(tiny):
    cfg, params = tiny
    forward = jax.jit(lambda toks: ref.forward(params, toks, cfg))

    def answer(prompt, n):
        buf = np.zeros((96,), np.int32)
        buf[:len(prompt)] = prompt
        for i in range(len(prompt), len(prompt) + n):
            buf[i] = int(np.asarray(forward(buf))[i - 1].argmax())
        return buf[len(prompt):len(prompt) + n].tolist()

    return answer


def _run(eng, *reqs, steps=400):
    for _ in range(steps):
        if all(r.state == FINISHED for r in reqs):
            return [r.output for r in reqs]
        eng.step()
    raise AssertionError("the requests did not finish")


def test_the_engine_serves_it_through_a_parked_snapshot(tiny, greedy):
    """``LLMEngine`` knows nothing of the family: a preamble registered
    alone, two sharers beside each other (one ragged), their whole
    preamble a hit THROUGH the snapshot, and the tokens the plain
    reference gives; the ring carries the step program's counters."""
    cfg, params = tiny
    perfmodel.clear_device_steps()
    eng = LLMEngine(params, cfg, num_blocks=64, block_size=BS, max_batch=4,
                    prefill_chunk_tokens=16, state_slots=7,
                    name="granite-ring")
    _run(eng, eng.add_request(PREFIX, 1))
    a = eng.add_request(PREFIX + BODY_A, 6)
    b = eng.add_request(PREFIX + BODY_B, 5)
    assert _run(eng, a, b) == [greedy(PREFIX + BODY_A, 6),
                               greedy(PREFIX + BODY_B, 5)]
    assert a.cached_tokens == b.cached_tokens == len(PREFIX)
    st = eng.states.stats()
    assert st["state_taken"] == 2 and st["state_resumed_tokens"] == 64
    assert st["state_slots_live"] == 0
    decoded = [e for e in perfmodel.device_step_events()
               if e["name"] == "llm.step" and e["deployment"] == "granite-ring"
               and e["decode_tokens"]]
    assert decoded and all(
        {"moe_experts_hit", "moe_held_rows", "moe_load_max",
         "kv_pages_in_runs"} <= set(e) for e in decoded)
    with pytest.raises(ValueError):
        LLMEngine(params, cfg, num_blocks=64, block_size=BS, max_batch=4,
                  state_slots=7, speculative=object())
