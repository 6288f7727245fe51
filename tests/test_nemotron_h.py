"""models/nemotron_h.py (state-space layers beside attention, not-gated
experts in a latent space) against its plain reference, at small sizes
on the CPU in float32: the two forms of the recurrence, the not-gated
expert through the grouped product, the shares of the expert layer, the
published parameter count, and prefill in spans then decode through
keys, values and state against the reference's full forward pass."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import (nemotron_h as nh, nemotron_h_ref as ref,
                            pack_span, pack_step, serving, step_columns,
                            unpack_span)
from ray_tpu.ops import moe, ssm

TINY = nh.NemotronHConfig(
    vocab_size=256, hidden_size=64, num_hidden_layers=5,
    hybrid_override_pattern="MEM*E", num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, mamba_num_heads=4, mamba_head_dim=8,
    n_groups=2, ssm_state_size=16, chunk_size=8, moe_latent_size=32,
    moe_intermediate_size=48, moe_shared_expert_intermediate_size=64,
    n_routed_experts=16, num_experts_per_tok=3, experts_held=16,
    max_seq=128, dtype="float32")
BS = 8


@pytest.fixture(scope="module")
def tiny():
    return TINY, nh.init(jax.random.key(0), TINY)


def test_parameter_count_reproduces_the_models_name():
    """From the published config alone: 120.67 B parameters, 12.23 B a
    token (22 experts, the head counted once); and the cell's share."""
    cfg = nh.NemotronHConfig()
    assert [cfg.hybrid_override_pattern.count(c) for c in "ME*"] \
        == [40, 40, 8]
    assert round(cfg.layer_params("M", 0) / 1e3) == 109640
    assert round(cfg.layer_params("*", 0) / 1e3) == 35656
    assert round(cfg.layer_params("E", 0) / 1e3) == 54531
    assert cfg.layer_params("E", 1) - cfg.layer_params("E", 0) == 5505024
    assert round(cfg.num_params(512) / 1e7) == 12067
    assert round(cfg.num_params(22, embedding=False) / 1e7) == 1223
    assert cfg.hybrid_override_pattern[26:37] == "EMEMEMEMEM*"
    share = nh.NemotronHConfig(
        num_hidden_layers=11, hybrid_override_pattern="EMEMEMEMEM*",
        vocab_size=16384, experts_held=64)
    assert round(share.num_params() / 1e6) == 2752
    assert serving(share).state.slot_bytes == 5 * (4194304 + 61440)


def test_init_makes_what_num_params_counts(tiny):
    cfg, params = tiny
    assert sum(x.size for x in jax.tree_util.tree_leaves(params)) \
        == cfg.num_params()


@pytest.mark.parametrize("n,chunk,H,G", [
    (13, 8, 4, 2), (16, 8, 4, 2), (5, 8, 4, 2), (40, 16, 4, 2), (1, 8, 4, 2),
    # ONE group of 128 heads (models/granite_hybrid.py): wider than the
    # scan's block of heads, whose blocks then read one B and one C.
    (20, 8, 128, 1), (9, 8, 32, 2),
    # A block of 256 rows, longer than a lane tile.
    (300, 256, 32, 1), (256, 256, 4, 2)])
def test_chunked_scan_equals_the_token_recurrence(n, chunk, H, G):
    """From a NON-ZERO initial state, at span lengths that are no
    multiple of the chunk: padding rows have dt 0 and move nothing."""
    rng = np.random.default_rng(n)
    P, N = 8, 16
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    x, B, C, S0 = f(n, H, P), f(n, G, N), f(n, G, N), f(H, P, N)
    dt = jnp.asarray(rng.uniform(0.01, 0.5, (n, H)), jnp.float32)
    A = -jnp.asarray(rng.uniform(0.5, 4, (H,)), jnp.float32)
    y_want, S_want = ssm.ssm_recurrence(x, dt, A, B, C, S0)
    # The kernel, under the Pallas interpreter here.
    y, S = ssm.ssd_scan(x, dt, A, B, C, S0, chunk)
    assert np.abs(y - y_want).max() < 2e-5 * max(1, chunk // 32)
    assert np.abs(S - S_want).max() < 2e-5


def test_a_group_of_heads_cuts_into_the_kernels_blocks():
    assert ssm._head_block(128, 8) == ssm._head_block(128, 1) == 64
    assert ssm._head_block(4, 2) == 4
    assert ssm._scan_heads(16) == ssm._scan_heads(128) == 16
    assert ssm._scan_heads(2) == 2
    for H, G in ((96, 1), (192, 2)):        # neither whole groups nor a part
        with pytest.raises(ValueError, match="do not cut"):
            ssm._head_block(H, G)
    with pytest.raises(ValueError, match="does not cut"):
        ssm._scan_heads(24)


def _update_case(H, G, P, N, slots, lanes, seed=0):
    """A pool of 2 layers and ``ssm_update``'s operands for ``lanes``
    (slot 0: a padded lane)."""
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    b = len(lanes)
    pool = f(2, slots, H, P, N)
    decay = jnp.asarray(rng.uniform(0.5, 1, (b, H)), jnp.float32)
    return pool, jnp.asarray(lanes, jnp.int32), decay, f(b, H, P), \
        f(b, G, N), f(b, G, N)


@pytest.mark.parametrize("H,G,P,N,slots,lanes", [
    (4, 2, 8, 16, 6, (2, 0, 5, 0)), (128, 1, 8, 16, 6, (2, 0, 5, 0)),
    (128, 8, 8, 16, 6, (2, 0, 5, 0)), (128, 2, 8, 16, 6, (2, 0, 5, 0)),
    # The cells' own block: heads of 64 by a state of 128, 8 groups of
    # 16 (Nemotron-3) and ONE of 128 (Granite 4.0-H); and a lane alone.
    (128, 8, 64, 128, 4, (3, 0, 1)), (128, 1, 64, 128, 4, (3, 0, 1)),
    (128, 8, 64, 128, 4, (2,)), (4, 2, 8, 16, 6, (4,))],
    ids=["4_in_2", "128_in_1", "128_in_8", "128_in_2", "cell_8_groups",
         "cell_1_group", "cell_one_lane", "one_lane"])
def test_update_kernel_moves_the_lanes_slots_and_no_other(H, G, P, N, slots,
                                                          lanes):
    """Whole groups in a block of heads (4 in 2; 128 in 8, Nemotron-3's)
    and a group wider than a block (128 in 1, Granite 4.0-H's; in 2,
    a block a group), at the tests' small heads and at the cells'."""
    pool, at, decay, dtx, B, C = _update_case(H, G, P, N, slots, lanes)
    y, out = ssm.ssm_update(pool, 1, at, decay, dtx, B, C)
    y_want, want = ssm.ssm_update_reference(pool, 1, at, decay, dtx, B, C)
    live = np.flatnonzero(np.asarray(lanes))
    assert y.shape == y_want.shape and y.dtype == jnp.float32
    assert np.abs(y - y_want)[live].max() < 1e-5 * max(1, N // 16)
    for l in range(2):
        for s in range(1, slots):       # slot 0 is scratch
            assert np.abs(out[l, s] - want[l, s]).max() < 1e-5, (l, s)
    assert np.array_equal(out[0], pool[0])          # the other layer
    rest = sorted(set(range(1, slots)) - set(lanes))
    assert np.array_equal(out[1, rest], pool[1, rest])


@pytest.mark.parametrize("H,G,P,N", [(4, 2, 8, 16), (128, 8, 64, 128),
                                     (128, 1, 64, 128)])
def test_the_state_written_is_bit_for_bit_the_three_elementwise_steps(
        H, G, P, N):
    """``S <- decay S + (dt x) B^T`` is a product, a product and a sum
    an element, in that order, whatever the kernel does around them: a
    live lane's slot comes back BIT-EQUAL to the plain form's (compiled
    as the interpreter compiles the kernel: the same fused arithmetic).
    Only ``y`` is summed in another order, and holds to 1e-5 a term."""
    pool, at, decay, dtx, B, C = _update_case(H, G, P, N, 4, (3, 0, 1),
                                              seed=1)
    y, out = ssm.ssm_update(pool, 0, at, decay, dtx, B, C)
    y_want, want = jax.jit(ssm.ssm_update_reference, static_argnums=1)(
        pool, 0, at, decay, dtx, B, C)
    assert np.array_equal(out[0, [3, 1]], want[0, [3, 1]])
    assert np.abs(y - y_want)[[0, 2]].max() < 1e-5 * max(1, N // 16)


def test_not_gated_expert_through_the_grouped_product_equals_a_dense_loop():
    rng = np.random.default_rng(1)
    T, d, f, E, k = 24, 32, 48, 8, 3
    g = lambda *s: jnp.asarray(rng.normal(size=s) * 0.3, jnp.float32)
    x, w1, w2 = g(T, d), g(E, d, f), g(E, f, d)
    experts = jnp.asarray(np.stack([rng.permutation(E)[:k]
                                    for _ in range(T)]), jnp.int32)
    weights = jnp.asarray(rng.uniform(0.1, 1, (T, k)), jnp.float32)
    y, sizes = moe.routed_experts(x, experts, weights, w1, w2,
                                  activation="relu2")
    want = np.zeros((T, d), np.float32)
    for t in range(T):
        for j in range(k):
            e = int(experts[t, j])
            want[t] += float(weights[t, j]) * np.asarray(
                jnp.square(jax.nn.relu(x[t] @ w1[e])) @ w2[e])
    assert np.abs(np.asarray(y) - want).max() < 1e-4
    assert int(sizes.sum()) == T * k
    with pytest.raises(ValueError, match="activation"):
        moe.routed_experts(x, experts, weights, w1, w2, activation="gelu")


def test_the_shares_routed_parts_add_up_to_the_uncut_layer(tiny):
    """Eight shares of 2 experts: what each computes for its own
    experts, with the shared expert (which every chip computes alike)
    counted once, is the uncut layer; the reference is given the same
    share."""
    cfg, params = tiny
    p = params["layers"][1]
    h = jnp.asarray(np.random.default_rng(2).normal(size=(10, 64)),
                    jnp.float32)
    layer = jax.jit(nh._experts, static_argnums=(2, 3))
    whole, _ = layer(h, p, cfg, "chunk")
    shared = nh._relu2(h, p["s1"], p["s2"])
    parts = 0
    for i in range(8):
        share = dataclasses.replace(cfg, experts_held=2, first_expert=2 * i)
        # A routed expert's weights go by its GLOBAL id: a share's
        # parameters are slices of the whole model's.
        ps = dict(p, w1=p["w1"][2 * i:2 * i + 2], w2=p["w2"][2 * i:2 * i + 2])
        if i == 3:
            made = nh.init_layer(jax.random.key(0), share, 1)
            assert all(np.array_equal(made[k], ps[k]) for k in ps)
        out, sizes = layer(h, ps, share, "chunk")
        assert sizes.shape == (2,)
        with jax.default_matmul_precision("highest"):
            want = ref.experts(h, ref._f32(ps), share)
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


_CHUNK = jax.jit(nh.forward_prefill_chunk, static_argnums=(7,))
_STEP = jax.jit(nh.forward_step, static_argnames=("q", "cfg"))


@pytest.mark.parametrize("spans", [(16, 24, 3), (43,)])
def test_prefill_in_unequal_spans_then_decode_equals_the_reference(tiny,
                                                                   spans):
    """Logits, through keys, values and state: spans of unequal length
    (no multiple of the scan's chunk), the first from zeros WHATEVER the
    slot held, a span that reads one slot and writes another (a parked
    snapshot taken up), then one-token steps in place beside a padded
    lane."""
    cfg, params = tiny
    seq = np.random.default_rng(0).integers(0, 256, 50).tolist()
    want = np.asarray(ref.forward(params, seq, cfg))
    pools = [jnp.zeros((1, 32, BS, 32)), jnp.zeros((1, 32, BS, 32)),
             jnp.full((2, 4, 4, 8, 16), 7.0),      # the last tenant's
             jnp.full((2, 4, 3, cfg.conv_dim), 7.0)]
    table, upto, slot = list(range(1, 9)), 0, 2
    for i, c in enumerate(spans):
        dst = 3 if i == 1 else slot         # the second span moves slots
        row, pools = _chunk(cfg, params, seq, table, upto, c, slot, dst,
                            pools)
        upto, slot = upto + c, dst
        assert np.abs(row - want[upto - 1]).max() < 1e-5, (i, c)
    assert upto == 43
    for pos in range(43, 50):
        packed = pack_step(
            [[seq[pos]], [0]], [[pos], [0]],
            np.array([table + [0] * 8, [0] * 16]), [pos + 1, 1], [1, 1],
            [[table[pos // BS]], [0]], [[pos % BS], [0]],
            state_slots=[slot, 0])
        logits, ids, *pools = _STEP(params, packed, *pools, q=1, cfg=cfg)
        assert np.abs(np.asarray(logits[0, 0]) - want[pos]).max() < 1e-5
        assert ids.shape == (2 + len(nh.COUNTERS), 1)
        assert int(ids[0, 0]) == int(want[pos].argmax())


def test_the_seam_says_what_a_sequence_keeps():
    s = serving(TINY)
    assert s.state.layers == (0, 2)
    assert [shape for shape, _ in s.state.parts] \
        == [(4, 8, 16), (3, TINY.conv_dim)]
    assert s.state.parts[0][1] == jnp.float32       # S, whatever the dtype
    assert s.kinds[0].layers == (3,) and s.kinds[0].rows == (32, 32)
    assert s.counters == nh.COUNTERS
    for other in ("gpt", "laguna", "kimi_k2"):
        import importlib

        mod = importlib.import_module(f"ray_tpu.models.{other}")
        cfg = next(v for k, v in vars(mod).items()
                   if k.endswith("Config") and isinstance(v, type))
        assert "state" not in vars(cfg)
    # The packed array's state column sits behind the head, before the
    # tables; without a state the layout is what it was.
    assert step_columns(1) == step_columns(1, 0, False)
    with_state = step_columns(1, 0, True)
    assert with_state.state_slot == with_state.head
    assert with_state.table == step_columns(1).table + 1
    # A chunk's table carries its extras behind ctx_len and last.
    t = pack_span(np.arange(4), [7, 8], 32, 15, 5, 6)
    got = unpack_span(jnp.asarray(t), 2 * BS, BS, extra=2)
    assert [int(x) for x in got[2:]] == [32, 15, 5, 6]
    assert len(unpack_span(jnp.asarray(t[:-2]), 2 * BS, BS)) == 4


def test_decode_scores_one_row_a_lane():
    with pytest.raises(ValueError, match="q must be 1"):
        nh.forward_step(None, None, None, None, None, None, q=2, cfg=TINY)


@pytest.mark.parametrize("field,value", [
    ("mlp_hidden_act", "silu"), ("n_group", 2), ("use_conv_bias", False),
    ("hybrid_override_pattern", "MEM"), ("first_expert", 9)])
def test_config_refuses_what_is_not_built(field, value):
    with pytest.raises(ValueError):
        dataclasses.replace(TINY, **{field: value})
