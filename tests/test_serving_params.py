"""The engine serves from parameters cast once (PR 61): the seam's
``Serving.at_rest`` makes, when an engine is built, the tree its two
programs READ. GPT's checkpoint leaves are float32 and its programs
round each to ``cfg.dtype`` in front of its product; rounded once, at
rest, the same products take the same bits. Held here by EQUALITY, on
the CPU at tiny widths: nothing below has a tolerance.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from ray_tpu.llm import LLMEngine  # noqa: E402
from ray_tpu.llm.engine import _jit_programs  # noqa: E402
from ray_tpu.models import (gpt, pack_span, pack_step,  # noqa: E402
                            served_params, serving)

BF16 = gpt.GPTConfig(vocab_size=128, max_seq=64, d_model=64, n_layer=2,
                     n_head=4, dtype=jnp.bfloat16)
F32 = gpt.GPTConfig(vocab_size=128, max_seq=64, d_model=64, n_layer=2,
                    n_head=4, dtype=jnp.float32)
BS, NB = 8, 16

leaves = jax.tree_util.tree_leaves


@pytest.fixture(scope="module")
def given():
    """A checkpoint's tree: float32, every leaf."""
    tree = gpt.init(jax.random.key(3), BF16)
    assert {x.dtype for x in leaves(tree)} == {jnp.dtype(jnp.float32)}
    return tree


@pytest.fixture(scope="module")
def served(given):
    return served_params(given, BF16)


def _named(tree):
    return {jax.tree_util.keystr(path): x for path, x in
            jax.tree_util.tree_leaves_with_path(tree)}


def _same(got, want):
    """Every output equal, bit for bit (a bfloat16 array is compared as
    the float32 values it holds: the widening is exact)."""
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        assert np.array_equal(np.asarray(g, np.float32),
                              np.asarray(w, np.float32))


def _pools(seed):
    rng = np.random.default_rng(seed)
    draw = lambda: jnp.asarray(rng.standard_normal(
        (BF16.n_layer, NB, BS, BF16.kv_heads * BF16.head_dim)), BF16.dtype)
    return draw(), draw()


def test_the_served_tree_keeps_only_the_norm_scales_in_float32(given,
                                                              served):
    """Eight of GPT's eleven leaves are rounded to ``cfg.dtype``, to the
    values ``astype`` gives in the program; the three LayerNorm scales,
    which ``_layernorm`` multiplies in float32, hold the given values."""
    got, want = _named(served), _named(given)
    assert got.keys() == want.keys() and len(got) == 11
    f32 = sorted(name for name, x in got.items() if x.dtype == jnp.float32)
    assert f32 == ["['blocks']['ln1']", "['blocks']['ln2']", "['ln_f']"]
    for name, x in got.items():
        rounded = want[name] if name in f32 \
            else want[name].astype(jnp.bfloat16)
        assert x.dtype == rounded.dtype and x.shape == rounded.shape
        assert np.array_equal(np.asarray(x, np.float32),
                              np.asarray(rounded, np.float32))


@pytest.mark.parametrize("Q", [1, 3], ids=["decode", "verify"])
def test_decode_step_is_bit_equal_from_either_tree(given, served, Q):
    """``forward_step`` through the engine's own jitted program, one row
    a lane and three: logits, ids (the counter's row too) and both
    written pools are EQUAL from the float32 tree and the served one."""
    step = _jit_programs(BF16)[0]
    B, max_nb = 4, BF16.max_seq // BS
    rng = np.random.default_rng(5)
    tables = np.zeros((B, max_nb), np.int32)
    tables[:, :2] = 1 + np.arange(2 * B).reshape(B, 2)
    pos = np.array([3, 6, 7, 10], np.int32)[:, None] + np.arange(
        Q, dtype=np.int32)                              # cross a block
    packed = pack_step(
        rng.integers(0, BF16.vocab_size, (B, Q), dtype=np.int32), pos,
        tables, pos[:, -1] + 1, np.full((B,), Q, np.int32),
        np.take_along_axis(tables, pos // BS, axis=1), pos % BS)
    k_pool, v_pool = _pools(11)
    want = step(given, packed, k_pool + 0, v_pool + 0, q=Q)
    got = step(served, packed, k_pool + 0, v_pool + 0, q=Q)
    _same(got, want)
    assert not np.array_equal(np.asarray(got[2], np.float32),
                              np.asarray(k_pool, np.float32))  # it wrote


@pytest.mark.parametrize("ctx", [0, 20], ids=["cold_prompt",
                                              "behind_context"])
def test_prefill_chunk_is_bit_equal_from_either_tree(given, served, ctx):
    """``forward_prefill_chunk`` through the engine's own jitted
    program, a span from a prompt's start and one behind 20 resident
    tokens: the row, its id and both written pools are EQUAL."""
    chunk = _jit_programs(BF16)[1]
    n, max_nb = 16, BF16.max_seq // BS
    rng = np.random.default_rng(7)
    table = np.zeros((max_nb if ctx else 0,), np.int32)
    table[:3] = [4, 9, 2][:table.size]
    span = pack_span(table, np.array([11, 12], np.int32), ctx, n - 3)
    tokens = rng.integers(0, BF16.vocab_size, (1, n), dtype=np.int32)
    k_pool, v_pool = _pools(13)
    want = chunk(given, tokens, k_pool + 0, v_pool + 0, span)
    got = chunk(served, tokens, k_pool + 0, v_pool + 0, span)
    _same(got, want)
    assert not np.array_equal(np.asarray(got[2], np.float32),
                              np.asarray(k_pool, np.float32))


REQS = [dict(prompt=[1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11], max_tokens=12),
        dict(prompt=[9, 8, 7], max_tokens=9),
        dict(prompt=[20, 21] * 9, max_tokens=6, seed=4, temperature=0.8)]


def _tokens(params, cfg=BF16, **kw):
    eng = LLMEngine(params, cfg, num_blocks=NB, block_size=BS, max_batch=4,
                    prefill_chunk_tokens=8, **kw)
    handles = [eng.add_request(**r) for r in REQS]
    for _ in range(200):
        if not eng._waiting and not eng._active:
            return eng, [h.output for h in handles]
        eng.step()
    raise AssertionError("the engine does not drain")


def test_engine_emits_the_same_tokens_and_keeps_no_float32_weight(given,
                                                                  served):
    """An engine built from float32 leaves casts eight of eleven, holds
    the served tree alone (half the given bytes, but for the scales)
    and emits, greedy and sampled, the tokens of an engine built from
    the already-cast tree, which casts nothing and keeps what it got."""
    eng, got = _tokens(given)
    ready, want = _tokens(served)
    assert got == want and all(len(out) == r["max_tokens"]
                               for out, r in zip(got, REQS))
    stats, kept = eng.stats(), _named(eng.params)
    assert stats["param_leaves_cast"] == 8
    assert sorted(n for n, x in kept.items() if x.dtype == jnp.float32) \
        == ["['blocks']['ln1']", "['blocks']['ln2']", "['ln_f']"]
    assert stats["param_bytes_at_rest"] == sum(
        x.nbytes if "ln" in name else x.nbytes // 2
        for name, x in _named(given).items())
    assert ready.stats()["param_leaves_cast"] == 0
    assert all(a is b for a, b in zip(leaves(ready.params), leaves(served)))


def test_a_draft_proposer_is_served_from_the_cast_tree_too(given):
    """``llm/spec.py`` keeps a draft model's parameters through the same
    function: a float32 draft tree given beside the target's is held
    cast, the target's own tree (no ``draft_params``) is the engine's,
    and the tokens are the plain engine's either way."""
    _, plain = _tokens(given)
    eng, own = _tokens(given, speculative={"mode": "draft", "k": 2})
    assert all(a is b for a, b in zip(
        leaves(eng._spec.proposer.params), leaves(eng.params)))
    draft = gpt.init(jax.random.key(9), BF16)
    eng, other = _tokens(given, speculative={
        "mode": "draft", "k": 2, "draft_params": draft, "draft_cfg": BF16})
    assert sum(x.dtype == jnp.float32 for x in
               leaves(eng._spec.proposer.params)) == 3
    assert own == plain and other == plain


def test_each_leaf_keeps_its_sharding(given):
    """A tree laid over a mesh is cast in place: every leaf of the
    served tree has the given leaf's sharding, the engine's too; a tree
    that was never committed to a device stays uncommitted (the pools a
    step returns would otherwise commit with it)."""
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "tp"))
    specs = {"wte": P("tp", None), "wpe": P(), "ln_f": P(),
             "blocks": {"ln1": P(), "ln2": P(),
                        "wq": P(None, None, "tp", None),
                        "wk": P(None, None, "tp", None),
                        "wv": P(None, None, "tp", None),
                        "wo": P(None, "tp", None, None),
                        "wi": P(None, None, "tp"),
                        "wm": P(None, "tp", None)}}
    laid = jax.tree_util.tree_map(
        lambda x, spec: jax.device_put(x, NamedSharding(mesh, spec)),
        given, specs)
    got = served_params(laid, BF16)
    for name, x in _named(got).items():
        assert x.sharding == _named(laid)[name].sharding, name
    assert _named(got)["['blocks']['wi']"].dtype == jnp.bfloat16
    assert not any(x.committed for x in leaves(served_params(given, BF16)))


def test_with_float32_activations_the_served_tree_is_the_given_one():
    """``cfg.dtype`` float32: nothing to round, no program is built, the
    engine keeps the tree it was handed."""
    tree = gpt.init(jax.random.key(3), F32)
    assert served_params(tree, F32) is tree
    eng = LLMEngine(tree, F32, num_blocks=NB, block_size=BS, max_batch=2)
    assert eng.params is tree and eng.stats()["param_leaves_cast"] == 0
    assert serving(F32).cost["param_bytes"] == 4
    assert serving(BF16).cost["param_bytes"] == 2


@pytest.mark.parametrize("family", ["laguna", "kimi_k2", "nemotron_h"])
def test_the_other_families_are_served_as_given(family):
    """Laguna, Kimi and Nemotron make ``cfg.dtype`` leaves in ``init``:
    their seams name no ``at_rest``, ``served_params`` hands their trees
    back themselves and an engine counts no leaf cast."""
    import importlib

    cfg = importlib.import_module(f"test_{family}").TINY
    model = serving(cfg)
    assert model.at_rest is None
    tree = model.init(jax.random.key(0), cfg)
    assert served_params(tree, cfg) is tree
    eng = LLMEngine(tree, cfg, num_blocks=32, block_size=16, max_batch=2)
    assert eng.params is tree
    stats = eng.stats()
    assert stats["param_leaves_cast"] == 0
    assert stats["param_bytes_at_rest"] == sum(x.nbytes for x in
                                               leaves(tree))
