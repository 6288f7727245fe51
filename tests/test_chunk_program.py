"""A prefill chunk is one program (PR 33): ``Serving.chunk`` attends,
writes its own span into the pools it is donated and hands back one row
and its argmax. Held here on the CPU, through the engine's own
scheduling, against what a chunk was before: the same layers and the
head on every row as one program, then ``write_prefill`` a kind of
layer as another.

Every chunk the engine dispatches in a scenario is run both ways from
the same inputs (the engine's own arrays, the pools copied before they
are donated) and compared: both pools bit for bit everywhere, zeroed
tails and untouched blocks included (the pools start as noise); the
row against ``logits[0, c - 1]`` to rounding (the head on one row is a
matrix-vector product, on every row a matrix-matrix one: the CPU sums
them in another order, 1e-7 apart in float32 and a last place of
bfloat16); the id against the argmax of the row it came with, exactly.
"""

import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm.engine import LLMEngine
from ray_tpu.llm.kv_cache import PagedKVCache
from ray_tpu.models import gpt, laguna, layers, unpack_span

BS = 8
GPT_F32 = gpt.GPTConfig(vocab_size=128, max_seq=64, d_model=64, n_layer=2,
                        n_head=4, dtype=jnp.float32)
GPT_BF16 = gpt.GPTConfig(vocab_size=128, max_seq=64, d_model=64, n_layer=2,
                         n_head=4, dtype=jnp.bfloat16)
LAGUNA = laguna.LagunaConfig(
    vocab_size=256, hidden_size=64, intermediate_size=128,
    num_hidden_layers=5, num_attention_heads_per_layer=(4, 8, 8, 8, 4),
    num_key_value_heads=2, head_dim=16, num_experts=8,
    num_experts_per_tok=2, moe_intermediate_size=32,
    shared_expert_intermediate_size=32, sliding_window=24,
    layer_types=("full_attention", "sliding_attention",
                 "sliding_attention", "sliding_attention",
                 "full_attention"),
    mlp_layer_types=("dense", "sparse", "sparse", "sparse", "sparse"),
    max_seq=160, dtype="float32",
    rope_full=dict(rope_theta=500000, rope_type="yarn", factor=64,
                   original_max_position_embeddings=32, beta_slow=1,
                   beta_fast=64, attention_factor=1.4158883083359672,
                   partial_rotary_factor=0.5))
# Logits here are under 1 in magnitude: a few float32 roundings, and
# one place of bfloat16 in [0.5, 1).
ROW_TOL = {"float32": 2e-6, "bfloat16": 2 ** -8}
CONFIGS = {"gpt_f32": GPT_F32, "gpt_bf16": GPT_BF16, "laguna": LAGUNA}


@pytest.fixture(scope="module")
def params():
    made = {}

    def get(name):
        if name not in made:
            cfg = CONFIGS[name]
            mod = importlib.import_module(type(cfg).__module__)
            made[name] = mod.init(jax.random.key(7), cfg)
        return made[name]

    return get


def _layers_then_head(cfg):
    """The chunk as it was, from the model's own parts: one program,
    every row through the head, the pools read only."""
    mod = importlib.import_module(type(cfg).__module__)
    # GPT-2's tied head is its own; Laguna's is models/layers.py's.
    head = functools.partial(gpt._head, cfg=cfg) if mod is gpt else \
        functools.partial(layers.head, eps=cfg.rms_norm_eps)

    @jax.jit
    def run(params, *args):
        x, *kv = mod._chunk_layers(params, *args, cfg=cfg)
        return (head(params, x), *kv)

    return run


def _equal(a, b):
    return np.array_equal(np.asarray(a, np.float32),
                          np.asarray(b, np.float32))


def _noise(eng, seed=5):
    """Fill the pools with noise, so a write that strays, or a tail
    that is not zeroed, shows."""
    rng = np.random.default_rng(seed)
    for kv in filter(None, (eng.kv, eng.kv_window)):
        kv.k, kv.v = (jnp.asarray(rng.standard_normal(kv.k.shape),
                                  kv.k.dtype) for _ in range(2))


def _shadow(eng):
    """Run every chunk the engine dispatches both ways and compare;
    returns the list of what each chunk was: a dict of ``upto``, ``c``,
    ``n``, ``table`` (slots), and for a window kind ``lead`` (blocks
    written to the scratch block) and ``regranted`` (blocks the chunk
    both reads as context and writes)."""
    cfg, real = eng.cfg, eng._prefill_chunk
    before = _layers_then_head(cfg)
    seen = []

    def pool_of(kind, k, v):
        kv = PagedKVCache(cfg, num_blocks=k.shape[1], block_size=BS,
                          kind=kind)
        kv.k, kv.v = k, v
        return kv

    def on_chunk(params, toks, k, v, table, *window):
        n = toks.shape[1]
        bt, dest, upto, last = (np.asarray(a)
                                for a in unpack_span(table, n, BS))
        c = int(last) + 1
        pos = np.minimum(int(upto) + np.arange(n, dtype=np.int32),
                         eng.model.max_seq - 1)
        was = {"upto": int(upto), "c": c, "n": n, "table": bt.size}
        copies = [jnp.array(a, copy=True) for a in (k, v) + window[:2]]
        extra, wdest = (), None
        if window:
            win = window[2]
            nbw = win.size - 1 - n // BS
            extra = (copies[2], copies[3], win[:nbw], np.int32(win[nbw]))
            wdest = win[nbw + 1:]
            lead = int(np.argmax(wdest != 0))
            assert (wdest[lead:] != 0).all() and (wdest[:lead] == 0).all()
            was["lead"] = lead
            was["regranted"] = sorted(
                set(wdest[lead:].tolist()) & set(win[:nbw].tolist()) - {0})
        logits, k_new, v_new, *win_new = before(
            params, toks, pos, copies[0], copies[1], bt, np.int32(upto),
            *extra)
        full = pool_of(0, copies[0], copies[1])
        full.write_prefill(k_new[:, 0, :c], v_new[:, 0, :c], dest.tolist())
        want = [full.k, full.v]
        if window:
            side = pool_of(1, copies[2], copies[3])
            skip = was["lead"] * BS
            side.write_prefill(win_new[0][:, 0, skip:c],
                               win_new[1][:, 0, skip:c],
                               wdest[was["lead"]:].tolist())
            want += [side.k, side.v]
        out = real(params, toks, k, v, table, *window)
        row, tok, *pools = out
        for i, (got, exp) in enumerate(zip(pools, want)):
            if i >= 2 and was["lead"]:
                # Slid-out leading blocks land in the scratch block,
                # which the parent never wrote: everything but it.
                got, exp = got[:, 1:], exp[:, 1:]
            assert _equal(got, exp), (was, i)
        gap = np.abs(np.asarray(row, np.float32)
                     - np.asarray(logits[0, c - 1], np.float32)).max()
        assert gap <= ROW_TOL[row.dtype.name], (was, gap)
        assert int(tok) == int(np.argmax(np.asarray(row, np.float32))), was
        seen.append(was)
        return out

    eng._prefill_chunk = on_chunk
    return seen


def _drain(eng):
    while eng.step():
        pass


def _prompt(seed, n, vocab=120):
    return np.random.default_rng(seed).integers(1, vocab, n).tolist()


# case -> (prompt tokens, chunk budget, what must have been dispatched)
CASES = {
    # a cold prompt in one span: the empty table, no padding
    "cold_prompt": (24, None, lambda s: s == [
        {"upto": 0, "c": 24, "n": 24, "table": 0}]),
    # whole blocks behind resident context, under the full-length table
    "behind_context": (32, 16, lambda s: s[1] == {
        "upto": 16, "c": 16, "n": 16, "table": 64 // BS}),
    # the last chunk ragged: 5 real rows of 8, the tail written as zeros
    "ragged_last_chunk": (37, 16, lambda s: s[-1] == {
        "upto": 32, "c": 5, "n": 8, "table": 64 // BS}),
}


@pytest.mark.parametrize("case", list(CASES))
@pytest.mark.parametrize("name", ["gpt_f32", "gpt_bf16"])
def test_gpt_chunk_program_equals_layers_then_write_prefill(params, name,
                                                            case):
    tokens, budget, expect = CASES[case]
    eng = LLMEngine(params(name), CONFIGS[name], num_blocks=32,
                    block_size=BS, max_batch=2,
                    prefill_chunk_tokens=budget)
    _noise(eng)
    seen = _shadow(eng)
    eng.add_request(_prompt(1, tokens), max_tokens=3)
    _drain(eng)
    assert expect(seen), seen


WINDOW_CASES = {
    "cold_prompt": (19, None, lambda s: s[0]["table"] == 0
                    and s[0]["c"] == 19 and s[0]["lead"] == 0),
    "behind_context": (32, 16, lambda s: s[1]["upto"] == 16
                       and s[1]["c"] == s[1]["n"] == 16),
    "ragged_last_chunk": (37, 16, lambda s: s[-1]["c"] == 5
                          and s[-1]["n"] == 8),
    # 48 tokens in one span with a window of 24: the span's first two
    # blocks are out of the window the next query keeps, and land in
    # the scratch block 0.
    "leading_blocks_slid_out": (70, 48, lambda s: s[0]["lead"] == 2
                                and s[0]["c"] == 48),
    # chunks of 16 past the window: the slide frees blocks that the
    # grant hands straight back, so a chunk writes a block it reads.
    "freed_block_granted_again": (70, 16, lambda s: any(
        x["regranted"] for x in s)),
}


@pytest.mark.parametrize("case", list(WINDOW_CASES))
def test_laguna_chunk_program_equals_layers_then_write_prefill(params,
                                                               case):
    """Both kinds of pool. Where a block freed by the slide is granted
    again inside the same chunk the program must read every layer's
    context before it writes: the comparison reads the pools as they
    stood before the dispatch, so a write that came first would show
    in the row and in the blocks written."""
    tokens, budget, expect = WINDOW_CASES[case]
    eng = LLMEngine(params("laguna"), LAGUNA, num_blocks=64,
                    block_size=BS, max_batch=2,
                    prefill_chunk_tokens=budget)
    _noise(eng)
    seen = _shadow(eng)
    eng.add_request(_prompt(2, tokens, 256), max_tokens=3)
    _drain(eng)
    assert expect(seen), seen


@pytest.mark.parametrize("name", ["gpt_f32", "laguna"])
def test_a_chunk_length_never_seen_compiles_exactly_one_program(params,
                                                                name):
    """The standing witness that ``llm.prefill.device`` dispatches once:
    after a warm-up, a prompt whose chunk has a length no program was
    compiled for costs exactly ONE backend compilation, the chunk
    program's. (Before PR 33 it cost the program and a dozen small
    ones: the slices, pads, reshapes, conversions and the scatter of
    ``write_prefill``, each dispatched eagerly with the device idle.)
    Greedy and sampled alike: the id or the row is fetched, not
    computed."""
    from jax import monitoring

    # A pool of a size no other test in the process uses: the programs
    # are shared a configuration (llm/engine.py ``_jit_programs``), and
    # a file that ran before this one in the same worker may have
    # compiled a five-block chunk at the usual 64 blocks already.
    eng = LLMEngine(params(name), CONFIGS[name], num_blocks=61,
                    block_size=BS, max_batch=2, prefill_chunk_tokens=None)
    # Warm-up: the decode program, a cold prompt of 2 blocks, a prompt
    # behind a cached block (the full-length table), greedy and sampled.
    eng.add_request(_prompt(3, 13), max_tokens=3)
    eng.add_request(_prompt(3, 13)[:8] + _prompt(4, 7), max_tokens=3,
                    temperature=0.7, seed=1)
    _drain(eng)
    compiled = []

    def listener(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiled.append(event)

    monitoring.register_event_duration_secs_listener(listener)
    try:
        # 5 blocks cold, then 4 blocks behind the cached first block:
        # two lengths never seen, one program each.
        eng.add_request(_prompt(5, 37), max_tokens=2)
        _drain(eng)
        assert len(compiled) == 1, compiled
        eng.add_request(_prompt(5, 37)[:8] + _prompt(6, 29), max_tokens=2,
                        temperature=0.7, seed=2)
        _drain(eng)
        assert len(compiled) == 2, compiled
        # and a length seen before compiles nothing
        eng.add_request(_prompt(7, 37), max_tokens=2)
        _drain(eng)
        assert len(compiled) == 2, compiled
    finally:
        monitoring.unregister_event_duration_listener(listener)


# -- several spans in ONE program: the latent family (PR 67) ------------------

def _latent(name):
    import test_kimi_k2
    import test_xing4

    return {"kimi": test_kimi_k2.TINY, "xing": test_xing4.TINY}[name]


@pytest.fixture(scope="module")
def latent_params():
    made = {}

    def get(name):
        if name not in made:
            cfg = _latent(name)
            mod = importlib.import_module(type(cfg).__module__)
            made[name] = mod.init(jax.random.key(0), cfg)
        return made[name]

    return get


def _spans_program(cfg):
    """(the family's chunk program as the engine holds it, a function
    that runs ``spans`` through it as ONE call). A span is (tokens, the
    sequence's block table, tokens resident before it)."""
    from ray_tpu.llm.engine import _jit_programs
    from ray_tpu.models import pack_spans, serving

    model = serving(cfg)
    program = _jit_programs(cfg)[1]

    def run(params, pool, spans):
        toks, packed = [], []
        for tokens, table, upto in spans:
            c = len(tokens)
            pad = -c % BS
            toks += list(tokens) + [0] * pad
            packed.append((table[:-(-upto // BS)],
                           table[upto // BS:(upto + c + pad) // BS], upto, c))
        rows, ids, pool = program(
            params, np.asarray([toks], np.int32), jnp.array(pool, copy=True),
            pack_spans(packed, model.max_seq // BS, BS, model.chunk_spans))
        return (np.asarray(rows, np.float32)[:len(spans)],
                np.asarray(ids)[:len(spans)], pool)

    return program, run


def _three_sequences(cfg, params, run, seed=11):
    """Three prompts with their own block tables in a pool of noise:
    ``a`` has 16 tokens resident, ``b`` none, ``c`` 24 (written here, a
    program each). Returns (pool, the three next spans): 16 rows behind
    16, 24 rows from the start, 13 rows (padded to 16) behind 24."""
    from ray_tpu.models import serving

    rng = np.random.default_rng(seed)
    seqs = [rng.integers(0, 256, n).tolist() for n in (40, 24, 37)]
    tables = [list(range(1 + 8 * i, 9 + 8 * i)) for i in range(3)]
    width = serving(cfg).kinds[0].rows[0]
    pool = jnp.asarray(rng.standard_normal(
        (cfg.num_hidden_layers, 32, BS, width)), jnp.float32)
    for seq, table, upto in ((seqs[0], tables[0], 16),
                             (seqs[2], tables[2], 24)):
        *_, pool = run(params, pool, [(seq[:upto], table, 0)])
    return pool, [(seqs[0][16:32], tables[0], 16),
                  (seqs[1], tables[1], 0),
                  (seqs[2][24:], tables[2], 24)]


@pytest.mark.parametrize("name", ["kimi", "xing"])
def test_three_spans_in_one_program_equal_three_programs(latent_params,
                                                         name):
    """``[a | b | c]`` as ONE program against the three spans run one
    after another, from the same pool of noise: the same three ids;
    each span's row of logits, and the latent rows it left in the pool,
    to float32 rounding (measured 1.3e-6 on values of a few units: the
    CPU's products sum a row in an order that follows the program's
    row count, 56 here and 16 or 24 there, and a row's attention sums
    its keys in the order they lie in the program; a key that is not a
    row's own would move its logits by 1e-1); the zeroed tails of
    ragged spans and every block no span names, bit for bit. The
    middle span starts its prompt and the others lie behind context;
    the last is ragged; rows 16..39 lie across the kernel's query
    blocks at this size."""
    cfg, params = _latent(name), latent_params(name)
    _, run = _spans_program(cfg)
    pool, spans = _three_sequences(cfg, params, run)
    rows, ids, packed = run(params, pool, spans)
    want_rows, want_ids, serial = [], [], pool
    for span in spans:
        r, i, serial = run(params, serial, [span])
        want_rows.append(r[0])
        want_ids.append(i[0])
    assert ids.tolist() == want_ids
    assert np.abs(rows - np.stack(want_rows)).max() < ROW_TOL["float32"]
    got, want = np.asarray(packed), np.asarray(serial)
    assert np.abs(got - want).max() < 4e-6
    untouched = np.setdiff1d(np.arange(32), [1, 2, 3, 4, 9, 10, 11, 17, 18,
                                             19, 20, 21])
    assert np.array_equal(got[:, untouched], np.asarray(pool)[:, untouched])
    # the ragged span's 13 rows end in block 21: rows 5.. of it are zeros
    assert not got[:, 21, 5:].any() and got[:, 21, :5].any(-1).all()


@pytest.mark.parametrize("name", ["kimi", "xing"])
def test_a_length_never_seen_compiles_one_program_however_it_is_divided(
        latent_params, name):
    """The packed program's shapes are its rows' and its table's: 72
    rows behind context compile ONCE, as three spans, as two, as one,
    and 72 rows that are one span from a prompt's start (no table) once
    more. What the benchmark's warm-up compiles with single requests of
    every length is what a window of packed steps runs."""
    from jax import monitoring

    cfg, params = _latent(name), latent_params(name)
    _, run = _spans_program(cfg)
    pool, spans = _three_sequences(cfg, params, run)
    compiled = []

    def listener(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiled.append(event)

    seq = np.random.default_rng(12).integers(0, 256, 88).tolist()
    table = list(range(24, 32)) + [5, 6, 7]
    monitoring.register_event_duration_secs_listener(listener)
    try:
        run(params, pool, spans + [(seq[:16], table, 0)])   # 16+24+16+16
        assert len(compiled) == 1, compiled
        run(params, pool, [(seq[:56], table, 0), spans[0]])     # 56 + 16
        *_, held = run(params, pool, [(seq[:16], table, 0)])
        compiled.clear()                # (16 rows with no table: seen)
        run(params, held, [(seq[16:], table, 16)])      # 72 behind 16
        assert compiled == []
        run(params, pool, [(seq[:72], table, 0)])       # 72, no table
        assert len(compiled) == 1, compiled
    finally:
        monitoring.unregister_event_duration_listener(listener)
