"""The main path's kernels compile for the chip, at real widths.

The TPU compiler is installed here and compiles for a chip that is
described, not attached (/opt/skills/guides/on-chip-measurement §2), so
these tests refuse what the chip's compiler would refuse — a misaligned
tile, too much VMEM, a kernel that cannot be partitioned over a mesh —
without chip time. Nothing runs: a pass here is a compiler verdict, not
a chip result.

The topology is described inside a module-scoped fixture and only there:
one process at a time may load libtpu, so under xdist the call must not
happen at import or collection, and these tests stay in this one file.
"""

import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from ray_tpu.models import gpt, step_columns, window_table_len
from ray_tpu.ops.pallas.flash import flash_attention_pallas
from ray_tpu.ops.pallas.paged_fetch import paged_attention_stored
from ray_tpu.parallel import MeshSpec

# GPT-2-small serving widths: 12 KV heads x head_dim 64, block 16.
HKV, HD, BS, NB = 12, 64, 16, 4096
MAX_NB = 1024 // BS
# The chat cell's deployment: 64 lanes over 2,560 blocks.
CELL_B, CELL_NB = 64, 2560


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no libtpu / topology here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def as_tpu(monkeypatch):
    """The program picks kernel vs reference and compiled vs interpreted
    from jax.default_backend(), which here still says cpu: steer it in
    the test, not through an option of the program."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


def test_flash_forward_and_backward_compile(one_chip):
    """The bench shape: 24 rows x 12 heads x seq 1024 x head_dim 64,
    bf16, block 512, heads-major."""
    q = jax.ShapeDtypeStruct((24, 12, 1024, 64), jnp.bfloat16,
                             sharding=one_chip)
    attn = functools.partial(flash_attention_pallas, causal=True,
                             block_q=512, block_k=512, interpret=False,
                             layout="bhsd")
    fwd = _compile(attn, q, q, q)
    assert "tpu_custom_call" in fwd.as_text()
    bwd = _compile(
        jax.grad(lambda q, k, v: attn(q, k, v).astype(jnp.float32).sum(),
                 argnums=(0, 1, 2)), q, q, q)
    assert "tpu_custom_call" in bwd.as_text()


@pytest.mark.parametrize("layer", ["static_layer", "traced_layer"])
@pytest.mark.parametrize("q_len", [1, 5], ids=["decode", "verify_q5"])
@pytest.mark.parametrize("batch, hkv, group, hd, nb, max_nb", [
    (32, HKV, 1, HD, NB, MAX_NB),
    (64, HKV, 1, HD, 2560, MAX_NB),     # gpt2s-serve-chat's own shapes
    (16, 8, 8, 128, 2048, 128),         # head_dim 128, group 8
], ids=["b32_4096_blocks", "chat_cell", "hd128_group8"])
def test_paged_kernel_compiles(one_chip, layer, q_len, batch, hkv, group, hd,
                               nb, max_nb):
    """The kernel alone on a stack of 12 layers as stored, the layer a
    Python int or an operand (a model whose layers run under one scan):
    GPT-2's widths, a head half a lane tile, and a grouped head of 128."""
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    pool = S((12, nb, BS, hkv * hd), jnp.bfloat16)
    lanes = S((batch,), jnp.int32)

    def attend(q, k, v, tables, lens, qlens, starts, at):
        return paged_attention_stored(
            q, k, v, 7 if layer == "static_layer" else at, tables, lens,
            qlens, starts, name="paged_decode", interpret=False)

    c = _compile(attend, S((batch, q_len, hkv, group, hd), jnp.bfloat16),
                 pool, pool, S((batch, max_nb), jnp.int32), lanes, lanes,
                 lanes, S((), jnp.int32))
    (call,) = [line for line in c.as_text().splitlines()
               if "tpu_custom_call" in line]
    assert "%paged_decode" in call.split("=")[0]
    # (layer,) tables, lens, q lens, starts, run flags; q; K and V once.
    assert len(call.split("custom-call(")[1].split(")")[0].split(", ")) \
        == 8 + (layer == "traced_layer")


def test_flash_compiles_under_a_four_device_mesh(topo, as_tpu):
    """A Mosaic kernel cannot be partitioned automatically: without the
    shard_map in models/gpt.py this raises NotImplementedError. Full
    GPT-2-small widths, depth cut to 2, over MeshSpec.auto(4) (fsdp 4),
    forward and backward."""
    cfg = dataclasses.replace(gpt.GPT2_SMALL, n_layer=2, remat=True,
                              use_flash=True)
    mesh = MeshSpec.auto(4).build(list(topo.devices))
    params = jax.tree_util.tree_map(
        lambda leaf, spec: jax.ShapeDtypeStruct(
            leaf.shape, leaf.dtype, sharding=NamedSharding(mesh, spec)),
        jax.eval_shape(lambda: gpt.init(jax.random.key(0), cfg)),
        gpt.params_pspecs(cfg))
    tokens = jax.ShapeDtypeStruct(
        (8, cfg.max_seq), jnp.int32,
        sharding=NamedSharding(mesh, P(("dp", "fsdp"))))
    c = _compile(jax.grad(lambda p, t: gpt.loss_fn(p, t, cfg, mesh)),
                 params, tokens)
    text = c.as_text()
    assert "tpu_custom_call" in text
    assert "all-gather" in text or "all-reduce" in text


def test_whole_decode_step_compiles_with_the_kernel(one_chip, as_tpu):
    """forward_step at max_batch 32, one row a lane: the kernel must be
    in the program, compiled, not interpreted."""
    cfg = gpt.GPT2_SMALL
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    params = jax.tree_util.tree_map(
        lambda leaf: S(leaf.shape, leaf.dtype),
        jax.eval_shape(lambda: gpt.init(jax.random.key(0), cfg)))
    pool = S((cfg.n_layer, NB, BS, HKV * HD), jnp.bfloat16)
    B, i32 = 32, jnp.int32
    step = jax.jit(functools.partial(gpt.forward_step, q=1, cfg=cfg),
                   donate_argnums=(2, 3))
    c = step.lower(params, S((B, step_columns(1).table + MAX_NB), i32),
                   pool, pool).compile()
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("seq, names", [
    (1024, ("flash_fwd", "flash_bwd_fused")),
    (16384, ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")),
], ids=["fused_backward", "split_backward"])
def test_flash_kernels_carry_their_names(one_chip, seq, names):
    """A device trace finds a kernel by the name on its pallas_call
    (benchmark/named_kernels.py goes by it since PR 54): lowered for
    the TPU, forward and both forms of the backward."""
    q = jax.ShapeDtypeStruct((2, 12, seq, 64), jnp.bfloat16,
                             sharding=one_chip)
    attn = functools.partial(flash_attention_pallas, causal=True,
                             block_q=512, block_k=512, interpret=False,
                             layout="bhsd")
    text = jax.jit(jax.grad(
        lambda q, k, v: attn(q, k, v).astype(jnp.float32).sum(),
        argnums=(0, 1, 2))).lower(q, q, q).as_text()
    assert "tpu_custom_call" in text
    for name in names:
        assert name in text, name


def _chat_cell_params(one_chip):
    """GPT-2-small's parameters as the chat cell's engine keeps them
    (PR 61: ``gpt.init``'s float32 leaves in the dtypes of
    ``params_at_rest``, all bfloat16 but the three norm scales), as
    shapes on the described chip."""
    cfg = gpt.GPT2_SMALL
    given = jax.eval_shape(lambda: gpt.init(jax.random.key(0), cfg))
    return jax.tree_util.tree_map(
        lambda leaf, dt: jax.ShapeDtypeStruct(leaf.shape, dt,
                                              sharding=one_chip),
        given, gpt.dtypes_at_rest(given, cfg))


def _chat_cell_decode_lowered(one_chip):
    """The engine's own decode program lowered at the chat cell's shapes
    (GPT-2-small, 64 lanes of one row, 2,560 blocks of 16) from the
    parameters as served."""
    from ray_tpu.llm.engine import _jit_programs

    cfg = gpt.GPT2_SMALL
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    params = _chat_cell_params(one_chip)
    B, i32 = CELL_B, jnp.int32
    pool = S((cfg.n_layer, CELL_NB, BS, HKV * HD), jnp.bfloat16)
    decode = _jit_programs(cfg)[0]
    return decode.lower(params, S((B, step_columns(1).table + MAX_NB), i32),
                        pool, pool, q=1, firsts=S((B,), i32))


def test_decode_program_and_paged_kernel_carry_their_names(one_chip,
                                                           as_tpu):
    """The engine's own decode program, lowered for the TPU at the chat
    cell's shapes: the module is ``jit_llm_decode`` and it holds exactly
    one Mosaic call (the twelve layers run under one scan, the layer an
    operand of the kernel), ``paged_decode``, which takes the STACKED
    pools as the cache stores them and no view of a layer.
    benchmark/named_kernels.py finds the kernel by that name and
    divides its seconds by the executions of ``jit_llm_decode``: a
    renamed kernel or program would silence ``paged_kernel_ms``."""
    cfg, nb = gpt.GPT2_SMALL, CELL_NB
    text = _chat_cell_decode_lowered(one_chip).as_text()
    assert "module @jit_llm_decode " in text
    calls = [line for line in text.splitlines()
             if "@tpu_custom_call" in line]
    assert len(calls) == 1, len(calls)
    assert 'kernel_name = "paged_decode"' in calls[0]
    assert calls[0].count(
        f"tensor<{cfg.n_layer}x{nb}x{BS}x{HKV * HD}xbf16>") == 2
    assert f"x{nb}x{BS}x{HD}xbf16>" not in calls[0]


@pytest.fixture(scope="module")
def chat_decode(one_chip):
    """The chat cell's decode program compiled for the described v5e
    (one ~4 s compile for the tests that read it)."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        return _chat_cell_decode_lowered(one_chip).compile()


def test_compiled_decode_program_returns_ids_and_keeps_the_kernel_in_sight(
        chat_decode):
    """Compiled for the described v5e at the chat cell's shapes, the
    decode program returns the lanes' argmax ids with its one counter's
    row behind them, ``s32[65,1]``, beside its logits (the engine
    fetches those 65 ints and leaves the logits on the device), and its
    one Mosaic call is still ``paged_decode``, on the two stacked pools
    as stored, ``[12,2560,16,768]``, each ONCE, and on nothing of a
    layer pool's head-major shape ``[12,2560,16,64]`` (the benchmark's
    readers go by the kernel's NAME since PR 54, never by an operand).
    A refactor that moves either fails here, not in the benchmark's
    traced run."""
    import re

    cfg = gpt.GPT2_SMALL
    text = chat_decode.as_text()
    assert text.startswith("HloModule jit_llm_decode")
    entry = text[text.index("\nENTRY "):]
    root = next(line for line in entry.splitlines()
                if line.lstrip().startswith("ROOT "))
    outputs = re.findall(r"(\w+\[[\d,]*\])", root.split(" tuple(")[0])
    pools = f"bf16[{cfg.n_layer},{CELL_NB},{BS},{HKV * HD}]"
    assert outputs == [f"bf16[{CELL_B},1,{cfg.vocab_size}]",
                       f"s32[{CELL_B + 1},1]", pools, pools], root[:300]
    # Beside the parameters and the two pools the program takes ONE
    # host array, the step's packed bookkeeping (one hand-over a step),
    # and ``firsts``, ``s32[64]`` from the device: the first tokens of
    # the prompts whose last chunks are queued before it (PR 47).
    leaves = len(jax.tree_util.tree_leaves(
        jax.eval_shape(lambda: gpt.init(jax.random.key(0), cfg))))
    assert _entry_parameters(text) == leaves + 4
    entry_params = [line for line in entry.splitlines()
                    if " parameter(" in line]
    assert sum(f" s32[{CELL_B}]{{" in line or f" s32[{CELL_B}] " in line
               for line in entry_params) == 1, entry_params[-4:]
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 1 and "%paged_decode" in calls[0].split("=")[0]
    operands = re.findall(r"%[\w.\-]+", calls[0].split("custom-call(")[1]
                          .split(")")[0])
    # layer, tables, lens, q lens, starts, run flags; q; K, V: distinct.
    assert len(operands) == len(set(operands)) == 9, operands
    shapes = [re.search(rf"^\s*(?:ROOT )?{re.escape(o)} = (\S+?)\{{",
                        text, re.M).group(1) for o in operands]
    assert shapes.count(pools) == 2, shapes           # K's and V's pool
    assert f"[{HKV},{CELL_NB},{BS},{HD}]" not in text


LAYER_POOL = HKV * CELL_NB * BS * HD      # elements of one layer's K


def _results(text, *opcodes, at_least=LAYER_POOL):
    """(opcode, result shape) of every instruction of ``text``, fused
    bodies included, that has one of ``opcodes`` and a result of at
    least ``at_least`` elements (one layer's pool)."""
    import math
    import re

    found = []
    for m in re.finditer(
            r"^\s*(?:ROOT )?%[\w.\-]+ = (\w+\[([\d,]+)\])\S* ([\w\-]+)\(",
            text, re.M):
        shape, dims, op = m.groups()
        if op in opcodes and math.prod(map(int, dims.split(","))) \
                >= at_least:
            found.append((op, shape))
    return found


def test_compiled_decode_program_copies_the_pool_no_more_than_it_did(
        chat_decode):
    """ROADMAP A1's pin, tightened by PR 31 and closed by PR 59 (built by the refused PR 58).
    Compiled for the described v5e at the chat cell's shapes, the decode
    program (one row a lane through forward_step) (a) copies the
    stacked pool nowhere: the pools ride in the layer scan's carry and
    the step's rows are scattered into the donated buffers; (b) holds
    no ``copy`` or ``transpose`` of a layer's pool or more, N = 0, in
    the loop body or outside it; and (c) makes NO pass over a layer's
    pool at all: the paged kernel copies its pages out of the stacked
    pools itself, so nothing of a layer's size is sliced, joined,
    padded or written beside the two scatters. (Until PR 59 the kernel
    took a head-major operand ``bf16[12,2560,16,64]`` a pool, filled by
    one ``dynamic-update-slice`` fusion a head: 24 fusions a layer,
    1.25 s of a ~4 s trace; before PR 31 eight whole-pool copies and
    2.93 GB of temporaries. PERF.md section 6.) A compiler's count, not
    a time."""
    text = chat_decode.as_text()
    assert _results(text, "copy", "transpose", "copy-start") == []
    stack = f"bf16[{gpt.GPT2_SMALL.n_layer},{CELL_NB},{BS},{HKV * HD}]"
    assert _results(text, "scatter") == [("scatter", stack)] * 2
    assert _results(text, "dynamic-update-slice", "dynamic-slice",
                    "concatenate", "pad") == []
    assert chat_decode.memory_analysis().temp_size_in_bytes < 200e6


PROJECTION = 12 * 768 * 768      # elements of one attention projection


def _elements(shape):
    """Elements of an HLO shape as printed, ``bf16[12,768,3072]``."""
    import math

    return math.prod(map(int, shape[shape.index("[") + 1:-1].split(",")))


def _written(text):
    """``text`` without the bodies of its fused computations: the
    instructions left are those whose results are written to memory (a
    fused body's by the ``fusion`` instruction that calls it)."""
    import re

    return re.sub(r"^%fused_computation[^\n]*\{\n.*?^\}\n", "", text,
                  flags=re.M | re.S)


def _weight_sized(text, params):
    """(opcode, result shape) of every instruction of ``text`` outside a
    fused body that converts, copies or fuses into a result with as
    many elements as a leaf of ``params`` of an attention projection's
    size or more (the chunk's scores and the pools are larger than a
    projection too, and are nobody's weight)."""
    import math

    sizes = {math.prod(leaf.shape)
             for leaf in jax.tree_util.tree_leaves(params)}
    return [(op, shape) for op, shape in _results(
        _written(text), "fusion", "convert", "copy", "transpose",
        at_least=PROJECTION) if _elements(shape) in sizes]


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_served_programs_read_their_weights_as_stored(
        request, one_chip, as_tpu, program):
    """PR 61: from the parameters as served, compiled for the described
    v5e at the chat cell's shapes, neither program converts or copies a
    weight: nothing it writes to memory by a ``convert``, a ``copy``, a
    ``transpose`` or a fusion has a weight's size, from an attention
    projection's (12 x 768 x 768) up, and every entry parameter of that
    size is ``bf16``. (From float32 leaves each program wrote a
    ``bf16`` copy of seven leaves: with the float32 prefetch of ``wi``,
    six of the chat cell's ten largest device operations, 1.3 ms of
    every step; PERF.md section 6. Not counted: a fused body that
    WIDENS a weight it reads, as the chunk's head on one row does, a
    product on the vector unit, writes nothing; and the compiler's
    cross-program prefetch, ``copy-start`` / ``copy-done`` of ONE
    argument into faster memory, is a move it makes from either tree:
    ``wte`` as stored now, ``wi`` in float32 before.) A compiler's
    count, not a time."""
    import re

    c = request.getfixturevalue("chat_decode") if program == "decode" \
        else _chat_cell_chunk_lowered(one_chip, 512, MAX_NB).compile()
    text = c.as_text()
    assert _weight_sized(text, _chat_cell_params(one_chip)) == []
    if program == "decode":     # nor inside a fused body, there
        assert _results(text, "convert", "copy", "copy-start",
                        at_least=PROJECTION) == []
    entry = text[text.index("\nENTRY "):]
    entry = entry[:entry.index("\n}")]
    weights = [shape for shape in re.findall(
        r"= (\w+\[[\d,]+\])\S* parameter\(", entry)
        if _elements(shape) >= PROJECTION]
    # seven weights (``wpe`` is smaller), then the two pools
    assert len(weights) == 9 and all(
        shape.startswith("bf16[") for shape in weights), weights


@pytest.mark.parametrize("program", ["kv_scatter_blocks", "kv_copy_block"])
def test_compiled_pool_writers_update_the_pool_in_place(one_chip, program):
    """A chunk's pool write (32 blocks, a 512-token chunk) and the
    copy-on-write split, compiled for the described v5e at the chat
    cell's pool: no copy of the pool and under 64 MB of temporaries
    (the parent's scatter held four whole-stack copies and 1.51 GB, and
    took 14.2 ms on the chip for 0.1 ms of writing)."""
    from ray_tpu.llm import kv_cache

    L, i32 = gpt.GPT2_SMALL.n_layer, jnp.int32
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    pool = S((L, CELL_NB, BS, HKV * HD), jnp.bfloat16)
    if program == "kv_scatter_blocks":
        blocks = S((L, 32, BS, HKV * HD), jnp.bfloat16)
        c = kv_cache.kv_scatter_blocks.lower(pool, pool, blocks, blocks,
                                             S((32,), i32)).compile()
    else:
        c = kv_cache.kv_copy_block.lower(pool, pool, S((), i32),
                                         S((), i32)).compile()
    assert c.as_text().startswith(f"HloModule jit_{program}")
    assert _results(c.as_text(), "copy", "transpose", "copy-start",
                    "dynamic-slice") == []
    assert c.memory_analysis().temp_size_in_bytes < 64e6


# -- Laguna-XS.2 at the cell's shapes (benchmark/configs/laguna-xs2-serve) ----

LAGUNA_FULL_BLOCKS, LAGUNA_WINDOW_BLOCKS = 22528, 4096
LAGUNA_MAX_SEQ = 9216


def _laguna_cell():
    from ray_tpu.models import laguna

    kinds = ("full_attention", "sliding_attention", "sliding_attention",
             "sliding_attention", "full_attention")
    return laguna.LagunaConfig(
        num_hidden_layers=5,
        num_attention_heads_per_layer=(48, 64, 64, 64, 48),
        layer_types=kinds, max_seq=LAGUNA_MAX_SEQ,
        mlp_layer_types=("dense", "sparse", "sparse", "sparse", "sparse"))


@pytest.fixture(scope="module")
def laguna_programs(one_chip):
    """The engine's own decode and 512-token chunk programs for
    Laguna-XS.2, compiled for the described v5e at the cell's shapes:
    64 lanes, a full pool of 22,528 blocks (2 layers), a window pool of
    4,096 (3 layers), tables for 9,216 tokens. ~25 s for the pair."""
    from ray_tpu.llm.engine import _jit_programs
    from ray_tpu.models import laguna

    cfg = _laguna_cell()
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    params = jax.tree_util.tree_map(
        lambda leaf: S(leaf.shape, leaf.dtype),
        jax.eval_shape(lambda: laguna.init(jax.random.key(0), cfg)))
    B, i32, bf16 = CELL_B, jnp.int32, jnp.bfloat16
    full = S((2, LAGUNA_FULL_BLOCKS, BS, 8 * 128), bf16)
    window = S((3, LAGUNA_WINDOW_BLOCKS, BS, 8 * 128), bf16)
    max_nb = LAGUNA_MAX_SEQ // BS
    nbw = window_table_len(cfg.sliding_window, BS)
    decode, chunk = _jit_programs(cfg)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        lowered = decode.lower(
            params, S((B, step_columns(1, nbw).table + max_nb), i32),
            full, full, window, window, q=1, firsts=S((B,), i32))
        low_chunk = chunk.lower(
            params, S((1, 512), i32), full, full,
            S((max_nb + 512 // BS + 2,), i32), window, window,
            S((nbw + 1 + 512 // BS,), i32))
        return {
            "param_leaves": len(jax.tree_util.tree_leaves(params)),
            "decode_kernels": _kernel_bodies(lowered.as_text()),
            "decode": lowered.compile(),
            "chunk": low_chunk.compile(),
            **_as_lowered({"decode": lowered, "chunk": low_chunk}),
        }


def _kernel_bodies(lowered_text):
    """(kernel name, sha256 of its body) of each Mosaic call of a
    LOWERED program, in program order: the body is the kernel's own
    MLIR module, which the call carries serialized, printed without
    source locations (so a kernel that moved in its file, or whose
    callers did, reads the same)."""
    import base64
    import hashlib
    import re

    from jax._src.interpreters import mlir as jax_mlir
    from jax._src.lib.mlir import ir

    ctx = jax_mlir.make_ir_context()
    ctx.allow_unregistered_dialects = True      # ``stable_mosaic``
    found = []
    with ctx:
        for body in re.findall(r'body\\22: \\22([A-Za-z0-9+/=]+)\\22',
                               lowered_text):
            module = ir.Module.parse(base64.b64decode(body))
            asm = module.operation.get_asm(enable_debug_info=False)
            name = re.match(r"module @(\w+)", asm).group(1)
            found.append((name, hashlib.sha256(asm.encode())
                          .hexdigest()[:16]))
    return found


def _text_sha(text):
    """sha256 of a LOWERED program's text with each Mosaic call's
    serialized body cut out: the body carries the path of the checkout
    it was traced in (``_kernel_bodies`` reads the bodies)."""
    import hashlib
    import re

    text = re.sub(r'backend_config = "[^\n]*?"(?=[,}\s])',
                  'backend_config = "..."', text)
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def _as_lowered(lowered: dict):
    """What ``test_the_programs_that_keep_the_16_row_tile...`` holds of
    a model's LOWERED programs: each one's text, and the grouped
    product's kernels in it."""
    texts = {name: low.as_text() for name, low in lowered.items()}
    return {
        "texts": {name: _text_sha(text) for name, text in texts.items()},
        "moe_kernels": {name: sorted({
            kernel for kernel in _kernel_bodies(text)
            if kernel[0].startswith("moe_experts")})
            for name, text in texts.items()},
    }


def _mosaic_lines(text):
    """(name, instruction) of each Mosaic call of a compiled program,
    the name without its ``.n`` suffix."""
    import re

    return [(re.sub(r"\.\d+$", "", line.split("=")[0].strip().lstrip("%")),
             line) for line in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line]


def _mosaic_calls(text):
    import collections

    return collections.Counter(name for name, _ in _mosaic_lines(text))


def _kernel_operands(text, name):
    """The operand names of each Mosaic call whose result is ``name``,
    a list a call."""
    import re

    return [re.findall(r"%[\w.\-]+",
                       line.split("custom-call(")[1].split(")")[0])
            for kernel, line in _mosaic_lines(text) if kernel == name]


def _operand_shapes(text, name):
    """The operands' shapes (as printed: ``f32[64,2,32,128]{3,2,1,0}``)
    of each Mosaic call whose result is ``name``, a list a call."""
    import re

    shape_of = dict(re.findall(r"^\s*(?:ROOT )?(%[\w.\-]+) = (\S+)", text,
                               re.MULTILINE))
    return [[shape_of[operand] for operand in call]
            for call in _kernel_operands(text, name)]


def _column_blocks(text, name):
    """Operands of the Mosaic calls ``name`` that are a column block
    ``[b, nk, P, 128]`` of the cells' heads of 64: what ``ssm_update``
    was handed before PR 65 (``dt x`` and the decay stood up as lane
    columns by the program around it, 4.2 MB written and read a layer;
    since then ``dt x`` goes in as it lies, a row two heads, and the
    decay as scalars)."""
    return [shape for call in _operand_shapes(text, name) for shape in call
            if shape.startswith((f"f32[{CELL_B},1,64,128]",
                                 f"f32[{CELL_B},2,64,128]"))]


def _fusions_of(text, shape):
    """How many fusions of a compiled program give ``shape``."""
    import re

    return len(re.findall(rf"= {re.escape(shape)}\S* fusion\(", text))


def _pool_sized(text, *opcodes):
    """``_results`` at the size of the window kind's pool of ONE layer
    (the smaller of the two kinds')."""
    return _results(text, *opcodes,
                    at_least=LAGUNA_WINDOW_BLOCKS * BS * 8 * 128)


def test_laguna_decode_program_reads_the_pools_as_stored(laguna_programs):
    """The decode program at the cell's shapes: five paged calls named
    by kind (``attn_full`` x 2, ``attn_window`` x 3) and the grouped
    product twice a routed layer (``moe_experts_decode`` x 8), which is
    how the benchmark's readers find them; no ``copy`` or ``transpose``
    of a layer's pool (the paged call copies its pages out of the pool
    as stored: no head-major view, which at this pool would move ~6 GB
    a step) and each paged call takes its K pool and its V pool ONCE
    (it fetches its own pages since PR 42; a page window an operand
    made 32 of them), the run flags of a kind computed once for its
    layers; all four pools donated and aliased to their outputs, each
    written by one in-place scatter a layer; the ids come back with the
    three counter rows; and the step's temporaries are a few MB, not a
    pool's worth."""
    c = laguna_programs["decode"]
    text = c.as_text()
    assert text.startswith("HloModule jit_llm_decode")
    assert _mosaic_calls(text) == {"attn_full": 2, "attn_window": 3,
                                   "moe_experts_decode": 8}
    # tables, context lens, q lens, starts, run flags; q; K, V: distinct.
    for name in ("attn_full", "attn_window"):
        for operands in _kernel_operands(text, name):
            assert len(operands) == len(set(operands)) == 8, operands
    # A flag a group of 8 table slots: 72 a lane of the full kind's
    # table, 8 of the window kind's (two steps of 32 pages).
    assert _fusions_of(text, f"s32[{CELL_B},72]") == 1
    assert _fusions_of(text, f"s32[{CELL_B},8]") == 1
    # params' leaves, the step's ONE packed array, then the full kind's
    # K and V (outputs 2, 3 behind the logits and the ids) and the
    # window kind's behind them, and ``firsts`` from the device (PR 47):
    # nothing else is handed over.
    leaves = laguna_programs["param_leaves"]
    assert _aliased(text) == {leaves + 1: 2, leaves + 2: 3,
                              leaves + 3: 4, leaves + 4: 5}
    assert _entry_parameters(text) == leaves + 6
    assert _pool_sized(text, "copy", "transpose", "copy-start",
                       "dynamic-update-slice", "concatenate", "pad") == []
    full = f"bf16[2,{LAGUNA_FULL_BLOCKS},{BS},1024]"
    window = f"bf16[3,{LAGUNA_WINDOW_BLOCKS},{BS},1024]"
    assert sorted(_pool_sized(text, "scatter")) == sorted(
        [("scatter", full)] * 4 + [("scatter", window)] * 6)
    entry = text[text.index("\nENTRY "):]
    root = next(line for line in entry.splitlines()
                if line.lstrip().startswith("ROOT "))
    assert f"s32[{CELL_B + 3},1]" in root and f"bf16[{CELL_B},1,100352]" \
        in root
    assert c.memory_analysis().temp_size_in_bytes < 100e6


def _entry_parameters(text):
    """How many arguments the compiled program's entry takes."""
    import re

    entry = text[text.index("\nENTRY "):]
    entry = entry[:entry.index("\n}")]
    return len(set(re.findall(r" parameter\((\d+)\)", entry)))


def _aliased(text):
    """{parameter index: output index} of the module's
    ``input_output_alias``."""
    import re

    head = text[:text.index("entry_computation_layout")]
    return {int(p): int(o) for o, p in re.findall(
        r"\{(\d+)\}: \((\d+), \{\}", head)}


def test_laguna_chunk_program_pays_for_routed_experts_only(laguna_programs):
    """A 512-token chunk behind a 9,216-token table, ONE program: the
    grouped product runs as the ``moe_experts_chunk`` kernel (4,096
    assignments in tiles of 16 rows, not 256 dense experts: 825 GFLOP a
    layer); the four pools are donated, aliased to the outputs and
    written by one in-place scatter each, after every layer has read
    its context (no copy of a layer's pool, no other writer); the head
    runs on the one row that comes back (no ``[512, vocab]`` logits);
    and the temporaries stay under half a GB (no scores at all since
    PR 38: the attention is the ``chunk_attn`` kernel, once a layer)."""
    c = laguna_programs["chunk"]
    text = c.as_text()
    assert text.startswith("HloModule jit_llm_prefill_chunk")
    assert _mosaic_calls(text) == {"moe_experts_chunk": 8, "chunk_attn": 5}
    assert _pool_sized(text, "copy", "transpose", "copy-start",
                       "dynamic-slice", "dynamic-update-slice") == []
    full = f"bf16[2,{LAGUNA_FULL_BLOCKS},{BS},1024]"
    window = f"bf16[3,{LAGUNA_WINDOW_BLOCKS},{BS},1024]"
    assert sorted(_pool_sized(text, "scatter")) == sorted(
        [("scatter", full)] * 2 + [("scatter", window)] * 2)
    # params' leaves come first: the pools are the arguments after the
    # tokens, and outputs 2-5 behind the row and its id.
    n = laguna_programs["param_leaves"]
    assert _aliased(text) == {n + 1: 2, n + 2: 3, n + 4: 4, n + 5: 5}
    assert "[512,100352]" not in text and "[1,512,100352]" not in text
    assert c.memory_analysis().temp_size_in_bytes < 500e6
    cost = c.cost_analysis()
    flops = (cost[0] if isinstance(cost, (list, tuple)) else cost).get(
        "flops", 0.0)
    assert flops < 1.5e12, flops       # XLA's own count, kernels aside


def _chat_cell_chunk_lowered(one_chip, n, table):
    """The engine's own chunk program lowered at the chat cell's shapes
    from the parameters as served: ``n`` tokens behind a table of
    ``table`` slots."""
    from ray_tpu.llm.engine import _jit_programs

    cfg = gpt.GPT2_SMALL
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    pool = S((cfg.n_layer, CELL_NB, BS, HKV * HD), jnp.bfloat16)
    return _jit_programs(cfg)[1].lower(
        _chat_cell_params(one_chip), S((1, n), jnp.int32), pool, pool,
        S((table + n // BS + 2,), jnp.int32))


@pytest.mark.parametrize("n, table", [(512, MAX_NB), (320, 0)],
                         ids=["behind_context", "cold_prompt"])
def test_chat_cell_chunk_program_writes_its_span_in_place(one_chip, as_tpu,
                                                          n, table):
    """The chat cell's chunk program (a 512-token chunk behind a
    64-slot table; a cold 320-token prompt with none), ONE program a
    chunk: named ``jit_llm_prefill_chunk``; both pools donated and
    aliased to the outputs; each written by exactly one in-place
    scatter and touched by no pool-sized ``copy``, ``transpose``,
    ``copy-start`` or ``dynamic-slice`` (the context is gathered by
    table); what comes back is one row of logits and its argmax, the
    head on that row alone (the parent wrote ``f32``/``bf16[1,512,
    50304]`` for one row's sake); temporaries under 256 MB."""
    c = _chat_cell_chunk_lowered(one_chip, n, table).compile()
    cfg, params = gpt.GPT2_SMALL, _chat_cell_params(one_chip)
    text = c.as_text()
    assert text.startswith("HloModule jit_llm_prefill_chunk")
    leaves = len(jax.tree_util.tree_leaves(params))
    assert _aliased(text) == {leaves + 1: 2, leaves + 2: 3}
    stack = f"bf16[{cfg.n_layer},{CELL_NB},{BS},{HKV * HD}]"
    assert _results(text, "scatter") == [("scatter", stack)] * 2
    assert _results(text, "copy", "transpose", "copy-start",
                    "dynamic-slice", "dynamic-update-slice",
                    "concatenate", "pad") == []
    entry = text[text.index("\nENTRY "):]
    root = next(line for line in entry.splitlines()
                if line.lstrip().startswith("ROOT "))
    assert f"bf16[{cfg.vocab_size}]" in root and "s32[]" in root
    assert f"[{n},{cfg.vocab_size}]" not in text
    assert c.memory_analysis().temp_size_in_bytes < 256e6


def test_stored_paged_kernel_compiles_with_grouped_queries(one_chip):
    """``paged_attention_stored`` alone at head_dim 128: 6 query heads
    a KV head (full layers, 48 heads) and 8 (window layers, 64), one
    row a lane and a speculative 3, on the pool as stored."""
    from ray_tpu.ops.pallas.paged_fetch import paged_attention_stored

    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    pool = S((2, 2048, BS, 8 * 128), jnp.bfloat16)
    for group, q_len, nb in ((6, 1, 576), (8, 1, 34), (8, 3, 35)):
        lanes = S((16,), jnp.int32)
        c = _compile(
            lambda q, k, v, tables, lens, qlens, starts:
            paged_attention_stored(q, k, v, 1, tables, lens, qlens, starts,
                                   name="attn_test", interpret=False),
            S((16, q_len, 8, group, 128), jnp.bfloat16), pool, pool,
            S((16, nb), jnp.int32), lanes, lanes, lanes)
        assert "attn_test" in c.as_text()


# -- Kimi-K2.5 at the cell's shapes (benchmark/configs/kimi-k25-serve) --------

KIMI_BLOCKS, KIMI_MAX_SEQ, KIMI_ROW = 24576, 17408, 640


@pytest.fixture(scope="module")
def kimi_programs(one_chip):
    """The engine's own decode and chunk programs for the served share
    of Kimi-K2.5 (5 layers, 12 of 384 experts, a 20,480-token slice of
    the vocabulary), compiled for the described v5e at the cell's
    shapes: 64 lanes over ONE latent pool of 24,576 blocks of 16 rows
    of 640, tables for 17,408 tokens. ~25 s for the three."""
    from ray_tpu.llm.engine import _jit_programs
    from ray_tpu.models import kimi_k2

    cfg = kimi_k2.KimiK2Config(num_hidden_layers=5, vocab_size=20480,
                               experts_held=12, max_seq=KIMI_MAX_SEQ)
    assert cfg.row_width == KIMI_ROW
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    params = jax.tree_util.tree_map(
        lambda leaf: S(leaf.shape, leaf.dtype),
        jax.eval_shape(lambda: kimi_k2.init(jax.random.key(0), cfg)))
    B, i32 = CELL_B, jnp.int32
    pool = S((5, KIMI_BLOCKS, BS, KIMI_ROW), jnp.bfloat16)
    max_nb = KIMI_MAX_SEQ // BS
    decode, chunk = _jit_programs(cfg)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        lowered = {
            "decode": decode.lower(
                params, S((B, step_columns(1).table + max_nb), i32), pool,
                q=1, firsts=S((B,), i32)),
            # ``pack_spans``' array: four numbers a span behind the
            # table and the blocks written (PR 67).
            "chunk": chunk.lower(
                params, S((1, 512), i32), pool,
                S((max_nb + 512 // BS + 4 * kimi_k2.CHUNK_SPANS,), i32)),
            "cold_chunk": chunk.lower(
                params, S((1, 512), i32), pool,
                S((512 // BS + 4 * kimi_k2.CHUNK_SPANS,), i32)),
        }
        return {
            "param_leaves": len(jax.tree_util.tree_leaves(params)),
            **{name: low.compile() for name, low in lowered.items()},
            **_as_lowered(lowered),
        }


def _kimi_pool_sized(text, *opcodes):
    """``_results`` at the size of ONE layer of the latent pool."""
    return _results(text, *opcodes, at_least=KIMI_BLOCKS * BS * KIMI_ROW)


def test_kimi_decode_program_attends_the_latent_pool_as_stored(
        kimi_programs):
    """The decode program at the cell's shapes: the absorbed kernel
    once a layer under its name (``attn_latent`` x 5) and the grouped
    product twice a routed layer (``moe_experts_decode`` x 8), which is
    how the benchmark's readers find them; the ONE pool is donated and
    aliased to its output, written by one in-place scatter a layer, and
    nothing pool-sized is copied, transposed or padded (a row of 640 is
    five whole lane tiles: with rows of 576 the runtime keeps the pool
    at rest in a layout of its own and this program copies all 2.3 GB
    of it in and out, 2.56 GB of temporaries; PERF.md section 6, PR
    34); the kernel takes the pool ONCE (it fetches its own pages since
    PR 42; a page window an operand made 32 of them) and the five
    layers share one array of run flags; the ids come back with the
    four counter rows; temporaries are tens of MB."""
    c = kimi_programs["decode"]
    text = c.as_text()
    assert text.startswith("HloModule jit_llm_decode")
    assert _mosaic_calls(text) == {"attn_latent": 5, "moe_experts_decode": 8}
    # tables, context lens, q lens, run flags; q; the pool: distinct.
    for operands in _kernel_operands(text, "attn_latent"):
        assert len(operands) == len(set(operands)) == 6, operands
    assert _fusions_of(text, f"s32[{CELL_B},{KIMI_MAX_SEQ // BS // 8}]") == 1
    assert _kimi_pool_sized(text, "copy", "transpose", "copy-start",
                            "dynamic-update-slice", "concatenate",
                            "pad") == []
    pool = f"bf16[5,{KIMI_BLOCKS},{BS},{KIMI_ROW}]"
    assert _kimi_pool_sized(text, "scatter") == [("scatter", pool)] * 5
    # params' leaves, the step's ONE packed array, then the pool: output
    # 2 behind the logits and the ids; and ``firsts`` from the device.
    assert _aliased(text) == {kimi_programs["param_leaves"] + 1: 2}
    assert _entry_parameters(text) == kimi_programs["param_leaves"] + 3
    entry = text[text.index("\nENTRY "):]
    root = next(line for line in entry.splitlines()
                if line.lstrip().startswith("ROOT "))
    assert f"s32[{CELL_B + 4},1]" in root and f"bf16[{CELL_B},1,20480]" \
        in root
    assert c.memory_analysis().temp_size_in_bytes < 100e6


@pytest.mark.parametrize("which", ["chunk", "cold_chunk"])
def test_kimi_chunk_program_writes_its_span_in_place(kimi_programs, which):
    """A 512-token chunk behind a 17,408-token table (and one from a
    prompt's start, with no table), ONE program: the latent rows go up
    to keys and values 16 heads at a time (``chunk_attn`` once a layer,
    in the loop over head groups), so the temporaries stay under half a
    GB (all 64 heads at once are 0.59 GB of keys and values alone); the
    pool is donated, aliased and
    written by ONE in-place scatter after every layer has read it; the
    routed experts are the ``moe_experts_chunk`` kernel; the head runs
    on the four rows that come back, one a span the program can carry
    (PR 67: its table is ``pack_spans``', and ``chunk_attn`` is the
    variant that takes a description of each row)."""
    c = kimi_programs[which]
    text = c.as_text()
    assert text.startswith("HloModule jit_llm_prefill_chunk")
    assert _mosaic_calls(text) == {"moe_experts_chunk": 8, "chunk_attn": 5}
    assert _kimi_pool_sized(text, "copy", "transpose", "copy-start",
                            "dynamic-slice", "dynamic-update-slice",
                            "concatenate", "pad") == []
    assert _kimi_pool_sized(text, "scatter") == [
        ("scatter", f"bf16[5,{KIMI_BLOCKS},{BS},{KIMI_ROW}]")]
    assert _aliased(text) == {kimi_programs["param_leaves"] + 1: 2}
    assert "[512,20480]" not in text and "[1,512,20480]" not in text
    assert c.memory_analysis().temp_size_in_bytes < 500e6


@pytest.mark.parametrize("q_len", [1, 4], ids=["decode", "verify_q4"])
def test_latent_kernel_compiles_at_the_published_widths(one_chip, q_len):
    """``paged_attention_latent`` alone: 64 heads against rows of 640
    (512 latent + 64 rotary + padding), values the first 512 columns,
    one row a lane and a speculative 4."""
    from ray_tpu.ops.pallas.paged_fetch import paged_attention_latent

    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    lanes = S((16,), jnp.int32)
    c = _compile(
        lambda q, pool, tables, lens, qlens: paged_attention_latent(
            q, pool, 1, tables, lens, qlens, rank=512, scale=0.1447,
            interpret=False),
        S((16, q_len, 64, KIMI_ROW), jnp.bfloat16),
        S((2, 2048, BS, KIMI_ROW), jnp.bfloat16),
        S((16, KIMI_MAX_SEQ // BS), jnp.int32), lanes, lanes)
    assert "attn_latent" in c.as_text()



# -- the prefill chunk's attention kernel (PR 38) ------------------------------

def _f32_ending_in(text, *widths):
    """float32 results, fused bodies included, of three or more
    dimensions whose last is one of ``widths`` (a key count): what a
    chunk's scores would be."""
    import re

    return re.findall(r"f32\[(?:\d+,){2,}(?:%s)\]"
                      % "|".join(map(str, widths)), text)


@pytest.mark.parametrize("kvh, g, n, dk, dv, ds, slots, window", [
    (8, 6, 512, 128, 128, 0, LAGUNA_MAX_SEQ, None),
    (8, 8, 320, 128, 128, 0, LAGUNA_MAX_SEQ, None),
    (8, 8, 512, 128, 128, 0, 34 * BS, 512),
    (16, 1, 512, 192, 128, 64, KIMI_MAX_SEQ, None),
    (16, 1, 128, 192, 128, 64, KIMI_MAX_SEQ, None),
    (16, 1, 384, 192, 128, 0, 0, None),
    (16, 1, 512, 192, 128, 64, KIMI_MAX_SEQ, "rows"),
    (16, 1, 2048, 192, 128, 64, 4736, "rows"),      # XING_MAX_SEQ
    (16, 1, 1536, 192, 128, 64, 0, "rows"),
], ids=["laguna_g6", "laguna_g8_320", "laguna_window", "kimi_group",
        "kimi_group_128", "wide_keys_no_table", "kimi_group_described",
        "xing_group_2048_described", "no_table_described"])
def test_chunk_attention_kernel_compiles_at_the_cells_shapes(
        one_chip, kvh, g, n, dk, dv, ds, slots, window):
    """``chunk_attn`` alone, for the described v5e: Laguna's grouped
    queries at head width 128 behind 9,216 slots and behind a window's
    table, a group of 16 of Kimi's heads (keys of 128 + a shared 64,
    values of 128) behind 17,408 slots, and keys 192 wide with no table
    at all. ``ctx_len`` and ``base`` are operands: one compile serves
    every context. And the variant that takes a description of each
    row (``window`` "rows" here; PR 67), alone, at the latent family's
    shapes: Kimi's group behind its table, Xing4.0's 2,048 rows (two
    query blocks) behind its 4,736 slots, and with no table."""
    from ray_tpu.ops.pallas.chunk_attention import (chunk_attention,
                                                    padded_keys)

    S = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dt, sharding=one_chip)
    keys = padded_keys(slots + n)
    described = window == "rows"

    def attend(q, k, v, shared, ctx_len, base, *rows):
        return chunk_attention(
            q, k, v, ctx_len, ctx_slots=slots, scale=dk ** -0.5,
            k_shared=shared if ds else None, base=base,
            window=None if described else window, rows=rows or None,
            interpret=False)

    c = _compile(attend, S((kvh, g, n, dk)), S((kvh, keys, dk - ds)),
                 S((kvh, keys, dv)), S((keys, max(ds, 1))),
                 S((), jnp.int32), S((), jnp.int32),
                 *[S((n,), jnp.int32)] * (3 * described))
    calls = [line for line in c.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 1 and "%chunk_attn" in calls[0].split("=")[0]


def test_laguna_chunk_program_keeps_its_scores_out_of_hbm(laguna_programs):
    """The 512-token chunk behind 9,216 slots attends in ``chunk_attn``
    once a layer, both kinds (the window kind's table is 544 slots);
    the parent's ``f32[6 or 8, 512, 9728]`` scores a KV head, and the
    window kind's ``[.., 512, 1056]``, are in no buffer and no fused
    body; the temporaries fell from ~0.4 GB to under 0.15."""
    c = laguna_programs["chunk"]
    text = c.as_text()
    assert _mosaic_calls(text)["chunk_attn"] == 5
    assert _f32_ending_in(text, LAGUNA_MAX_SEQ + 512, 34 * BS + 512) == []
    assert c.memory_analysis().temp_size_in_bytes < 150e6


@pytest.mark.parametrize("which", ["chunk", "cold_chunk"])
def test_kimi_chunk_program_keeps_its_scores_out_of_hbm(kimi_programs,
                                                        which):
    """The up-projecting chunk attends in ``chunk_attn`` once a layer
    (the call sits in the loop over groups of 16 heads); the parent's
    ``f32[64, 512, 1024]`` scores a context block, or any scores over
    the 17,920 keys, are in no buffer and no fused body."""
    text = kimi_programs[which].as_text()
    assert _mosaic_calls(text)["chunk_attn"] == 5
    assert _f32_ending_in(text, 1024, KIMI_MAX_SEQ, KIMI_MAX_SEQ + 512) == []


def test_gpt2_chunk_program_has_no_chunk_kernel(one_chip, as_tpu):
    """GPT-2's chunk keeps its XLA-made attention (ISSUE 38: 2.45 ms
    behind a 1,024-slot table, nothing to win): lowered for the TPU at
    the chat cell's shapes, its program holds no Mosaic call at all."""
    text = _chat_cell_chunk_lowered(one_chip, 512, MAX_NB).as_text()
    assert "module @jit_llm_prefill_chunk " in text
    assert "tpu_custom_call" not in text and "chunk_attn" not in text


# -- the programs of a step, queued back to back (PR 47) ----------------------

@pytest.fixture(scope="module")
def chunk_program_texts(one_chip):
    """The three models' chunk programs lowered for the TPU (the chat
    cell's 512-token chunk behind context; Laguna's and Kimi's at their
    tiny test shapes, a 64-token chunk behind context); ``_text_sha``
    cuts each Mosaic call's serialized body out."""
    import test_kimi_k2
    import test_laguna
    from ray_tpu.llm.engine import _jit_programs
    from ray_tpu.models import kimi_k2, laguna, serving

    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    i32, bf16 = jnp.int32, jnp.bfloat16

    def shapes(init, cfg):
        return jax.tree_util.tree_map(
            lambda leaf: S(leaf.shape, leaf.dtype),
            jax.eval_shape(lambda: init(jax.random.key(0), cfg)))

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        lowered = {"gpt": _chat_cell_chunk_lowered(one_chip, 512, MAX_NB)}
        for name, mod, cfg in (("laguna", laguna, test_laguna.TINY),
                               ("kimi", kimi_k2, test_kimi_k2.TINY)):
            model, n = serving(cfg), 64
            pools = [[S((len(kind.layers), 32, BS, width), bf16)
                      for width in kind.rows] for kind in model.kinds]
            spans = 4 * model.chunk_spans if model.chunk_spans > 1 else 2
            args = [*pools[0],
                    S((model.max_seq // BS + n // BS + spans,), i32)]
            if len(pools) > 1:
                nbw = window_table_len(model.kinds[1].window, BS, 1)
                args += [*pools[1], S((nbw + 1 + n // BS,), i32)]
            lowered[name] = _jit_programs(cfg)[1].lower(
                shapes(mod.init, cfg), S((1, n), i32), *args)
    return {name: low.as_text() for name, low in lowered.items()}


# GPT-2's since PR 61, which lowers it from the parameters as served
# (bfloat16 weights in: "99837368b616cd4b" from float32 leaves). Kimi's
# re-recorded by PR 67, on its tree: the latent family's chunk program
# takes several spans (``pack_spans``' table, a description of each row
# for ``chunk_attn``, a head row a span); "568d0ef5d4d34d94" before.
CHUNK_TEXT_AT_PR46 = {"gpt": "8f3e1ee80c41bc30",
                      "laguna": "15e00d4b01149d99",
                      "kimi": "6ca702de31f7ef6b"}


@pytest.mark.parametrize("model", sorted(CHUNK_TEXT_AT_PR46))
def test_the_chunk_programs_are_what_they_were_before_the_step_queued_them(
        chunk_program_texts, model):
    """PR 47 hands a finishing prompt's first token to the decode
    program on the device and touches no chunk program: ``Serving.chunk``,
    ``pack_span`` and the three models' chunk functions lower, for the
    TPU, to the text they lowered to at the parent commit (sha256,
    recorded there with this function; Laguna's and Kimi's hold the
    ``chunk_attn`` kernel, GPT-2's none). So the chat cell's warmed
    chunk lengths keep their compile-cache keys (ROADMAP A7: an
    XLA-only program's key survives any move of its source); whoever
    changes a chunk program records the new text knowingly."""
    text = chunk_program_texts[model]
    assert ('kernel_name = "chunk_attn"' in text) == (model != "gpt")
    assert ("tpu_custom_call" in text) == (model != "gpt")
    assert _text_sha(text) == CHUNK_TEXT_AT_PR46[model]


# The served programs PR 66's move of the families' shared parts runs
# through and no table above holds: lowered at the cells' shapes by this
# file's fixtures, sha256 of the text (``_text_sha``), recorded on PR
# 65's tree with the test below. ``PROGRAMS_AT_PR62`` has the rest.
SERVED_TEXT_AT_PR65 = {
    ("decode", "gpt"): "ca9e1a33518210aa",
    ("decode", "granite"): "3d881707e540edb1",
    ("chunk", "granite"): "290aecb22f3fd387",
    # Re-recorded by PR 67, on its tree (it is Kimi's chunk program,
    # which takes several spans now); "5c9be7b5d7ee8776" before.
    ("chunk", "xing"): "2a211bec042c5200",
}


@pytest.mark.parametrize("program, model", [
    ("chunk", "nemotron"), ("chunk", "granite"), ("chunk", "xing"),
    ("decode", "gpt"), ("decode", "laguna"), ("decode", "kimi"),
    ("decode", "nemotron"), ("decode", "granite"), ("decode", "xing")])
def test_the_served_programs_are_what_they_were_under_five_forward_passes(
        program, model, request, one_chip):
    """PR 66 gives what the families share a module of its own
    (models/seam.py, layers.py, mamba2.py) and changes no program the
    chip runs: the three chunk programs the test above does not hold
    and every family's decode program lower, for the TPU at the cells'
    shapes, to the text they lowered to on the parent tree, where the
    hashes were recorded before the first function moved. The text
    carries no source locations, so code that only moves keeps its
    hash; an operation added, dropped, reordered or renamed does not."""
    if model == "gpt":
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(jax, "default_backend", lambda: "tpu")
            got = _text_sha(_chat_cell_decode_lowered(one_chip).as_text())
    else:
        programs = request.getfixturevalue(f"{model}_programs")
        got = programs["chunk_text"] if (program, model) == ("chunk", "xing") \
            else programs["texts"][program]
    held = SERVED_TEXT_AT_PR65.get((program, model))
    assert got == (held or PROGRAMS_AT_PR62[model]["texts"][program])


def test_the_placing_program_compiles_once_whatever_the_lane():
    """A finishing prompt's id goes into ``firsts`` at its lane by ONE
    tiny jitted program whose lane is a traced scalar from the device:
    eight prompts ending in one step in eight different lanes cost one
    backend compilation of it in all (on a CPU here; the shapes are the
    same on a chip)."""
    from jax import monitoring

    from ray_tpu.llm import engine as llm_engine
    from ray_tpu.llm.engine import LLMEngine

    cfg = gpt.GPTConfig(vocab_size=128, max_seq=64, d_model=64, n_layer=2,
                        n_head=4, dtype=jnp.float32)
    eng = LLMEngine(gpt.init(jax.random.key(0), cfg), cfg, num_blocks=67,
                    block_size=8, max_batch=9)
    eng.add_request([1, 2, 3], max_tokens=3)    # warm-up: lane 0
    while eng.step():
        pass
    placed, compiled = [], []
    place = llm_engine._place_first

    def on_place(firsts, lane, tok):
        placed.append(int(lane))
        return place(firsts, lane, tok)

    def listener(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiled.append(event)

    monitoring.register_event_duration_secs_listener(listener)
    llm_engine._place_first = on_place
    try:
        for i in range(8):
            eng.add_request([5, 6, 7 + i], max_tokens=4 + i)
        while eng.step():
            pass
        eng.add_request([9, 9, 9], max_tokens=3)
        while eng.step():
            pass
    finally:
        llm_engine._place_first = place
        monitoring.unregister_event_duration_listener(listener)
    # (the last prompt's chunk was alone in flight: awaited, not placed)
    assert sorted(placed) == list(range(8))
    # One compilation at most (none if a test before this one placed a
    # token at this batch size in this process).
    assert len(compiled) <= 1, compiled


# -- Nemotron-3-Super at the cell's shapes (configs/nemotron3-super-serve) ----

NEMO_BLOCKS, NEMO_SLOTS, NEMO_MAX_SEQ = 9216, 129, 4864
NEMO_STATE = f"f32[5,{NEMO_SLOTS},128,64,128]"
NEMO_CONV = f"bf16[5,{NEMO_SLOTS},3,10240]"


@pytest.fixture(scope="module")
def nemotron_programs(one_chip):
    """The engine's own decode and chunk programs for the served share
    of Nemotron-3-Super (one period of 11 layers, 64 of 512 experts, a
    16,384-token slice of the vocabulary), compiled for the described
    v5e at the cell's shapes: 64 lanes, keys and values of ONE attention
    layer in 9,216 blocks of 16 rows of 256, and the five state-space
    layers' state in 129 slots (2.7 GB in float32 beside 40 MB of
    convolution rows). ~35 s for the two."""
    from ray_tpu.llm.engine import _jit_programs
    from ray_tpu.models import nemotron_h

    cfg = nemotron_h.NemotronHConfig(
        num_hidden_layers=11, hybrid_override_pattern="EMEMEMEMEM*",
        vocab_size=16384, experts_held=64, max_seq=NEMO_MAX_SEQ)
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    params = jax.tree_util.tree_map(
        lambda leaf: S(leaf.shape, leaf.dtype),
        jax.eval_shape(lambda: nemotron_h.init(jax.random.key(0), cfg)))
    B, i32 = CELL_B, jnp.int32
    kv = S((1, NEMO_BLOCKS, BS, 256), jnp.bfloat16)
    state = (S((5, NEMO_SLOTS, 128, 64, 128), jnp.float32),
             S((5, NEMO_SLOTS, 3, 10240), jnp.bfloat16))
    max_nb = NEMO_MAX_SEQ // BS
    decode, chunk = _jit_programs(cfg)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        lowered = decode.lower(
            params, S((B, step_columns(1, 0, True).table + max_nb), i32),
            kv, kv, *state, q=1, firsts=S((B,), i32))
        # block table, 32 blocks written, ctx_len, last, two slots
        low_chunk = chunk.lower(
            params, S((1, 512), i32), kv, kv,
            S((max_nb + 512 // BS + 4,), i32), *state)
        return {
            "param_leaves": len(jax.tree_util.tree_leaves(params)),
            "decode_kernels": _kernel_bodies(lowered.as_text()),
            "decode": lowered.compile(),
            "chunk": low_chunk.compile(),
            **_as_lowered({"decode": lowered, "chunk": low_chunk}),
        }


def _nemo_state_sized(text, *opcodes):
    """``_results`` at the size of ONE layer of the state pool."""
    return _results(text, *opcodes, at_least=NEMO_SLOTS * 128 * 64 * 128)


def test_nemotron_decode_program_moves_its_states_in_place(
        nemotron_programs):
    """The decode program at the cell's shapes: the state update once a
    state-space layer under its name (``ssm_update`` x 5), the stored
    paged call once (``attn_full``), the grouped product twice an
    expert layer (``moe_experts_decode`` x 10): how the benchmark's
    readers find them. All four pools (keys, values, states,
    convolution rows) are donated and aliased to their outputs; the
    2.7 GB state pool is an operand of the five kernels and of NOTHING
    else that makes a pool-sized result: no gather of the lanes' slots,
    no scatter back, no copy, no change of layout (what that costs when
    it goes wrong is the Kimi pool's ``why_640``: 2.56 GB of
    temporaries a step). Temporaries are tens of MB."""
    c = nemotron_programs["decode"]
    text = c.as_text()
    assert text.startswith("HloModule jit_llm_decode")
    assert _mosaic_calls(text) == {"ssm_update": 5, "attn_full": 1,
                                   "moe_experts_decode": 10}
    assert _nemo_state_sized(text, "copy", "transpose", "copy-start",
                             "gather", "scatter", "dynamic-slice",
                             "dynamic-update-slice", "concatenate", "pad",
                             "fusion") == []
    for line in [l for n, l in _mosaic_lines(text) if n == "ssm_update"]:
        assert NEMO_STATE in line.split("custom-call(")[0]     # comes back
    assert _column_blocks(text, "ssm_update") == []
    # params' leaves, the packed array, then the four pools: outputs 2-5
    # behind the logits and the ids; and ``firsts`` from the device.
    n = nemotron_programs["param_leaves"]
    assert _aliased(text) == {n + 1: 2, n + 2: 3, n + 3: 4, n + 4: 5}
    assert _entry_parameters(text) == n + 6
    entry = text[text.index("\nENTRY "):]
    root = next(line for line in entry.splitlines()
                if line.lstrip().startswith("ROOT "))
    assert f"s32[{CELL_B + 4},1]" in root and f"bf16[{CELL_B},1,16384]" \
        in root and NEMO_STATE in root and NEMO_CONV in root
    assert c.memory_analysis().temp_size_in_bytes < 150e6


def test_nemotron_chunk_program_scans_from_a_slot_into_a_slot(
        nemotron_programs):
    """A 512-token span behind a 4,864-token table, ONE program:
    ``chunk_attn`` for the one attention layer, ``moe_experts_chunk``
    twice an expert layer, the chunked scan once a state-space layer
    (``ssm_scan``); each state-space layer reads ONE slot of the
    state pool and writes one (a slice and an in-place update of 4 MB,
    never the pool); all four pools aliased; the head runs on the one
    row that comes back; temporaries stay under a quarter of a GB."""
    c = nemotron_programs["chunk"]
    text = c.as_text()
    assert text.startswith("HloModule jit_llm_prefill_chunk")
    assert _mosaic_calls(text) == {"moe_experts_chunk": 10, "chunk_attn": 1,
                                   "ssm_scan": 5}
    assert _nemo_state_sized(text, "copy", "transpose", "copy-start",
                             "gather", "scatter", "concatenate",
                             "pad") == []
    n = nemotron_programs["param_leaves"]
    assert _aliased(text) == {n + 1: 2, n + 2: 3, n + 4: 4, n + 5: 5}
    assert "[512,16384]" not in text and "[1,512,16384]" not in text
    assert c.memory_analysis().temp_size_in_bytes < 250e6


# -- Granite 4.0-H Small at the cell's shapes (granite4-h-small-serve) ---------

GRANITE_BLOCKS, GRANITE_SLOTS, GRANITE_MAX_SEQ = 12288, 97, 2816
GRANITE_STATE = f"f32[9,{GRANITE_SLOTS},128,64,128]"
GRANITE_CONV = f"bf16[9,{GRANITE_SLOTS},3,8448]"


@pytest.fixture(scope="module")
def granite_programs(one_chip):
    """The engine's own decode and chunk programs for the served share
    of granite-4.0-h-small (one period of 10 layers, 36 of 72 experts,
    half the vocabulary), compiled for the described v5e at the cell's
    shapes: 64 lanes, keys and values of ONE attention layer in 12,288
    blocks of 16 rows of 1,024, and the nine Mamba-2 layers' state in 97
    slots (3.7 GB in float32); a 1,024-token span behind a 2,816-token
    table. ONE group of 128 heads: two blocks of 64 a lane in the
    update, eight blocks of 16 a block of 256 rows in the scan."""
    from ray_tpu.llm.engine import _jit_programs
    from ray_tpu.models import granite_hybrid as gh

    cfg = gh.GraniteHybridConfig(
        num_hidden_layers=10, vocab_size=50176, experts_held=36,
        layer_types=gh.GraniteHybridConfig().layer_types[10:20],
        max_seq=GRANITE_MAX_SEQ)
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    params = jax.tree_util.tree_map(
        lambda leaf: S(leaf.shape, leaf.dtype),
        jax.eval_shape(lambda: gh.init(jax.random.key(0), cfg)))
    B, i32 = CELL_B, jnp.int32
    kv = S((1, GRANITE_BLOCKS, BS, 1024), jnp.bfloat16)
    state = (S((9, GRANITE_SLOTS, 128, 64, 128), jnp.float32),
             S((9, GRANITE_SLOTS, 3, 8448), jnp.bfloat16))
    max_nb = GRANITE_MAX_SEQ // BS
    decode, chunk = _jit_programs(cfg)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        lowered = decode.lower(
            params, S((B, step_columns(1, 0, True).table + max_nb), i32),
            kv, kv, *state, q=1, firsts=S((B,), i32))
        low_chunk = chunk.lower(
            params, S((1, 1024), i32), kv, kv,
            S((max_nb + 1024 // BS + 4,), i32), *state)
        return {"param_leaves": len(jax.tree_util.tree_leaves(params)),
                "decode": lowered.compile(), "chunk": low_chunk.compile(),
                **_as_lowered({"decode": lowered, "chunk": low_chunk})}


def _granite_state_sized(text, *opcodes):
    """``_results`` at the size of ONE layer of the state pool, in its
    float32 (the one attention layer's keys are as many elements again,
    in bfloat16, and are written by one in-place scatter)."""
    return [(op, shape) for op, shape in _results(
        text, *opcodes, at_least=GRANITE_SLOTS * 128 * 64 * 128)
        if shape.startswith("f32[")]


def test_granite_decode_program_moves_its_states_in_place(granite_programs):
    """The decode program at the cell's shapes and the published
    widths: the state update once a Mamba-2 layer under its name
    (``ssm_update`` x 9), the stored paged call once (``attn_full``),
    the grouped product twice an expert block, which EVERY layer has
    (``moe_experts_decode`` x 20): how the benchmark's readers find
    them. All four pools are donated and aliased to their outputs; the
    3.7 GB state pool is an operand of the nine kernels and of nothing
    else that makes a pool-sized result. The tied head reads the
    embedding as it lies: no transposed copy of it."""
    c = granite_programs["decode"]
    text = c.as_text()
    assert text.startswith("HloModule jit_llm_decode")
    assert _mosaic_calls(text) == {"ssm_update": 9, "attn_full": 1,
                                   "moe_experts_decode": 20}
    assert _granite_state_sized(text, "copy", "transpose", "copy-start",
                                "gather", "scatter", "dynamic-slice",
                                "dynamic-update-slice", "concatenate", "pad",
                                "fusion") == []
    for line in [l for n, l in _mosaic_lines(text) if n == "ssm_update"]:
        assert GRANITE_STATE in line.split("custom-call(")[0]
    assert _column_blocks(text, "ssm_update") == []
    n = granite_programs["param_leaves"]
    assert _aliased(text) == {n + 1: 2, n + 2: 3, n + 3: 4, n + 4: 5}
    assert _entry_parameters(text) == n + 6
    entry = text[text.index("\nENTRY "):]
    root = next(line for line in entry.splitlines()
                if line.lstrip().startswith("ROOT "))
    assert f"s32[{CELL_B + 4},1]" in root and f"bf16[{CELL_B},1,50176]" \
        in root and GRANITE_STATE in root and GRANITE_CONV in root
    assert _results(text, "copy", "transpose",
                    at_least=50176 * 4096) == []
    assert c.memory_analysis().temp_size_in_bytes < 200e6


def test_granite_chunk_program_scans_from_a_slot_into_a_slot(
        granite_programs):
    """A 1,024-token span behind a 2,816-token table, ONE program:
    ``chunk_attn`` for the one attention layer, the grouped product
    twice an expert block in TALL tiles (1,024 x 10 / 72 = 142 rows an
    expert: ``moe_experts_chunk_r64`` x 20), the chunked scan once a
    Mamba-2 layer (``ssm_scan`` x 9) at the published block of 256
    rows; each reads ONE slot of the state pool and writes one; all
    four pools aliased; the head runs on the one row that comes back."""
    c = granite_programs["chunk"]
    text = c.as_text()
    assert text.startswith("HloModule jit_llm_prefill_chunk")
    assert _mosaic_calls(text) == {"moe_experts_chunk_r64": 20,
                                   "chunk_attn": 1, "ssm_scan": 9}
    assert _granite_state_sized(text, "copy", "transpose", "copy-start",
                                "gather", "scatter", "concatenate",
                                "pad") == []
    n = granite_programs["param_leaves"]
    assert _aliased(text) == {n + 1: 2, n + 2: 3, n + 4: 4, n + 5: 5}
    assert "[1024,50176]" not in text and "[1,1024,50176]" not in text
    assert c.memory_analysis().temp_size_in_bytes < 1.2e9


@pytest.mark.parametrize("H, G, l, rows", [(128, 1, 256, 1024),
                                           (128, 1, 256, 256),
                                           (128, 8, 128, 512)])
def test_the_state_space_kernels_compile_whatever_a_groups_width(
        one_chip, as_tpu, H, G, l, rows):
    """``ssd_scan`` and ``ssm_update`` alone at the two cells' widths
    (heads of 64, state 128): ONE group of 128 heads at the published
    block of 256 rows (Granite 4.0-H: a group wider than either
    kernel's block of heads) and 8 groups of 16 at 128 (Nemotron-3).
    The kernels keep their names."""
    from ray_tpu.ops import ssm

    S = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(
        shape, dt, sharding=one_chip)
    text = _compile(
        lambda x, dt, A, B, C, S0: ssm.ssd_scan(x, dt, A, B, C, S0, l),
        S((rows, H, 64)), S((rows, H)), S((H,)), S((rows, G, 128)),
        S((rows, G, 128)), S((H, 64, 128))).as_text()
    assert _mosaic_calls(text) == {"ssm_scan": 1}
    text = _compile(
        lambda pool, slots, decay, dtx, B, C: ssm.ssm_update(
            pool, 1, slots, decay, dtx, B, C),
        S((2, 9, H, 64, 128)), S((CELL_B,), jnp.int32), S((CELL_B, H)),
        S((CELL_B, H, 64)), S((CELL_B, G, 128)),
        S((CELL_B, G, 128))).as_text()
    assert _mosaic_calls(text) == {"ssm_update": 1}


# -- Xing4.0 at the cell's shapes (benchmark/configs/xing4-29b-serve) ----------

XING_BLOCKS, XING_MAX_SEQ, XING_LAYERS, XING_STREAMS = 19456, 4736, 7, 14336


@pytest.mark.parametrize("rows", [64, 2048], ids=["decode_64", "chunk_2048"])
def test_mhc_kernels_compile_at_the_published_widths(one_chip, rows):
    """``mhc_pre`` and ``mhc_post`` alone: four streams of 3,584 as one
    row of 14,336, ``Phi`` [14,336, 24], a decode step's 64 rows (one
    block, padded to a lane tile for the transposes) and a chunk's
    2,048 (eight grid steps of 256); both carry their names, which is
    how the benchmark's readers find them, and ``mhc_post`` writes the
    streams it was donated."""
    from ray_tpu.ops import mhc

    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    bf16, f32 = jnp.bfloat16, jnp.float32
    kw = dict(n=4, iters=20, eps=1e-6, norm_eps=1e-6)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        pre = _compile(lambda X, phi, a, b: mhc.mhc_pre(X, phi, a, b, **kw),
                       S((rows, XING_STREAMS), bf16),
                       S((XING_STREAMS, 24), bf16), S((3,), f32),
                       S((24,), f32))
        post = jax.jit(lambda X, y, coef: mhc.mhc_post(X, y, coef, n=4),
                       donate_argnums=0).lower(
            S((rows, XING_STREAMS), bf16), S((rows, 3584), bf16),
            S((rows, 128), f32)).compile()
    for name, c in (("mhc_pre", pre), ("mhc_post", post)):
        (line,) = [l for _, l in _mosaic_lines(c.as_text())]
        assert f"%{name}" in line.split(" = ")[0]
    assert "input_output_alias={ {}: (0, {}" in post.as_text()
    assert post.memory_analysis().temp_size_in_bytes == 0


@pytest.fixture(scope="module")
def xing_programs(one_chip):
    """The engine's own decode and chunk programs for the served cut of
    Xing4.0-29B-A4B (7 layers: dense layer 0 and six routed ones, all 64
    experts, the whole vocabulary), compiled for the described v5e at
    the cell's shapes: 64 lanes over ONE latent pool of 19,456 blocks
    of 16 rows of 640, a 2,048-token span behind a 4,736-token table.
    ~35 s for the two."""
    from ray_tpu.llm.engine import _jit_programs
    from ray_tpu.models import kimi_k2, xing4

    cfg = xing4.Xing4Config(num_hidden_layers=XING_LAYERS,
                            first_k_dense_replace=1, max_seq=XING_MAX_SEQ)
    assert cfg.row_width == KIMI_ROW
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    params = jax.tree_util.tree_map(
        lambda leaf: S(leaf.shape, leaf.dtype),
        jax.eval_shape(lambda: xing4.init(jax.random.key(0), cfg)))
    B, i32 = CELL_B, jnp.int32
    pool = S((XING_LAYERS, XING_BLOCKS, BS, KIMI_ROW), jnp.bfloat16)
    max_nb = XING_MAX_SEQ // BS
    decode, chunk = _jit_programs(cfg)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        lowered = decode.lower(
            params, S((B, step_columns(1).table + max_nb), i32), pool,
            q=1, firsts=S((B,), i32))
        low_chunk = chunk.lower(
            params, S((1, 2048), i32), pool,
            S((max_nb + 2048 // BS + 4 * kimi_k2.CHUNK_SPANS,), i32))
        return {
            "param_leaves": len(jax.tree_util.tree_leaves(params)),
            "decode": lowered.compile(),
            "chunk": low_chunk.compile(),
            # Apart from ``texts``: PR 63 moved this program knowingly.
            "chunk_text": _text_sha(low_chunk.as_text()),
            **_as_lowered({"decode": lowered}),
        }


def _xing_config_file():
    import json

    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmark", "configs",
            "xing4-29b-serve.json")) as f:
        return json.load(f)


def test_xing_decode_program_mixes_the_streams_around_kimis_kernels(
        xing_programs):
    """The decode program at the cell's shapes: Kimi's absorbed kernel
    once a layer and its grouped product twice a routed layer, and
    around each of the 14 sublayers one ``mhc_pre`` and one ``mhc_post``
    under their names; the ONE pool is donated and aliased to its
    output, written by one in-place scatter a layer, nothing pool-sized
    is copied; the ids come back with FIVE counter rows; the
    temporaries are what the configuration's file says they are."""
    c = xing_programs["decode"]
    text = c.as_text()
    assert text.startswith("HloModule jit_llm_decode")
    assert _mosaic_calls(text) == {
        "attn_latent": 7, "moe_experts_decode": 12, "mhc_pre_decode": 14,
        "mhc_post_decode": 14}
    layer = XING_BLOCKS * BS * KIMI_ROW
    assert _results(text, "copy", "transpose", "copy-start",
                    "dynamic-update-slice", "concatenate", "pad",
                    at_least=layer) == []
    # One scatter a layer, in place (the compiler splits one of the
    # seven in two: eight fusions, each on the aliased pool).
    pool = f"bf16[{XING_LAYERS},{XING_BLOCKS},{BS},{KIMI_ROW}]"
    scatters = _results(text, "scatter", at_least=layer)
    assert set(scatters) == {("scatter", pool)} and 7 <= len(scatters) <= 8
    assert _aliased(text) == {xing_programs["param_leaves"] + 1: 2}
    entry = text[text.index("\nENTRY "):]
    root = next(line for line in entry.splitlines()
                if line.lstrip().startswith("ROOT "))
    assert f"s32[{CELL_B + 5},1]" in root \
        and f"bf16[{CELL_B},1,131072]" in root
    temp = c.memory_analysis().temp_size_in_bytes
    said = _xing_config_file()["programs_compiled_for_a_described_v5e"]
    assert temp < 50e6
    assert abs(temp - said["decode_temporaries_bytes"]) < 0.1 * temp


def test_xing_chunk_program_writes_its_span_in_place(xing_programs):
    """A 2,048-token chunk behind a 4,736-token table, ONE program:
    ``chunk_attn`` once a layer, ``moe_experts_chunk_r64`` twice a
    routed layer (2,048 rows give an expert 128: the tall tile, PR 63),
    the two residual kernels around all 14 sublayers, each
    ``mhc_post`` writing the 58.7 MB of streams it was handed (its
    first operand is its result's buffer: no second copy of the streams
    a sublayer); the pool donated, aliased and written by ONE in-place
    scatter; the head on the four rows that come back (one a span the
    program can carry, PR 67); the temporaries
    are what the configuration's file says they are, and leave the
    13.87 GB of weights and pool their room."""
    c = xing_programs["chunk"]
    text = c.as_text()
    assert text.startswith("HloModule jit_llm_prefill_chunk")
    assert _mosaic_calls(text) == {
        "chunk_attn": 7, "moe_experts_chunk_r64": 12, "mhc_pre_chunk": 14,
        "mhc_post_chunk": 14}
    layer = XING_BLOCKS * BS * KIMI_ROW
    assert _results(text, "copy", "transpose", "copy-start", "dynamic-slice",
                    "dynamic-update-slice", "concatenate", "pad",
                    at_least=layer) == []
    assert _results(text, "scatter", at_least=layer) == [
        ("scatter", f"bf16[{XING_LAYERS},{XING_BLOCKS},{BS},{KIMI_ROW}]")]
    assert _aliased(text) == {xing_programs["param_leaves"] + 1: 2}
    assert "[2048,131072]" not in text and "[1,2048,131072]" not in text
    # ONE copy the size of the streams, their opening as four copies of
    # the embedded rows; after it every ``mhc_post`` overwrites what it
    # is given.
    assert len(_results(text, "copy", at_least=2048 * XING_STREAMS)) == 1
    temp = c.memory_analysis().temp_size_in_bytes
    said = _xing_config_file()["programs_compiled_for_a_described_v5e"]
    assert temp < 500e6
    assert abs(temp - said["chunk_2048_behind_context_temporaries_bytes"]) \
        < 0.1 * temp


def test_kimis_decode_program_is_what_it_was_before_it_took_its_residual(
        one_chip, as_tpu):
    """PR 62 hands ``kimi_k2.forward_step`` and
    ``forward_prefill_chunk`` their residual path (``Residual``;
    ``PLAIN`` for Kimi itself) so that models/xing4.py can run the same
    loops on four streams. Kimi's own decode program, at its tiny test
    shapes with one row a lane and with three, lowers for the TPU to the
    text it lowered to at the parent commit (sha256, recorded there with
    this function); its chunk program is held by
    ``test_the_chunk_programs_are_what_they_were...`` above."""
    import test_kimi_k2
    from ray_tpu.llm.engine import _jit_programs
    from ray_tpu.models import kimi_k2, serving

    cfg = test_kimi_k2.TINY
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    params = jax.tree_util.tree_map(
        lambda leaf: S(leaf.shape, leaf.dtype),
        jax.eval_shape(lambda: kimi_k2.init(jax.random.key(0), cfg)))
    model = serving(cfg)
    pool = S((3, 32, BS, model.kinds[0].rows[0]), jnp.bfloat16)
    got = {}
    for q in (1, 3):
        got[q] = _text_sha(_jit_programs(cfg)[0].lower(
            params, S((8, step_columns(q).table + model.max_seq // BS),
                      jnp.int32), pool, q=q,
            firsts=S((8,), jnp.int32)).as_text())
    assert got == {1: "9958dbb64fb52d27", 3: "211888ce6c170213"}


# -- the stored kernels at head_dim 128 are what they were (PR 59) ------------

STORED_KERNELS_AT_PR57 = {
    "laguna": [("attn_full", "c1f3574976d76470"),
               ("attn_window", "90ce8d54296f1d30"),
               ("attn_window", "984c78dcf0dcb15f"),
               ("attn_window", "ed4595bb099930fa"),
               ("attn_full", "add6a59ba60f7c17")],
    "nemotron": [("attn_full", "2fb8c21143f770b5")],
}


@pytest.mark.parametrize("model", ["laguna", "nemotron"])
def test_the_stored_kernels_at_head_dim_128_lower_to_what_they_did(
        model, request):
    """PR 59 gave ``paged_attention_stored`` GPT-2's shape (a head half
    a lane tile) and a layer index that may be traced; Laguna's and
    Nemotron's callers still pass a Python int and whole-tile heads,
    and their decode programs at the cells' shapes lower ``attn_full``
    and ``attn_window`` to the kernels they lowered to at the parent
    commit: each Mosaic body printed without source locations, sha256,
    recorded at PR 57's tree with this function. So the pages, runs and
    arithmetic PR 42 measured stand; whoever changes the d = 128 path
    records the new text knowingly."""
    programs = request.getfixturevalue(f"{model}_programs")
    stored = [(name, sha) for name, sha in programs["decode_kernels"]
              if name.startswith("attn_")]
    assert stored == STORED_KERNELS_AT_PR57[model]


# -- the grouped product's tile follows the rows an expert gets (PR 63) -------

XING_EXPERTS, XING_TOP_K, XING_HIDDEN, XING_EXPERT_FF = 64, 4, 3584, 1024


@pytest.mark.parametrize("rows, tile", [
    (64, 16), (512, 64), (1024, 64), (1536, 64), (2048, 64)],
    ids=["decode_64", "chunk_512", "chunk_1024", "chunk_1536", "chunk_2048"])
def test_the_grouped_product_compiles_at_the_tile_its_rows_give(
        one_chip, as_tpu, rows, tile):
    """Xing4.0's two grouped products at their published widths
    (``[M, 3584] x [64, 3584, 2048]`` and ``[M, 1024] x [64, 1024,
    3584]``), at the tile and the column block ``ops/moe.py`` gives the
    decode step and each of the four chunk lengths the cell warms: the
    chip's compiler takes every one (a tall tile asks for the VMEM its
    whole-width weight block needs), and the kernel's name says which
    tile ran: ``_r<tile>`` behind it where the tile is over 16, the name
    it had where it is 16."""
    from ray_tpu.ops import moe

    assert moe.tile_rows(rows * XING_TOP_K, XING_EXPERTS) == tile
    M = moe.plan_rows(rows * XING_TOP_K, XING_EXPERTS, tile)
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    bf16 = jnp.bfloat16
    name = "moe_experts_chunk" + (f"_r{tile}" if tile > 16 else "")
    for k, n in ((XING_HIDDEN, 2 * XING_EXPERT_FF),
                 (XING_EXPERT_FF, XING_HIDDEN)):
        tn = moe._tile_cols(k, n, 2, tile)
        assert n % tn == 0 and tn % 128 == 0
        # A tall tile reads its rows once: the weight block is as wide
        # as the weights.
        assert (tn == n) == (tile > 16)
        text = _compile(
            lambda x, w, te, nu: moe.grouped_matmul(
                x, w, te, nu, "moe_experts_chunk"),
            S((M, k), bf16), S((XING_EXPERTS, k, n), bf16),
            S((M // tile,), jnp.int32), S((), jnp.int32)).as_text()
        assert [kernel.removeprefix("ROOT %")
                for kernel, _ in _mosaic_lines(text)] == [name]


PROGRAMS_AT_PR62 = {
    "laguna": {
        "texts": {"decode": "9f0541aab1ca182a", "chunk": "3a5682b240c7eecc"},
        "moe_kernels": {
            "decode": [("moe_experts_decode", "49d4c9f08194534c"),
                       ("moe_experts_decode", "cf435d0b7ef311a9")],
            "chunk": [("moe_experts_chunk", "1b3435937767bed7"),
                      ("moe_experts_chunk", "8e203221df276585")]}},
    "kimi": {
        # chunk and cold_chunk: re-recorded by PR 67, on its tree (the
        # program takes several spans; "1dfed06f04c4fac7" and
        # "85017cce77588407" before); the decode program and the grouped
        # products' bodies are PR 62's.
        "texts": {"decode": "cd54fd8dd84d3a7a", "chunk": "b4ea62119eb14f97",
                  "cold_chunk": "9152b619dfa5b6bb"},
        "moe_kernels": {
            "decode": [("moe_experts_decode", "3c9e044c4ce03286"),
                       ("moe_experts_decode", "8281b9bf30d7ee6a")],
            "chunk": [("moe_experts_chunk", "4c88f51ff0bb2351"),
                      ("moe_experts_chunk", "b623716b1f0f63b4")],
            "cold_chunk": [("moe_experts_chunk", "4c88f51ff0bb2351"),
                           ("moe_experts_chunk", "b623716b1f0f63b4")]}},
    "nemotron": {
        # decode: re-recorded by PR 65 (``ssm_update``'s operands: the decay
        # a scalar-prefetch array, ``dt x`` as it lies); the chunk program
        # and the grouped products' bodies are PR 62's.
        "texts": {"decode": "b7177c031df3bed4", "chunk": "7c2f03116a053fc2"},
        "moe_kernels": {
            "decode": [("moe_experts_decode", "350735f216903e16"),
                       ("moe_experts_decode", "8f235c3e9216f1c5")],
            "chunk": [("moe_experts_chunk", "1fc50472d12fabcc"),
                      ("moe_experts_chunk", "c9c825b4f4513a98")]}},
    "xing": {
        "texts": {"decode": "2b345588f0123fd8"},
        "moe_kernels": {
            "decode": [("moe_experts_decode", "68302d0d3d17d5e6"),
                       ("moe_experts_decode", "d84c3ecc194a7c8d")]}},
}


@pytest.mark.parametrize("model", ["laguna", "kimi", "nemotron", "xing"])
def test_the_programs_that_keep_the_16_row_tile_are_what_they_were(
        model, request):
    """PR 63 makes the grouped product's tile a function of the call's
    rows. Laguna's, Kimi's and Nemotron's decode and chunk programs and
    Xing4.0's decode program give an expert 0.8-22 rows at the cells'
    shapes and keep the 16-row tile: each LOWERS, for the TPU, to the
    text it lowered to at the parent commit, and its grouped products
    to the kernel bodies they were (sha256, recorded at PR 62's tree
    with this function). So what PRs 32-62 measured of them stands;
    whoever moves one records the new text knowingly."""
    programs = request.getfixturevalue(f"{model}_programs")
    assert {"texts": programs["texts"],
            "moe_kernels": programs["moe_kernels"]} == PROGRAMS_AT_PR62[model]
