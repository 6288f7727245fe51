"""GPT-2-class decoder-only transformer, parallelism-aware.

The flagship model for the Train north-star configs ("GPT-2 DDP" in
BASELINE.md). Written TPU-first:

  * bf16 activations, f32 params/optimizer (bf16 matmuls hit the MXU)
  * scan-over-layers with optional remat (fast compiles, low memory)
  * every param carries logical axis names; the same model runs dp-only,
    fsdp, tp, sp or any mix purely by changing the mesh + rule table
  * activation sharding constraints so XLA partitions along the intended
    axes instead of guessing

No counterpart exists in the reference (it orchestrates external models);
this model exists so the framework's Train/Tune/Serve stacks have a serious
native workload.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.attention import NEG_INF, causal_attention
from ..ops.flash_attention import flash_attention
from ..ops.pallas.paged_fetch import (kv_pages_in_runs_x1000,
                                      paged_attention_stored)
from ..parallel.sharding import (DEFAULT_RULES, logical_to_mesh_axes,
                                 shard_like)
from .seam import (Serving, keys_and_values, scatter_span, unpack_span,
                   unpack_step)


@dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 50304  # GPT-2 BPE padded to a multiple of 128 (MXU tiling)
    max_seq: int = 1024
    d_model: int = 768
    n_layer: int = 12
    n_head: int = 12
    n_kv_head: Optional[int] = None
    d_ff: Optional[int] = None  # default 4*d_model
    dtype: Any = jnp.bfloat16
    remat: bool = False
    use_flash: bool = False
    # Flash kernel block sizes (swept per shape; 512 is the v5e sweet spot
    # for seq 1024 — see BENCH notes).
    flash_block: int = 512
    # Rematerialization policy when remat=True:
    #   "dots_no_batch" — save only weight-stationary dots (max memory
    #       savings, recomputes every activation matmul in the backward)
    #   "dots"          — save every matmul output, recompute only the
    #       elementwise ops (layernorm/gelu/softmax) — near remat=False
    #       speed at a fraction of the extra memory
    #   "mlp_only"      — checkpoint ONLY each block's MLP; attention (the
    #       flash kernel) keeps its residuals, so the backward never
    #       re-runs the attention forward.
    #   "dots_flash"    — "dots" plus the flash kernel's tagged outputs
    #       (out + LSE): every attention residual is saved, so the remat
    #       retrace DCEs the kernel recompute while elementwise ops still
    #       rematerialize. Measured fastest at the bench shape.
    remat_policy: str = "dots_flash"

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_head

    @property
    def kv_heads(self) -> int:
        return self.n_kv_head or self.n_head

    @property
    def ff(self) -> int:
        return self.d_ff or 4 * self.d_model

    def num_params(self) -> int:
        m, f, L = self.d_model, self.ff, self.n_layer
        attn = m * m * 2 + 2 * m * (self.kv_heads * self.head_dim)
        mlp = 2 * m * f
        return self.vocab_size * m + self.max_seq * m + L * (attn + mlp + 2 * m) + m

    def flops_per_token(self) -> float:
        """Training FLOPs/token ≈ 6*N + attention term (delegates to
        util/perfmodel.py — the shared cost model the live
        llm_mfu/train_mfu telemetry series also price against)."""
        # In the function: util/perfmodel.py imports this package.
        from ..util import perfmodel

        return perfmodel.train_flops_per_token(self)


# Tiny/small presets used by tests, bench and the graft entry.
TINY = GPTConfig(vocab_size=512, max_seq=128, d_model=128, n_layer=2, n_head=4)
GPT2_SMALL = GPTConfig()  # 124M


def param_axes(cfg: GPTConfig) -> dict:
    """Logical-axis annotations matching init()'s param tree."""
    L = ("layers",)
    return {
        "wte": ("vocab", "embed"),
        "wpe": (None, "embed"),
        "blocks": {
            "ln1": L + (None,),
            "wq": L + ("embed", "heads", "head_dim"),
            "wk": L + ("embed", "kv", "head_dim"),
            "wv": L + ("embed", "kv", "head_dim"),
            "wo": L + ("heads", "head_dim", "embed"),
            "ln2": L + (None,),
            "wi": L + ("embed", "mlp"),
            "wm": L + ("mlp", "embed"),
        },
        "ln_f": (None,),
    }


@functools.partial(jax.jit, static_argnames=("cfg",))
def init(key, cfg: GPTConfig) -> dict:
    """Initialize params (f32). GPT-2-style scaled init. One program:
    leaf by leaf it was 19, and on a machine with no compile cache a
    served replica spent its first 40-odd seconds compiling them while
    its first request ran into the proxy's 60 s limit (PERF.md section
    6, PR 33). The values are the eager ones, bit for bit."""
    m, d, h, hk, f, L = (cfg.d_model, cfg.head_dim, cfg.n_head, cfg.kv_heads,
                         cfg.ff, cfg.n_layer)
    k = iter(jax.random.split(key, 16))
    std = 0.02
    resid_std = std / np.sqrt(2 * L)

    def rnd(key, shape, s):
        # The barrier keeps the draw and its scaling apart, as two
        # eager calls had them: fused, the compiler folds the two
        # constants into one and the weights differ in their last bit.
        draw = jax.lax.optimization_barrier(jax.random.normal(key, shape))
        return (draw * s).astype(jnp.float32)

    return {
        "wte": rnd(next(k), (cfg.vocab_size, m), std),
        "wpe": rnd(next(k), (cfg.max_seq, m), std),
        "blocks": {
            "ln1": jnp.ones((L, m), jnp.float32),
            "wq": rnd(next(k), (L, m, h, d), std),
            "wk": rnd(next(k), (L, m, hk, d), std),
            "wv": rnd(next(k), (L, m, hk, d), std),
            "wo": rnd(next(k), (L, h, d, m), resid_std),
            "ln2": jnp.ones((L, m), jnp.float32),
            "wi": rnd(next(k), (L, m, f), std),
            "wm": rnd(next(k), (L, f, m), resid_std),
        },
        "ln_f": jnp.ones((m,), jnp.float32),
    }


# The leaves _layernorm multiplies in float32: every other leaf's every
# use is ``.astype(cfg.dtype)``.
NORM_SCALES = ("ln1", "ln2", "ln_f")


def dtypes_at_rest(params, cfg: GPTConfig) -> dict:
    """The dtype a leaf of ``params`` (arrays or their shapes) is served
    in: ``cfg.dtype`` but for the norms' scales, which stay as given."""
    dt = jnp.dtype(cfg.dtype)
    return jax.tree_util.tree_map_with_path(
        lambda path, x: x.dtype if path[-1].key in NORM_SCALES else dt,
        params)


def params_at_rest(params, cfg: GPTConfig) -> dict:
    """``params`` as the served programs read them (the seam's
    ``Serving.at_rest``): every leaf but the norms' scales in
    ``cfg.dtype``, rounded ONCE here and not by every step. ``init``'s
    leaves are float32 (a trainer's master weights), and forward_step
    and forward_prefill_chunk round each to ``cfg.dtype`` in front of
    its product, so from a float32 tree both programs read and rewrote
    all 124M parameters every step (1.3 ms of the chat cell's 9.6,
    PERF.md section 6, PR 61); from this tree the same ``astype`` is no
    operation and the products take the same bits. One program over
    the whole tree (leaf by leaf is a compile a leaf: ``init``), each
    committed leaf's sharding kept; a tree that is already so, as every
    tree is where ``cfg.dtype`` is float32, is handed back itself."""
    tree = jax.tree_util
    wants = dtypes_at_rest(params, cfg)
    if all(x.dtype == want for x, want in
           zip(tree.tree_leaves(params), tree.tree_leaves(wants))):
        return params
    kept = tree.tree_map(
        lambda x: x.sharding if getattr(x, "committed", False) else None,
        params)
    return jax.jit(
        lambda p: tree.tree_map(lambda x, want: x.astype(want), p, wants),
        out_shardings=kept)(params)


def _layernorm(x, scale):
    x32 = x.astype(jnp.float32)
    mu = x32.mean(-1, keepdims=True)
    var = x32.var(-1, keepdims=True)
    out = (x32 - mu) * jax.lax.rsqrt(var + 1e-5) * scale
    return out.astype(x.dtype)


def _constrain(x, logical, mesh, rules):
    if mesh is None:
        return x
    spec = logical_to_mesh_axes(logical, rules)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def _block(x, p, cfg: GPTConfig, attend, mesh=None, rules=None,
           mlp_remat: bool = False):
    """The one transformer layer: norm -> attention sublayer -> residual
    -> norm -> MLP -> residual, on activations [batch, rows, d_model]
    (a decode step is rows = 1). p: per-layer slice of the stacked block
    params. ``attend(h, p)`` is the mode's attention sublayer: it
    returns the attention output already projected back to d_model and
    whatever the mode carries out of the layer (nothing when training,
    the updated layer pools in a decode step, a prefill chunk's K/V).
    Returns (x, carried)."""
    dt = cfg.dtype
    o, carried = attend(_layernorm(x, p["ln1"]), p)
    x = x + _constrain(o, ("batch", "seq", "embed_act"), mesh, rules)

    def mlp(xin):
        h2 = _layernorm(xin, p["ln2"])
        ff = jax.nn.gelu(jnp.einsum("bsm,mf->bsf", h2, p["wi"].astype(dt)))
        ff = _constrain(ff, ("batch", "seq", "mlp"), mesh, rules)
        return jnp.einsum("bsf,fm->bsm", ff, p["wm"].astype(dt))

    if mlp_remat:
        mlp = jax.checkpoint(mlp)
    x = x + _constrain(mlp(x), ("batch", "seq", "embed_act"), mesh, rules)
    return x, carried


def _qkv(h, p, dt):
    """This layer's projections of h [b, rows, m], each
    [b, rows, heads (kv heads for k and v), head_dim]."""
    return (jnp.einsum("bsm,mhd->bshd", h, p["wq"].astype(dt)),
            jnp.einsum("bsm,mhd->bshd", h, p["wk"].astype(dt)),
            jnp.einsum("bsm,mhd->bshd", h, p["wv"].astype(dt)))


def _causal_sublayer(h, p, cfg: GPTConfig, mesh, rules):
    """The training path's attention: causal over the rows themselves,
    nothing carried out of the layer."""
    dt = cfg.dtype
    if cfg.use_flash:
        # Heads-major end to end: q/k/v are emitted in the kernel's native
        # [b, heads, seq, d] layout, so there are no transposes around the
        # kernel AND autodiff saves ONE copy of each tensor (kernel
        # residuals == the weight-grad einsum inputs). (A fused qkv
        # concat-matmul was measured SLOWER — the per-layer concat breaks
        # XLA's cast/einsum fusion — so the three einsums stay separate.)
        q = jnp.einsum("bsm,mhd->bhsd", h, p["wq"].astype(dt))
        kk = jnp.einsum("bsm,mhd->bhsd", h, p["wk"].astype(dt))
        v = jnp.einsum("bsm,mhd->bhsd", h, p["wv"].astype(dt))
        q = _constrain(q, ("batch", "heads", "seq", None), mesh, rules)
        attn = functools.partial(flash_attention, causal=True,
                                 block_size=cfg.flash_block, layout="bhsd")
        if mesh is not None:
            # A Mosaic kernel cannot be partitioned automatically: each
            # device runs it on its own batch rows (data axes) and heads
            # (tp) with the whole sequence, which causal attention needs.
            spec = logical_to_mesh_axes(("batch", "heads", None, None),
                                        rules)
            attn = jax.shard_map(attn, mesh=mesh, in_specs=(spec,) * 3,
                                 out_specs=spec, check_vma=False)
        o = attn(q, kk, v)
        return jnp.einsum("bhsd,hdm->bsm", o, p["wo"].astype(dt)), None
    q, kk, v = _qkv(h, p, dt)
    q = _constrain(q, ("batch", "seq", "heads", None), mesh, rules)
    o = causal_attention(q, kk, v)
    return jnp.einsum("bshd,hdm->bsm", o, p["wo"].astype(dt)), None


# Activation rules: batch over data axes, seq over sp, hidden replicated
# (hidden sharding follows the matmul outputs: heads/mlp over tp).
ACT_RULES = {"embed_act": None}


def _embed(params, tokens, positions, cfg: GPTConfig, mesh=None, rules=None):
    """Token plus learned position embeddings, [batch, rows, d_model].
    ``positions`` indexes wpe's rows: an int array that broadcasts
    against ``tokens``, or a slice."""
    dt = cfg.dtype
    wte = params["wte"].astype(dt)
    if mesh is not None:
        tokens = _constrain(tokens, ("batch", "seq"), mesh, rules)
        # Replicate the table before the lookup: a gather from a
        # vocab/embed-sharded table cannot yield batch-sharded output
        # without XLA's "involuntary full rematerialization" of the
        # activation; one hoisted all-gather of the (modest) table is the
        # cheap way to cross that sharding boundary. The logits matmul
        # in _head still consumes the sharded table.
        wte = jax.lax.with_sharding_constraint(
            wte, NamedSharding(mesh, P(None, None)))
    x = wte[tokens] + params["wpe"].astype(dt)[positions]
    return _constrain(x, ("batch", "seq", "embed_act"), mesh, rules)


def _head(params, x, cfg: GPTConfig, mesh=None, rules=None):
    """Final norm and the tied vocabulary head: [batch, rows, vocab]."""
    x = _layernorm(x, params["ln_f"])
    logits = jnp.einsum("bsm,vm->bsv", x, params["wte"].astype(cfg.dtype))
    return _constrain(logits, ("batch", "seq", "vocab"), mesh, rules)


def forward(params, tokens, cfg: GPTConfig, mesh: Optional[Mesh] = None,
            rules: Optional[dict] = None) -> jax.Array:
    """tokens [b, s] int32 -> logits [b, s, vocab] (cfg.dtype)."""
    rules = {**DEFAULT_RULES, **ACT_RULES, **(rules or {})}
    x = _embed(params, tokens, slice(tokens.shape[1]), cfg, mesh, rules)
    attend = functools.partial(_causal_sublayer, cfg=cfg, mesh=mesh,
                               rules=rules)
    block_fn = functools.partial(_block, cfg=cfg, attend=attend, mesh=mesh,
                                 rules=rules)
    if cfg.remat and cfg.remat_policy == "mlp_only":
        # Checkpoint lives INSIDE the block (around the MLP); the block
        # itself — attention included — keeps its residuals.
        block_fn = functools.partial(block_fn, mlp_remat=True)
    elif cfg.remat:
        cp = jax.checkpoint_policies
        name = cfg.remat_policy
        if name == "dots_flash" and not (
                cfg.use_flash and jax.default_backend() == "tpu"):
            # Without the Pallas kernel (flash disabled, or a backend
            # where flash_attention lowers the blockwise-jnp reference
            # instead), dots_saveable would save O(seq^2) per-block
            # score/probability matmul outputs; those paths need the
            # aggressive policy.
            name = "dots_no_batch"
        policies = {
            "dots_no_batch": cp.dots_with_no_batch_dims_saveable,
            "dots": cp.dots_saveable,
            "dots_flash": cp.save_from_both_policies(
                cp.dots_saveable, cp.save_only_these_names("flash")),
        }
        if name not in policies:
            raise ValueError(
                f"remat_policy={cfg.remat_policy!r}; valid: "
                f"{sorted(policies)} or 'mlp_only'")
        block_fn = jax.checkpoint(block_fn, policy=policies[name])

    x, _ = jax.lax.scan(block_fn, x, params["blocks"])
    return _head(params, x, cfg, mesh, rules)


# ---------------------------------------------------------------------------
# Inference entry points (continuous-batching engine, llm/engine.py).
#
# Reference layer map: the reference runtime serves external inference
# engines; here the decode path is native. forward_prefill_chunk runs a
# span of a prompt against whatever of it already sits in the paged pool
# (llm/kv_cache.py) and WRITES the span's K/V into it; forward_step runs
# the next rows of every in-flight sequence against that pool through
# the paged-attention kernel (ops/pallas/paged_fetch). Both are the
# training layer (_block) around an attention sublayer of their own, on
# the training params — there is no separate "inference model".
# ---------------------------------------------------------------------------


def _greedy_ids(logits):
    """Argmax over the vocabulary, int32: the token a greedy lane takes
    (llm/sampling.py's rule, first index of the maximum on ties)."""
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def _paged_sublayer(h, p, layer, k_pool, v_pool, block_tables, context_lens,
                    q_lens, slot_blocks, slot_offsets, cfg: GPTConfig):
    """A decode step's attention at layer ``layer`` (traced: the layers
    run under one scan) of the stored pools [L, num_blocks, block_size,
    kv_heads * head_dim]: project every row, write its K/V in place at
    (layer, slot_blocks, slot_offsets), THEN attend over the lane's
    block table in the written pools as they are stored: write-then-
    attend, so a row sees itself; the kernel
    (ops/pallas/paged_fetch.py, under the name ``paged_decode``) copies
    its pages out of the stacked pools itself, so the step makes no
    other pass over them. Carries the updated pools out of the layer."""
    dt = cfg.dtype
    B, Q = h.shape[:2]
    hkv, group = cfg.kv_heads, cfg.n_head // cfg.kv_heads
    q, k_tok, v_tok = _qkv(h, p, dt)
    # Real rows have unique slots by construction; padding rows and
    # padded lanes collide on the scratch block, which is never read
    # unmasked.
    k_pool = k_pool.at[layer, slot_blocks, slot_offsets].set(
        k_tok.astype(k_pool.dtype).reshape(B, Q, -1))
    v_pool = v_pool.at[layer, slot_blocks, slot_offsets].set(
        v_tok.astype(v_pool.dtype).reshape(B, Q, -1))
    # No window: every row sees from the table's first key on (row i
    # sees from ``starts + i``, so zeros would hide a verify step's
    # first i keys from its row i).
    o = paged_attention_stored(
        q.reshape(B, Q, hkv, group, cfg.head_dim), k_pool, v_pool, layer,
        block_tables, context_lens, q_lens,
        jnp.full_like(context_lens, -Q), name="paged_decode")
    o = jnp.einsum("bqhd,hdm->bqm",
                   o.reshape(B, Q, cfg.n_head, cfg.head_dim),
                   p["wo"].astype(dt))
    return o, (k_pool, v_pool)


# What forward_step appends to its ids: 1000 x the share of the batch's
# live cache pages that the paged kernel fetches in whole runs.
COUNTERS = ("kv_pages_in_runs_x1000",)


def forward_step(params, packed, k_pool, v_pool, *, q: int,
                 cfg: GPTConfig, firsts=None):
    """One decode step for a batch of in-flight sequences: ``q`` rows a
    lane against the paged pool in ONE batched paged-attention forward.

    Row 0 of a lane is its current (last sampled, not yet written) token
    and rows 1..q-1 a proposed continuation (speculative verify); plain
    decoding is q = 1. The engine decides the q_lens[lane] leading rows
    (accepting or rejecting proposals); the pool writes of rejected rows
    are rolled back host-side (kv_cache.truncate) — garbage beyond
    context_lens is never attended.

    Args:
      packed: [b, W] int32, the step's whole bookkeeping in ONE array
        that the engine keeps current (models/seam.py
        ``step_columns``), taken apart here by static slices into:
      tokens / positions: [b, q] int32 — each row's token and absolute
        position. Rows past q_lens[lane], and every row of a padded
        lane, are padding: their slots point at the pool's reserved
        scratch block 0 and their logits are garbage the engine never
        reads.
      block_tables: [b, max_nb] int32, 0-padded.
      context_lens: [b] int32 — resident tokens per lane INCLUDING its
        q_lens real rows (1 for a padded lane).
      q_lens: [b] int32 — real rows per lane (1 = plain decode lane).
      slot_blocks / slot_offsets: [b, q] int32 — the pool block and
        in-block offset each row is written at.
      k_pool / v_pool: [L, num_blocks, block_size, kv_heads * head_dim]
        (donate these in the caller's jit: they ride in the layer
        scan's carry, so steady-state decode writes the step's rows
        into the donated buffers and copies nothing of the stack).
      q: rows a lane, a Python int (a shape, not a value).
      firsts: [b] int32 or None — a lane's row-0 token where not
        negative, decided on the device by the chunk program queued
        before this step (models/seam.py ``unpack_step``).

    Returns (logits [b, q, vocab], ids [b + 1, q] int32, k_pool,
    v_pool): rows 0..b-1 of ``ids`` are the argmax of each logits row
    (the first index of the maximum, as ``numpy.argmax`` on the same
    row), so a greedy lane's tokens are decided here and the host
    fetches ids, not logits; row b is ``COUNTERS``.
    """
    (tokens, positions, block_tables, context_lens, q_lens, slot_blocks,
     slot_offsets, _) = unpack_step(packed, q, firsts=firsts)
    x = _embed(params, tokens, positions, cfg)

    def layer(carry, xs):
        x, k_pool, v_pool = carry
        p, i = xs
        attend = functools.partial(
            _paged_sublayer, layer=i, k_pool=k_pool, v_pool=v_pool,
            block_tables=block_tables, context_lens=context_lens,
            q_lens=q_lens, slot_blocks=slot_blocks,
            slot_offsets=slot_offsets, cfg=cfg)
        x, (k_pool, v_pool) = _block(x, p, cfg, attend)
        return (x, k_pool, v_pool), None

    (x, k_pool, v_pool), _ = jax.lax.scan(
        layer, (x, k_pool, v_pool),
        (params["blocks"], jnp.arange(cfg.n_layer)))
    logits = _head(params, x, cfg)
    in_runs = kv_pages_in_runs_x1000(block_tables, context_lens, k_pool,
                                     v_pool, score_rows=q * cfg.n_head)
    ids = jnp.concatenate([
        _greedy_ids(logits),
        jnp.broadcast_to(in_runs, (len(COUNTERS), q)).astype(jnp.int32)])
    return logits, ids, k_pool, v_pool


def _chunk_attention(q, k_tok, v_tok, k_ctx, v_ctx, ctx_len):
    """Attention for one prefill chunk over [pool context ++ chunk].

    q / k_tok / v_tok: [b, c, heads(kv), d] — this chunk's projections.
    k_ctx / v_ctx: [b, S, kv_heads, d] — the sequence's pool slots
    gathered from its block table (S = table_len * block_size; only the
    first ctx_len hold real tokens). The key axis is the concatenation
    [S pool slots ++ c chunk slots]; query i sits at absolute position
    ctx_len + i, so the mask admits pool slots < ctx_len (all strictly
    before any query) and chunk slots j <= i (causal within the chunk).
    Padded chunk tails are keyed AFTER every real query index and thus
    never attended. Same f32-softmax / NEG_INF discipline as
    ops.attention.causal_attention.
    """
    b, c, hq, d = q.shape
    S = k_ctx.shape[1]
    k = jnp.concatenate([k_ctx.astype(q.dtype), k_tok], axis=1)
    v = jnp.concatenate([v_ctx.astype(q.dtype), v_tok], axis=1)
    hk = k.shape[2]
    if hq != hk:
        rep = hq // hk
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * (d ** -0.5)
    qi = jnp.arange(c)[:, None]
    kp = jnp.arange(S + c)[None, :]
    mask = jnp.where(kp < S, kp < ctx_len, (kp - S) <= qi)
    logits = jnp.where(mask[None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _chunk_sublayer(h, p, layer, k_pool, v_pool, block_table, ctx_len,
                    cfg: GPTConfig):
    """A prefill chunk's attention at layer ``layer`` over [its
    sequence's pool context ++ the chunk]; the stored pools are read
    only. Carries the chunk's own K/V out of the layer."""
    dt = cfg.dtype
    hkv, hd = cfg.kv_heads, cfg.head_dim
    q, k_tok, v_tok = _qkv(h, p, dt)
    # This sequence's pool context, gathered by table from the stacked
    # pool: [nb, BS, hkv * d], already in slot order (S = nb * BS slots,
    # 0 for an empty table).
    slots = block_table.shape[0] * k_pool.shape[2]
    k_ctx = k_pool[layer, block_table].reshape(1, slots, hkv, hd)
    v_ctx = v_pool[layer, block_table].reshape(1, slots, hkv, hd)
    o = _chunk_attention(q, k_tok, v_tok, k_ctx, v_ctx, ctx_len)
    return jnp.einsum("bshd,hdm->bsm", o, p["wo"].astype(dt)), (k_tok, v_tok)


def _chunk_layers(params, tokens, positions, k_pool, v_pool, block_table,
                  ctx_len, cfg: GPTConfig):
    """A span's layers: ``tokens`` [1, n] at ``positions`` [n] against
    the sequence's context in the pools, which are only read. Returns
    (x [1, n, d_model] before the final norm and head, k
    [L, 1, n, kv_heads, head_dim], v like k): the span's own K/V."""
    x = _embed(params, tokens, positions, cfg)

    def layer(x, xs):
        p, i = xs
        attend = functools.partial(_chunk_sublayer, layer=i, k_pool=k_pool,
                                   v_pool=v_pool, block_table=block_table,
                                   ctx_len=ctx_len, cfg=cfg)
        return _block(x, p, cfg, attend)

    x, (k, v) = jax.lax.scan(
        layer, x, (params["blocks"], jnp.arange(cfg.n_layer)))
    return x, k, v


def forward_prefill_chunk(params, tokens, k_pool, v_pool, table,
                          cfg: GPTConfig):
    """One span of a prompt, as ONE program: a whole cold prompt, or
    one chunk of an incremental prefill. It attends over the resident
    context, writes its own K/V into the pools and hands back the one
    row the host may need.

    Sarathi-style chunked admission and prefix-cache hits both land
    here: run ``tokens`` [1, n] (n a whole number of blocks, the real
    tokens first) whose context — earlier prompt chunks, possibly
    computed by ANOTHER request and shared through the prefix pool —
    already sits in the paged pool.

    Args:
      k_pool / v_pool: [L, num_blocks, block_size, kv_heads * head_dim]
        (donate these in the caller's jit, as the decode step's).
      table: int32 ``[block table (nb) | destination (n / block_size) |
        ctx_len | last]`` (models/seam.py ``pack_span``). The block
        table is
        0-padded like decode's. It may be EMPTY (nb = 0, with ctx_len
        0): a span with no resident context attends over itself alone,
        at the cost of a plain causal forward, and row ``last`` is
        ``forward``'s on its tokens. The destination names the blocks
        the span's n tokens are written to (granted to this request and
        private: shared blocks must be COW-split before). ``ctx_len``:
        tokens already resident. ``last``: the index of the span's last
        real token; the rows after it are padding, and are written as
        zeros. Token i sits at position min(ctx_len + i, max_seq - 1).

    Every layer READS the pools as they came in; the span is written
    after the last layer (models/seam.py ``scatter_span``), so a
    destination block may be one the block table still names.

    Returns (row [vocab]: the logits of token ``last``; id: their
    argmax, int32, which a greedy request takes as its token; k_pool,
    v_pool). The head runs on that one row.
    """
    n = tokens.shape[1]
    block_table, dest, ctx_len, last = unpack_span(table, n,
                                                   k_pool.shape[2])
    positions = jnp.minimum(ctx_len + jnp.arange(n, dtype=jnp.int32),
                            cfg.max_seq - 1)
    x, k, v = _chunk_layers(params, tokens, positions, k_pool, v_pool,
                            block_table, ctx_len, cfg)
    k_pool, v_pool = scatter_span((k_pool, v_pool), (k[:, 0], v[:, 0]),
                                  dest, last + 1)
    row = _head(params, jax.lax.dynamic_slice_in_dim(x, last, 1, axis=1),
                cfg)[0, 0]
    return row, _greedy_ids(row), k_pool, v_pool


def cost_shape(cfg: GPTConfig) -> dict:
    """The cost description util/perfmodel.py prices this model's steps
    from: matmul weights a token passes (W) and the vocabulary head's
    share of them, the attention coefficient a context position, parameters in all and as stored, KV elements a
    token."""
    m, f, L = cfg.d_model, cfg.ff, cfg.n_layer
    h, hk, d = cfg.n_head, cfg.kv_heads, cfg.head_dim
    per_layer = m * h * d + 2 * m * hk * d + h * d * m + 2 * m * f
    n = cfg.num_params()
    return {
        "matmul_weights": L * per_layer + cfg.vocab_size * m,
        "head_weights": cfg.vocab_size * m,   # of those, the head's
        "attn_per_ctx": 4.0 * m * L,     # flops per token per context pos
        "chunk_attn_per_ctx": 4.0 * m * L,   # a chunk's rows cost the same
        "chunk_ctx_ops": 0.0,            # a cached key is read as it is
        "attn_windows": (),              # no layer with a window
        "num_params": n,
        "streamed_params": lambda rows: n,   # every weight, every step
        "param_bytes": jnp.dtype(cfg.dtype).itemsize,   # as served
        "kv_bytes_per_token": 2 * L * hk * d,   # k+v elements per token
        "m": m, "L": L,
    }


def serving(cfg: GPTConfig):
    """This model behind the serving seam (models/seam.py): one
    kind of layer, every layer keeps every token."""
    full = keys_and_values("full", range(cfg.n_layer), cfg.kv_heads,
                           cfg.head_dim, None, cfg.dtype)
    return Serving(init=init, step=forward_step,
                   chunk=forward_prefill_chunk,
                   at_rest=functools.partial(params_at_rest, cfg=cfg),
                   kinds=(full,),
                   cost=cost_shape(cfg), max_seq=cfg.max_seq,
                   vocab_size=cfg.vocab_size, counters=COUNTERS)


@jax.custom_vjp
def _xent(logits, targets):
    """Mean next-token cross-entropy with a hand-written VJP.

    Two reasons not to let autodiff handle this:
      * the f32 upcasts stay FUSED (a whole-[b,s,vocab] f32 copy is
        3.3 GB at the bench config);
      * the backward emits dlogits in the LOGITS' dtype (bf16), not f32 —
        at the bench shape that halves the single biggest transient of
        the whole step (3.2 GB -> 1.6 GB), which is what lets the
        remat-free configuration fit in one v5e's HBM.
    """
    logz = jax.scipy.special.logsumexp(logits.astype(jnp.float32), axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return (logz - gold.astype(jnp.float32)).mean()


def _xent_fwd(logits, targets):
    logz = jax.scipy.special.logsumexp(logits.astype(jnp.float32), axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return (logz - gold.astype(jnp.float32)).mean(), (logits, targets, logz)


def _xent_bwd(res, g):
    logits, targets, logz = res
    n = logz.size
    # softmax - onehot, elementwise-fused in f32, landed in logits dtype.
    p = jnp.exp(logits.astype(jnp.float32) - logz[..., None])
    onehot = jax.nn.one_hot(targets, logits.shape[-1], dtype=jnp.float32)
    dlogits = ((p - onehot) * (g / n)).astype(logits.dtype)
    return dlogits, None


_xent.defvjp(_xent_fwd, _xent_bwd)


def loss_fn(params, tokens, cfg: GPTConfig, mesh=None, rules=None):
    """Next-token cross-entropy (targets = tokens shifted left)."""
    logits = forward(params, tokens[:, :-1], cfg, mesh, rules)
    return _xent(logits, tokens[:, 1:])


def make_train_step(cfg: GPTConfig, optimizer, mesh: Optional[Mesh] = None,
                    rules: Optional[dict] = None, donate: bool = True):
    """Build the compiled SPMD train step: (state, tokens) -> (state, metrics).

    state = {"params": ..., "opt_state": ..., "step": i}. With a mesh, XLA
    partitions per the param/activation shardings and inserts gradient
    reductions automatically — the in-graph equivalent of the reference's
    NCCL allreduce in torch DDP
    (/root/reference/python/ray/train/torch/config.py:106).
    """

    # The function's name is the program's on a device trace's ``XLA
    # Modules`` line: ``jit_train_step``.
    def train_step(state, tokens):
        loss, grads = jax.value_and_grad(loss_fn)(
            state["params"], tokens, cfg, mesh, rules
        )
        updates, opt_state = optimizer.update(
            grads, state["opt_state"], state["params"]
        )
        # In the function: only a trainer needs optax installed.
        import optax

        params = optax.apply_updates(state["params"], updates)
        new_state = {"params": params, "opt_state": opt_state,
                     "step": state["step"] + 1}
        return new_state, {"loss": loss}

    donate_argnums = (0,) if donate else ()
    return jax.jit(train_step, donate_argnums=donate_argnums)


def params_pspecs(cfg: GPTConfig, rules=None) -> dict:
    """PartitionSpec pytree matching init()'s param tree."""
    rules = {**DEFAULT_RULES, **(rules or {})}
    is_ann = lambda x: isinstance(x, tuple) and all(
        isinstance(e, (str, type(None))) for e in x)
    return jax.tree_util.tree_map(
        lambda ann: logical_to_mesh_axes(ann, rules), param_axes(cfg),
        is_leaf=is_ann)


def shard_state(state, mesh: Mesh, cfg: GPTConfig, rules=None):
    """device_put a train state with param-aligned shardings. Optimizer
    moments mirror params *by tree structure* (see parallel.sharding
    shard_like), so wq/wk/wv — equal shapes, different specs — stay correct.
    """
    pspec = params_pspecs(cfg, rules)
    params = jax.tree_util.tree_map(
        lambda leaf, spec: jax.device_put(leaf, NamedSharding(mesh, spec)),
        state["params"], pspec)
    opt_state = shard_like(state["opt_state"], state["params"], pspec, mesh)
    return {"params": params, "opt_state": opt_state,
            "step": jax.device_put(state["step"], NamedSharding(mesh, P()))}
