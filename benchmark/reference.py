"""The plain references that decide ``correct``, copied from PR 21's
``chip_smoke.py`` so that no later PR can move them.

Both run outside the timed window, at the configuration's own width, on
the device the cell runs on. Tolerances are stated here with what the
chip showed when they were set (PERF.md section 6, PR 21).
"""

from __future__ import annotations

import functools
import math

LOSS_START_TOL = 0.5    # first loss vs ln(vocab): random init [0.16]
LOSS_TOL = 1e-3         # kernel loss vs dense jnp loss, batch 0 [3e-5];
                        # the loss moves ~0.006 over three steps, so this
                        # still tells an update from none, and a lower
                        # precision in the kernel from the stated one
LOGIT_MARGIN_EPS = 0.05  # top-two margin under which bf16 may flip argmax


def dense_loss(params, tokens, cfg, mesh) -> float:
    """The training loss of ``tokens`` on ``params`` through dense
    ``jax.numpy`` attention: the configuration with its kernel off."""
    import dataclasses

    import jax

    from ray_tpu.models import gpt

    dense = dataclasses.replace(cfg, use_flash=False)
    return float(jax.jit(
        lambda p, t: gpt.loss_fn(p, t, dense, mesh))(params, tokens))


def train_checks(losses: list, dense: float, vocab: int) -> list:
    """[(ok, what)] for a training run: ``losses`` are every step's
    loss, the first taken on the parameters ``dense`` was."""
    want = math.log(vocab)
    return [
        (len(losses) > 1 and all(math.isfinite(x) for x in losses),
         f"{len(losses)} steps, every loss finite"),
        (abs(losses[0] - want) < LOSS_START_TOL,
         f"first loss {losses[0]:.4f} within {LOSS_START_TOL} of "
         f"ln(vocab) {want:.4f}"),
        (abs(losses[0] - dense) < LOSS_TOL,
         f"kernel loss equals the dense jnp loss on batch 0 within "
         f"{LOSS_TOL} (diff {abs(losses[0] - dense):.6f})"),
        (losses[-1] < losses[0],
         f"last loss {losses[-1]:.4f} below the first {losses[0]:.4f}"),
    ]


@functools.lru_cache(maxsize=None)
def _forward(cfg):
    import jax

    from ray_tpu.models import gpt

    return jax.jit(lambda p, t: gpt.forward(p, t, cfg))


def greedy_check(params, cfg, prompt: list, got: list) -> tuple:
    """Greedy decoding over ``gpt.forward`` (dense attention, no cache),
    teacher-forced with the engine's own tokens: one causal forward
    pass over prompt + answer scores every position at once. Returns
    (exact matches, flips, worst reference margin among the flips)."""
    import jax.numpy as jnp
    import numpy as np

    seq = list(prompt) + list(got)
    # One program for every comparison: the model's whole context.
    buf = np.zeros((1, max(cfg.max_seq, len(seq))), np.int32)
    buf[0, :len(seq)] = seq
    logits = np.asarray(_forward(cfg)(params, jnp.asarray(buf))[
        0, len(prompt) - 1:len(seq) - 1], np.float32)
    exact = flips = 0
    worst = 0.0
    for row, tok in zip(logits, got):
        if int(row.argmax()) == tok:
            exact += 1
        else:
            # Tolerated only where bf16 cannot tell the reference's
            # best token from the engine's.
            flips += 1
            worst = max(worst, float(row.max() - row[tok]))
    return exact, flips, worst


def serve_checks(what: str, exact: int, flips: int, worst: float,
                 n: int) -> list:
    return [(n > 0 and exact + flips == n and worst < LOGIT_MARGIN_EPS,
             f"{what} matches plain greedy decoding over "
             f"gpt.forward: {exact}/{n} tokens equal, {flips} flips, "
             f"worst reference margin {worst:.4f} (limit "
             f"{LOGIT_MARGIN_EPS})")]
