"""Operations and bytes an algorithm needs, from shapes alone.

The yardstick's own arithmetic: nothing here is read from the program,
so a PR that changes ``util/perfmodel.py`` cannot move a benchmark
number. ``shape`` is the ``fields`` object of a configuration file
(``d_model``, ``n_layer``, ``n_head``, ``vocab_size``, ``max_seq`` and
optionally ``n_kv_head`` / ``d_ff``).

Conventions, chosen so that a share of a peak cannot pass 100%:
  * only matrix multiplications are counted (2 operations a
    multiply-add); layer norms, GELU, softmax and the optimizer are not;
  * attention is counted causally: a query at position i needs i + 1
    keys, so a whole sequence needs T * (T + 1) / 2 score columns a
    head, half of the dense square;
  * recomputation (remat, the flash backward's second pass over the
    scores) is not counted: it is work the implementation chose.
"""

from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of ``device_kind``. An unknown
    kind is an error: pricing it as another chip would publish a wrong
    share."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r} in "
            f"benchmark/peaks.json; add them with their source")
    return table[device_kind]


def _dims(shape: dict) -> tuple:
    m, L, h = shape["d_model"], shape["n_layer"], shape["n_head"]
    hk = shape.get("n_kv_head") or h
    d = m // h
    f = shape.get("d_ff") or 4 * m
    return m, L, h, hk, d, f


def matmul_weights(shape: dict) -> int:
    """Weights that a token is multiplied by: the blocks' projections
    and MLPs and the (tied) output head. The embedding lookups are
    gathers, not multiplications."""
    m, L, h, hk, d, f = _dims(shape)
    per_layer = m * h * d + 2 * m * hk * d + h * d * m + 2 * m * f
    return L * per_layer + shape["vocab_size"] * m


def train_flops_per_token(shape: dict, seq: int) -> float:
    """Forward + backward operations one trained token needs: 6 per
    matmul weight, and causal attention (scores and weighted values,
    forward 4 * d a key and head, backward twice that) over a mean
    context of (seq + 1) / 2 keys."""
    m, L, h, hk, d, f = _dims(shape)
    attn = 3 * 4.0 * h * d * L * (seq + 1) / 2.0
    return 6.0 * matmul_weights(shape) + attn


def flash_flops_per_step(shape: dict, batch: int, seq: int) -> float:
    """What the attention kernels of one training step need, forward
    and backward, over all layers: causal scores and weighted values
    forward (4 * d a query-key pair), and the backward's four products
    plus the one recomputation of the scores that defines the flash
    backward (10 * d a pair)."""
    m, L, h, hk, d, f = _dims(shape)
    pairs = batch * h * seq * (seq + 1) / 2.0
    return (4.0 + 10.0) * d * pairs * L


def kv_bytes_per_token(shape: dict, dtype_bytes: int = 2) -> int:
    """Keys and values one context token holds over all layers."""
    m, L, h, hk, d, f = _dims(shape)
    return 2 * L * hk * d * dtype_bytes
