"""Bytes and operations of the four-stream residual path
(manifold-constrained hyper-connections), for ``mhc_chunk_roofline_pct``:
the work that was NEEDED, from the configuration's fields, whatever
implements it.

A token's residual is ``hc_mult`` = n streams of ``hidden_size`` = C
values. Around each of a layer's two sublayers the path has to read the
n streams and write them once (the mixing ``X' = H_res X + H_post y``)
and to write one stream for the sublayer and read one back (``h`` out,
``y`` in): ``(2 n C + 2 C)`` values at the served width. Whether the
read for the coefficients and the read for the mixing are one pass or
two, whether a sublayer's post-mix and the next one's pre-mix are one
kernel, and the coefficients' own few hundred bytes are the
implementation's: a second pass over ``X`` is not needed work, so the
share errs low. Operations: the product with ``Phi`` (2 n C (n^2 + 2n)),
the mean square (2 n C), the pre-mix (2 n C) and the post-mix
(2 (n^2 + n) C); the Sinkhorn chain's few thousand are left out. ~12
operations a byte, far under the chip's ridge: the memory side decides.
"""


def _sizes(fields: dict) -> tuple:
    return fields["hc_mult"], fields["hidden_size"], \
        2 * fields["num_hidden_layers"]


def bytes_per_token(fields: dict, act_bytes: int = 2) -> int:
    """Bytes one token's rows have to move on the residual path, every
    sublayer of every layer held."""
    n, C, sublayers = _sizes(fields)
    return sublayers * (2 * n * C + 2 * C) * act_bytes


def operations_per_token(fields: dict) -> float:
    """Floating-point operations of one token's coefficients and mixes,
    every sublayer."""
    n, C, sublayers = _sizes(fields)
    return sublayers * (2.0 * n * C * (n * n + 2 * n) + 4.0 * n * C
                        + 2.0 * (n * n + n) * C)
