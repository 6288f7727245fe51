"""Operations and bytes of the routed experts' grouped product in one
decode step of the DeepSeek-V3 block's families (Kimi-K2, Xing4.0), for
``moe_routed_roofline_pct``: the work that was NEEDED, from what the
step routed, not what a kernel happened to do. ``moe_cost`` is the same
count from Laguna's fields (``mlp_layer_types``); this family says its
routed layers as ``num_hidden_layers`` - ``first_k_dense_replace``.

An assignment (one token to one expert held here) passes the expert's
three matrices (gate and up ``[hidden, width]``, down ``[width,
hidden]``): ``6 x hidden x width`` operations. An expert that got any
token has its three matrices read once: ``3 x hidden x width``
parameters at the served width. Rows in and out are small beside them
and left out, so the share errs low.
"""


def _sizes(fields: dict) -> tuple:
    return (fields["hidden_size"], fields["moe_intermediate_size"],
            fields["num_hidden_layers"] - fields["first_k_dense_replace"])


def operations(assignments_a_layer: float, fields: dict) -> float:
    """Floating-point operations of one step's grouped products, all
    routed layers: ``assignments_a_layer`` tokens-times-held-experts
    each."""
    m, f, routed = _sizes(fields)
    return 6.0 * m * f * assignments_a_layer * routed


def bytes_read(experts_hit_a_layer: float, fields: dict,
               param_bytes: int = 2) -> float:
    """Bytes of expert weights one step has to read, all routed layers:
    the held experts that got a token, once each."""
    m, f, routed = _sizes(fields)
    return 3.0 * m * f * param_bytes * experts_hit_a_layer * routed
