"""Bytes and operations of the Granite 4.0-H configurations' three
kernels, for ``hybrid_ssm_update_roofline_pct``,
``hybrid_ssm_scan_roofline_pct`` and ``hybrid_moe_roofline_pct``: the
work that was NEEDED, from this configuration's own fields (the
published key names: ``layer_types``, ``mamba_n_heads``, ...) and the
step's counters, whatever implements it.

The two state-space kernels run the recurrence ``ssm_cost`` counts, so
its functions are fed this configuration's fields under the names they
read (``as_ssm_fields``): the same count of the same work as the
accepted state-space cell is read by (each live state in once and out
once; a scanned row's ``x``, ``y``, ``B``, ``C``, ``dt`` and the span's
state once each way).

An expert here is SwiGLU at the full hidden size: an assignment (one
token to one held expert) passes gate and up ``[hidden, width]`` and
down ``[width, hidden]``: ``6 x hidden x width`` operations; an expert
that got any token has its three matrices read once: ``3 x hidden x
width`` parameters at the served width. EVERY layer has an expert
block. The router, the shared MLP and the rows in and out are outside
the grouped kernel and left out, so the share errs low."""

from benchmark import ssm_cost


def as_ssm_fields(fields: dict) -> dict:
    """This configuration's fields under the names ``ssm_cost`` reads:
    a letter ``M`` a Mamba-2 layer."""
    return {
        "hybrid_override_pattern": "".join(
            "M" if kind == "mamba" else "*" for kind in fields["layer_types"]),
        "mamba_num_heads": fields["mamba_n_heads"],
        "mamba_head_dim": fields["mamba_d_head"],
        "ssm_state_size": fields["mamba_d_state"],
        "n_groups": fields["mamba_n_groups"],
        "chunk_size": fields["mamba_chunk_size"],
    }


def update_bytes(lanes: float, fields: dict) -> float:
    return ssm_cost.update_bytes(lanes, as_ssm_fields(fields))


def update_operations(lanes: float, fields: dict) -> float:
    return ssm_cost.update_operations(lanes, as_ssm_fields(fields))


def scan_bytes(tokens: float, executions: float, fields: dict) -> float:
    return ssm_cost.scan_bytes(tokens, executions, as_ssm_fields(fields))


def scan_operations(tokens: float, fields: dict) -> float:
    return ssm_cost.scan_operations(tokens, as_ssm_fields(fields))


def _expert(fields: dict) -> tuple:
    return (fields["hidden_size"], fields["intermediate_size"],
            len(fields["layer_types"]))


def moe_operations(assignments_a_layer: float, fields: dict) -> float:
    """Floating-point operations of one step's grouped products, all
    layers: ``assignments_a_layer`` tokens-times-held-experts each."""
    m, f, layers = _expert(fields)
    return 6.0 * m * f * assignments_a_layer * layers


def moe_bytes_read(experts_hit_a_layer: float, fields: dict,
                   param_bytes: int = 2) -> float:
    """Bytes of expert weights one step has to read, all layers: the
    held experts that got a token, once each."""
    m, f, layers = _expert(fields)
    return 3.0 * m * f * param_bytes * experts_hit_a_layer * layers
