"""Operations and bytes of the routed experts' grouped products at the
LATENT width in one decode step, for ``latent_moe_roofline_pct``: the
work that was NEEDED, from what the step routed to the experts held
here, not what a kernel happened to do.

An expert of this family is not gated: two matrices, ``[moe_latent_size,
moe_intermediate_size]`` and its transpose. An assignment (one token to
one held expert) passes both: ``4 x latent x width`` operations. An
expert that got any token has both read once: ``2 x latent x width``
parameters at the served width. The latent projections before the
dispatch and after the combine are dense products outside the grouped
kernel and are left out, as are the rows in and out, so the share errs
low."""


def _sizes(fields: dict) -> tuple:
    return (fields["moe_latent_size"], fields["moe_intermediate_size"],
            fields["hybrid_override_pattern"].count("E"))


def operations(assignments_a_layer: float, fields: dict) -> float:
    """Floating-point operations of one step's grouped products, all
    expert layers: ``assignments_a_layer`` tokens-times-held-experts
    each."""
    lat, f, layers = _sizes(fields)
    return 4.0 * lat * f * assignments_a_layer * layers


def bytes_read(experts_hit_a_layer: float, fields: dict,
               param_bytes: int = 2) -> float:
    """Bytes of expert weights one step has to read, all expert layers:
    the held experts that got a token, once each."""
    lat, f, layers = _sizes(fields)
    return 2.0 * lat * f * param_bytes * experts_hit_a_layer * layers
