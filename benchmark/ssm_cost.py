"""Bytes and operations of the state-space layers' recurrence, for
``ssm_update_roofline_pct`` and ``ssm_scan_roofline_pct``: the work
that was NEEDED, from the configuration's fields
and the step's counters, whatever implements it.

A Mamba-2 layer keeps, a sequence, a state of ``mamba_num_heads x
mamba_head_dim x ssm_state_size`` values in float32. A decode step has
to read each live lane's state once and write it once, every
state-space layer (the letter ``M`` of ``hybrid_override_pattern``), and
nothing else of the pool: a padded lane's scratch slot, a parked
snapshot and a copy into a gathered buffer are not needed work. The
rows of the convolution's state (61 KB a layer beside 4.19 MB) move
outside the recurrence and are left out, so the share errs low. A
token's update is 2 operations a state value (decay, add the outer
product) and its output 2 more (``S C``): far under the chip's ridge,
so the memory side decides."""


def _layers(fields: dict) -> int:
    return fields["hybrid_override_pattern"].count("M")


def state_values(fields: dict) -> int:
    """Values of one layer's state ``S`` a sequence."""
    return (fields["mamba_num_heads"] * fields["mamba_head_dim"]
            * fields["ssm_state_size"])


def update_bytes(lanes: float, fields: dict, state_bytes: int = 4) -> float:
    """Bytes one decode step has to move for ``lanes`` live lanes, all
    state-space layers: each state in once and out once."""
    return 2.0 * lanes * _layers(fields) * state_values(fields) * state_bytes


def update_operations(lanes: float, fields: dict) -> float:
    """Floating-point operations of those updates and their outputs."""
    return 4.0 * lanes * _layers(fields) * state_values(fields)


def scan_operations(tokens: float, fields: dict) -> float:
    """Floating-point operations the chunked scan of ``tokens`` rows
    needs, all state-space layers: a row against its block's rows (the
    scores ``C B^T`` a group and the masked product a head, half of the
    square on average), what it adds to the block's state, and what it
    reads of the state handed in."""
    H, P, N = (fields["mamba_num_heads"], fields["mamba_head_dim"],
               fields["ssm_state_size"])
    G, l = fields["n_groups"], fields["chunk_size"]
    a_row = 2.0 * (l / 2.0) * (G * N + H * P) + 4.0 * H * P * N
    return tokens * _layers(fields) * a_row


def scan_bytes(tokens: float, executions: float, fields: dict,
               act_bytes: int = 2, state_bytes: int = 4) -> float:
    """Bytes the chunked scans of ``tokens`` rows in ``executions``
    spans have to move, all state-space layers: a row's ``x`` in and
    its output out (``mamba_num_heads x mamba_head_dim`` each), its
    ``B`` and ``C`` (``n_groups x ssm_state_size`` each) and ``dt`` (a
    value a head), at the served activation width; and a span's state
    in once and out once. What a kernel moves in float32, or pads a
    span's tail with, is not needed work, so the share errs low."""
    H, P, N, G = (fields["mamba_num_heads"], fields["mamba_head_dim"],
                  fields["ssm_state_size"], fields["n_groups"])
    a_row = (2 * H * P + 2 * G * N + H) * act_bytes
    return _layers(fields) * (tokens * a_row + executions * 2.0
                              * state_values(fields) * state_bytes)
