"""Finding the Pallas kernels in a reduced trace. Nothing in the program
names them (no ``name=`` on ``pallas_call``, no ``named_scope``), so they
are found by what the TPU's op events hold: the whole HLO text of the
instruction, with ``custom_call_target="tpu_custom_call"`` for a Mosaic
kernel and the shapes of its operands. A kernel is told from another by
an operand only it takes, worked out from the configuration's own
sizes: the paged decode kernel reads a layer's KV pool
``[kv_heads, num_blocks, block_size, head_dim]``, the flash kernels a
layer's queries ``[batch, heads, seq, head_dim]``. A Mosaic kernel that
holds neither is counted for nobody and named in the log, so a later
kernel cannot slip into an older one's figures."""

from benchmark import xplane
from benchmark.harness import log, sized

MOSAIC = 'custom_call_target="tpu_custom_call"'


def _head_dim(fields: dict) -> int:
    return fields["d_model"] // fields["n_head"]


def paged_operand(c: dict) -> str:
    """The KV pool of one layer, as the paged decode kernel takes it."""
    f = c["model_fields"]
    kw = sized(c["config"]["serve"], c["rehearse"])["kwargs"]
    return (f"[{f.get('n_kv_head') or f['n_head']},{kw['num_blocks']},"
            f"{kw['block_size']},{_head_dim(f)}]")


def flash_operand(c: dict) -> str:
    """One layer's queries, as the flash kernels take them."""
    f = c["model_fields"]
    return (f"[{c['batch'] // c['chips']},{f['n_head']},{c['seq']},"
            f"{_head_dim(f)}]")


def mosaic_s(trace: dict, operand: str) -> tuple:
    """(seconds, calls) of the Mosaic kernels that take ``operand``:
    self time, on the average chip."""
    secs = calls = 0.0
    for name, s in trace["op_self_s"].items():
        if MOSAIC not in name:
            continue
        if operand in name:
            secs += s
            calls += trace["op_calls"][name]
        else:
            log(f"a Mosaic kernel without the operand {operand} is not "
                f"counted: {xplane.short_name(name)} ({s * 1e3:.3f} ms)")
    return secs, calls


def mosaic_s_per_step(trace: dict, operand: str):
    """Seconds of those kernels in one execution of the compiled
    program that ran most often (the training step, in a training
    trace), or None where the trace holds neither."""
    secs, calls = mosaic_s(trace, operand)
    if not calls or not trace["modules"]:
        return None
    steps = max(trace["modules"].values(), key=lambda cs: cs[1])[0]
    return secs / steps if steps else None
