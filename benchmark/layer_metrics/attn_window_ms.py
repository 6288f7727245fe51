"""``attn_full_ms`` for the layers with a window: the Mosaic kernels
named ``attn_window``, whose tables hold only the blocks that cover a
lane's last ``sliding_window`` tokens."""

from benchmark import named_kernels


def read(c):
    s = named_kernels.per_decode_step_s(c, "%attn_window")
    return None if s is None else s * 1e3
