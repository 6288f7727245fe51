"""Device time a decode step spends in the paged attention of the
layers that keep every token: the Mosaic kernels named ``attn_full``
(models/laguna.py's name on its paged call), all such layers, by
``named_kernels.per_decode_step_s``. Found by the kernel's name on the
trace's op events, never by an operand."""

from benchmark import named_kernels


def read(c):
    s = named_kernels.per_decode_step_s(c, "%attn_full")
    return None if s is None else s * 1e3
