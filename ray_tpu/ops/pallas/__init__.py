"""Pallas TPU kernels for the hot ops (flash attention et al.).

These are the hand-scheduled VMEM-resident paths; every kernel has a pure
jax reference implementation next to it that serves as the CPU fallback
and the ground truth in tests.
"""

import jax


def interpret_default() -> bool:
    """The Pallas interpreter runs a kernel only where Mosaic cannot:
    on the CPU backend (tests). On a TPU the kernel is always compiled."""
    return jax.default_backend() == "cpu"


from .flash import flash_attention_pallas  # noqa: E402,F401
from .paged_fetch import paged_attention_stored  # noqa: E402,F401
