"""Public jit'd entry points for the Pallas kernels.

Each takes ``interpret=None``: interpreted on the CPU backend, compiled for
the TPU's VMEM tiling on a chip (`repro.kernels.interpret_mode`).
"""

from __future__ import annotations

from .classify import classify
from .decode_attn import flash_decode
from .segsel import segment_select, segment_select_batch
from .zipfprob import pr_gc_bit_kernel, pr_user_bit_kernel, zipf_bit_sums

__all__ = [
    "segment_select", "segment_select_batch", "classify", "zipf_bit_sums",
    "pr_user_bit_kernel", "pr_gc_bit_kernel", "flash_decode",
]
