"""Pallas TPU kernels on the GC hot path, plus their jnp oracles (`ref`).

Every kernel entry takes ``interpret=None``: the kernel body is interpreted
on the CPU backend (tests, development hosts) and compiled by Mosaic on the
TPU. There is no flag that runs the interpreter on a chip.
"""

from __future__ import annotations

import jax


def interpret_mode(interpret: bool | None = None) -> bool:
    """Resolve a kernel's ``interpret`` argument. ``None`` follows the
    default backend: interpret on ``"cpu"``, compile on ``"tpu"``; any other
    backend raises, since the kernels are written for TPU tiling only. An
    explicit bool is returned as given (tests that steer the mode).

    The rule reads ``jax.default_backend()``, not the device the operands
    are placed on: under ``jax.default_device(<cpu device>)`` on a TPU host
    the kernels would still be lowered for Mosaic and fail to compile, so
    such a replay passes ``interpret=True`` or uses the jnp engine."""
    if interpret is not None:
        return bool(interpret)
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(f"Pallas kernels run on 'cpu' (interpreted) or 'tpu' "
                       f"(compiled); the default backend is {backend!r}")
