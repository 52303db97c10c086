"""Kernel-or-reference selection by platform.

Every selector in this package resolves ``None`` here: on a TPU backend the
Pallas kernels run compiled (Mosaic); everywhere else the jnp references
in ``ref.py`` serve, and a kernel that is asked for explicitly runs in the
Pallas interpreter.  Nothing else in the repo consults the backend.
"""

from __future__ import annotations

import jax


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_interpret(interpret: bool | None) -> bool:
    """None = auto: compiled Mosaic on TPU, the interpreter elsewhere."""
    return (not on_tpu()) if interpret is None else bool(interpret)


def resolve_use_kernel(use_kernel: bool | None) -> bool:
    """None = auto: the Pallas kernel on TPU, the jnp reference elsewhere."""
    return on_tpu() if use_kernel is None else bool(use_kernel)
