"""Shared helpers for the Pallas kernel subpackages."""
from __future__ import annotations

import jax


def default_interpret(interpret: bool | None = None) -> bool:
    """Resolve the ``interpret`` flag for a pallas_call.

    ``None`` (the default everywhere) auto-detects: compiled kernels on TPU,
    the interpreter on the CPU backend (tests). Any other backend raises —
    silently interpreting there would hide that the device never ran a
    kernel. An explicit bool wins, so tests can force interpret mode, and
    ahead-of-time compiles for a described TPU can force compiled kernels.
    """
    if interpret is not None:
        return interpret
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(f"Pallas kernels target the TPU; backend {backend!r} "
                       "has neither compiled kernels nor the CPU interpreter "
                       "(pass interpret= explicitly)")
