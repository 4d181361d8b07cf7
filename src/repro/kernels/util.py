"""Shared helpers for the FC kernel family (Pallas bodies + XLA refs)."""
from __future__ import annotations

from typing import Optional

import jax


def pallas_interpret() -> bool:
    """How Pallas kernels lower in this process: natively on a TPU
    backend, through the interpreter everywhere else."""
    return jax.default_backend() != "tpu"


def interpret_mode(interpret: Optional[bool]) -> bool:
    """A kernel entry point's ``interpret`` argument; None follows the
    backend (:func:`pallas_interpret`)."""
    return pallas_interpret() if interpret is None else interpret


def cdiv(a: int, b: int) -> int:
    return (a + b - 1) // b


def apply_activation(name: Optional[str], y):
    """The fused-epilogue activation table.  One definition, shared by the
    Pallas kernel epilogues and the XLA reference paths, so the two can
    never drift apart."""
    if name is None or name == "none":
        return y
    if name == "relu":
        return jax.nn.relu(y)
    if name == "silu":
        return jax.nn.silu(y)
    if name == "gelu":
        return jax.nn.gelu(y, approximate=True)
    raise ValueError(f"unknown fused activation {name!r}")
