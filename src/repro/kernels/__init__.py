"""Pallas TPU kernels (gossip mix, panel reduce, wire/residency codecs,
merge operators, fused AdamW, flash attention) and their jnp oracles
(``ref.py``).

Every kernel entry point takes ``interpret=None``, resolved here from the
platform: compiled Mosaic on a TPU, the Pallas interpreter on any other
backend. Callers above this package never choose; a test that compiles a
kernel for a described (not attached) TPU passes ``interpret=False``.
"""
from __future__ import annotations

import jax


def interpret_mode(interpret: bool | None = None) -> bool:
    """Whether a ``pallas_call`` runs in the interpreter. ``None`` means
    "interpret unless the backend is a TPU"; asking to interpret on a TPU
    is an error, never a silent slow path."""
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        return not on_tpu
    if interpret and on_tpu:
        raise ValueError("Pallas kernels never run interpreted on a TPU")
    return bool(interpret)
