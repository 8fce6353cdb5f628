"""jit'd public wrapper for the int-softmax Pallas kernel."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.precision import PrecisionConfig
from repro.kernels import resolve_interpret
from repro.kernels.int_softmax.kernel import int_softmax_kernel


@partial(jax.jit, static_argnames=("cfg", "axis", "row_block", "interpret"))
def int_softmax_pallas(x, cfg: PrecisionConfig = PrecisionConfig(), mask=None,
                       axis: int = -1, row_block: int = 8,
                       interpret: bool = None):
    """Drop-in replacement for core.int_softmax backed by the Pallas kernel.
    Accepts arbitrary leading dims; softmax over the last axis."""
    if axis not in (-1, x.ndim - 1):
        raise ValueError("int_softmax_pallas computes over the last axis")
    interpret = resolve_interpret(interpret)
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    m2 = None
    if mask is not None:
        m2 = jnp.broadcast_to(mask, shape).reshape(-1, shape[-1])
    out = int_softmax_kernel(x2, cfg, mask=m2, row_block=row_block,
                             interpret=interpret)
    return out.reshape(shape)
