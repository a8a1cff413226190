"""Jitted wrappers for the Block-RandK kernels with backend selection.

The wire format is the same on every backend: the ids name a set of
blocks, and the payload holds them in ascending block order. So
:func:`compress` and :func:`decompress` sort the ids (each row of
``[n, kb]`` ids) before either backend sees them; the kernels ask for that
order, the oracles do not.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import use_kernel
from repro.kernels.randk import randk as K
from repro.kernels.randk import ref as R


@functools.partial(jax.jit,
                   static_argnames=("block_size", "alpha", "use_pallas",
                                    "interpret"))
def compress(g, block_idx, *, block_size: int, alpha: float,
             use_pallas=None, interpret: bool = False):
    """[n, d] bank + ids ([kb] or [n, kb]) -> payload [n, kb * block_size],
    the blocks in ascending order."""
    block_idx = jnp.sort(block_idx, axis=-1)
    if use_kernel(use_pallas, interpret):
        return K.block_compress(g, block_idx, block_size, alpha,
                                interpret=interpret)
    return R.block_compress_ref(g, block_idx, block_size, alpha)


@functools.partial(jax.jit,
                   static_argnames=("block_size", "d", "use_pallas",
                                    "interpret"))
def decompress(payload, block_idx, *, block_size: int, d: int,
               use_pallas=None, interpret: bool = False):
    """Payload [n, kb * block_size] of :func:`compress` + its ids -> [n, d]."""
    block_idx = jnp.sort(block_idx, axis=-1)
    if use_kernel(use_pallas, interpret):
        return K.block_decompress(payload, block_idx, block_size, d,
                                  interpret=interpret)
    return R.block_decompress_ref(payload, block_idx, block_size, d)


@functools.partial(jax.jit,
                   static_argnames=("block_size", "beta", "use_pallas",
                                    "interpret"))
def momentum_update(bank_row, payload, block_idx, *, block_size: int,
                    beta: float, use_pallas=None, interpret: bool = False):
    if use_kernel(use_pallas, interpret):
        return K.momentum_scatter(bank_row, payload, block_idx, block_size,
                                  beta, interpret=interpret)
    return R.momentum_scatter_ref(bank_row, payload, block_idx, block_size,
                                  beta)
