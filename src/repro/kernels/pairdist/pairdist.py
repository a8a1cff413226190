"""Pallas TPU kernel: batched pairwise squared distances over the worker axis.

Serves BOTH NNM pre-aggregation (Allouah et al.'s Fixing-by-Mixing — each
worker vector replaced by the mean of its n-f nearest neighbours) and
(Multi-)Krum scoring: both start from the [n, n] squared-distance matrix
``||x_i - x_j||^2``. The pure-XLA rule materialises the Gram matrix from a
full f32 ``x @ x.T`` plus two more passes over ``x`` for the squared norms;
here one (B, d/block_d) grid makes a SINGLE memory-bound read of each
``[n, block_d]`` tile, accumulating the Gram block on the MXU in f32 into
the revisited [n_pad, n_pad] output block, and finalises
``d2 = sq_i + sq_j - 2 G`` (clamped at 0) in-register on the last
d-block — the tiny [n, n] output is the only other HBM traffic.

The worker axis is padded to a sublane multiple (8) with zero rows — zero
padding contributes nothing to inner products, and the pads are sliced off
the output. n <= 64 per the simulator contract, so the whole Gram tile
lives comfortably in VMEM next to the input tile.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def pairdist_kernel(x_ref, o_ref, *, n_blocks: int, n_pad: int):
    """One (b, j) grid step: accumulate the Gram block of x_ref
    [1, n_pad, block_d] into the revisited o_ref [1, n_pad, n_pad]; on the
    last d-block, transform the Gram matrix into clamped squared
    distances in place."""
    j = pl.program_id(1)
    xt = x_ref[0].astype(jnp.float32)  # [n_pad, block_d]
    g = jax.lax.dot_general(xt, xt, (((1,), (1,)), ((), ())),
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)

    @pl.when(j == 0)
    def _init():
        o_ref[0] = g

    @pl.when(j > 0)
    def _accumulate():
        o_ref[0] = o_ref[0] + g

    @pl.when(j == n_blocks - 1)
    def _finalise():
        gg = o_ref[0]
        row = jax.lax.broadcasted_iota(jnp.int32, (n_pad, n_pad), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (n_pad, n_pad), 1)
        diag = jnp.where(row == col, gg, 0.0)
        sq_i = jnp.sum(diag, axis=1, keepdims=True)   # [n_pad, 1]
        sq_j = jnp.sum(diag, axis=0, keepdims=True)   # [1, n_pad]
        o_ref[0] = jnp.maximum(sq_i + sq_j - 2.0 * gg, 0.0)


def pairdist_pallas_batched(x: jnp.ndarray, *, block_d: int = 2048,
                            interpret: bool = False) -> jnp.ndarray:
    """Batched pairwise squared distances: x [B, n, d] -> [B, n, n] (f32)."""
    b, n, d = x.shape
    n_pad = max(8, -(-n // 8) * 8)
    if n_pad != n:
        x = jnp.pad(x, ((0, 0), (0, n_pad - n), (0, 0)))
    d_pad = (-d) % block_d
    if d_pad:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, d_pad)))
    dp = d + d_pad
    n_blocks = dp // block_d

    kernel = functools.partial(pairdist_kernel, n_blocks=n_blocks,
                               n_pad=n_pad)
    out = pl.pallas_call(
        kernel,
        grid=(b, n_blocks),
        in_specs=[pl.BlockSpec((1, n_pad, block_d), lambda i, j: (i, 0, j))],
        out_specs=pl.BlockSpec((1, n_pad, n_pad), lambda i, j: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, n_pad, n_pad), jnp.float32),
        interpret=interpret,
        name="pairdist",
    )(x)
    return out[:, :n, :n]


def pairdist_pallas(x: jnp.ndarray, *, block_d: int = 2048,
                    interpret: bool = False) -> jnp.ndarray:
    """Pairwise squared distances: x [n, d] -> [n, n] (f32)."""
    return pairdist_pallas_batched(x[None], block_d=block_d,
                                   interpret=interpret)[0]
