"""Pallas TPU kernel: coordinate-wise median over the worker axis.

The sibling of the CWTM kernel (``repro.kernels.cwtm``): the median is a
rank-select inside the SAME bitonic sort network — only the static rank
weights change (the middle sorted row for odd n, the mean of the two middle
rows for even n), so this module reuses the CWTM tile plumbing
(``sorted_weighted_batched``: grid (B, d/block_d), one memory-bound
[n_pad, block_d] VMEM read per step) verbatim.
"""

from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp

from repro.kernels.cwtm.cwtm import sorted_weighted_batched


def median_weights(n: int) -> Tuple[float, ...]:
    """Rank weights of the coordinate-wise median: 1 at the middle sorted
    row (n odd), 1/2 at each of the two middle rows (n even) — matching
    ``jnp.median``'s midpoint convention."""
    assert n >= 1, n
    w = [0.0] * n
    if n % 2:
        w[n // 2] = 1.0
    else:
        w[n // 2 - 1] = 0.5
        w[n // 2] = 0.5
    return tuple(w)


def median_pallas_batched(x: jnp.ndarray, *, block_d: int = 2048,
                          interpret: bool = False) -> jnp.ndarray:
    """Batched coordinate-wise median: x [B, n, d] -> [B, d] — the grid
    engine's real shape (B = n_cells * n_seeds fusion lanes)."""
    return sorted_weighted_batched(x, median_weights(x.shape[1]),
                                   name="median", block_d=block_d,
                                   interpret=interpret)


def median_pallas(x: jnp.ndarray, *, block_d: int = 2048,
                  interpret: bool = False) -> jnp.ndarray:
    """Coordinate-wise median: x [n, d] -> [d]."""
    return median_pallas_batched(x[None], block_d=block_d,
                                 interpret=interpret)[0]
