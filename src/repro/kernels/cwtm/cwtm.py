"""Pallas TPU kernel: coordinate-wise trimmed mean over the worker axis.

This is the robust-aggregation hot loop of the virtual server: every training
round it processes all `D` coordinates of the momentum bank `[n_workers, D]`
— and under the fused grid engine (repro.core.sweep) it does so for every
scenario cell at once, so the engine-real shape is ``[B, n, d]`` with
``B = n_cells * n_seeds`` flat fusion lanes.

TPU mapping:
  * the coordinate axis is tiled into VMEM blocks of ``block_d`` lanes
    (a multiple of 128); each grid step loads an ``[n, block_d]`` tile;
  * the batch axis is a leading grid dimension — one ``(b, j)`` grid step
    per (fusion lane, coordinate block), so the whole pass is a single
    memory-bound sweep over the stacked ``[B, n, d]`` read;
  * the worker axis (n <= 64) lives across sublanes; we sort it with a
    Batcher bitonic network expressed as jnp.minimum/maximum over
    whole-lane vectors — fully vectorised on the VPU, no data-dependent
    control flow;
  * the output is a static rank weighting of the sorted rows, accumulated
    in f32: the trimmed window for CWTM, the middle element(s) for the
    coordinate-wise median (see ``repro.kernels.median`` — the sibling
    kernel shares this sort network and tile plumbing, it only swaps the
    weight vector).

Sorting cost is O(log^2 n) vector min/max passes per tile, so the kernel is
memory-bound by the single [n, block_d] read — exactly the roofline target
for an aggregation pass (``repro.launch.roofline.aggregation_roofline``).
"""

from __future__ import annotations

import functools
import math
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _bitonic_pairs(n: int):
    """Index pairs of a bitonic sorting network for n inputs (n power of 2)."""
    pairs = []
    k = 2
    while k <= n:
        j = k // 2
        while j >= 1:
            stage = []
            for i in range(n):
                l = i ^ j
                if l > i:
                    ascending = (i & k) == 0
                    stage.append((i, l, ascending))
            pairs.append(stage)
            j //= 2
        k *= 2
    return pairs


def sort_network_compares(n_pad: int) -> int:
    """Total compare-exchange pairs of the bitonic network — the FLOP side
    of the aggregation roofline (2 vector ops — min + max — per pair)."""
    return sum(len(stage) for stage in _bitonic_pairs(n_pad))


def _sort_rows(rows):
    """Ascending bitonic sort of a list of same-shape lane vectors."""
    rows = list(rows)
    for stage in _bitonic_pairs(len(rows)):
        for i, l, asc in stage:
            lo = jnp.minimum(rows[i], rows[l])
            hi = jnp.maximum(rows[i], rows[l])
            rows[i], rows[l] = (lo, hi) if asc else (hi, lo)
    return rows


def sorted_weight_kernel(x_ref, o_ref, *, n_pad: int,
                         weights: Tuple[float, ...]):
    """One VMEM tile: x_ref [1, n_pad, block_d] -> o_ref [1, 1, block_d].

    Rows [n, n_pad) are padding preloaded with +inf so they sort to the
    tail and ``weights`` (length n, indexed by sorted rank over the REAL
    rows) never touches them. The output is the static rank weighting
    sum_i weights[i] * sorted[i], accumulated in f32 — CWTM uses the
    trimmed-window weights, the coordinate-wise median the middle-rank
    weights (repro.kernels.median shares this kernel body).
    """
    rows = _sort_rows(x_ref[0, i, :].astype(jnp.float32)
                      for i in range(n_pad))
    acc = None
    for i, w in enumerate(weights):
        if w == 0.0:
            continue
        term = rows[i] * w if w != 1.0 else rows[i]
        acc = term if acc is None else acc + term
    o_ref[0, 0, :] = acc.astype(o_ref.dtype)


def sorted_weighted_batched(x: jnp.ndarray, weights: Sequence[float], *,
                            name: str, block_d: int = 2048,
                            interpret: bool = False) -> jnp.ndarray:
    """Static rank weighting of the sorted worker axis: [B, n, d] -> [B, d].

    The shared tile plumbing of the CWTM / coordinate-wise-median kernels:
    grid (B, d/block_d), each step one memory-bound [n_pad, block_d] read.
    ``weights[i]`` scales the i-th smallest value per coordinate; ``name``
    names the kernel's call (the compiled instruction, as a trace shows it).
    """
    b, n, d = x.shape
    weights = tuple(float(w) for w in weights)
    assert len(weights) == n, (len(weights), n)
    n_pad = 1 << max(1, math.ceil(math.log2(n)))
    if n_pad != n:
        fill = jnp.full((b, n_pad - n, d), jnp.inf, x.dtype)
        x = jnp.concatenate([x, fill], axis=1)

    # a smaller lane-aligned block that divides d spares the padded copy of
    # the whole stack
    while d % block_d and block_d % 256 == 0:
        block_d //= 2
    d_pad = (-d) % block_d
    if d_pad:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, d_pad)))
    dp = d + d_pad

    kernel = functools.partial(sorted_weight_kernel, n_pad=n_pad,
                               weights=weights)
    # the output carries a unit middle axis so that its block's last two
    # dimensions (1, block_d) equal the array's (1, ...) and a multiple of
    # 128 — the TPU tiling rule a (1, block_d) block over [B, dp] breaks
    # for B > 1
    out = pl.pallas_call(
        kernel,
        grid=(b, dp // block_d),
        in_specs=[pl.BlockSpec((1, n_pad, block_d), lambda i, j: (i, 0, j))],
        out_specs=pl.BlockSpec((1, 1, block_d), lambda i, j: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((b, 1, dp), x.dtype),
        interpret=interpret,
        name=name,
    )(x)
    return out[:, 0, :d]


def cwtm_weights(n: int, f: int) -> Tuple[float, ...]:
    """Rank weights of the trimmed mean: 1/(n-2f) over ranks [f, n-f)."""
    assert n > 2 * f, (n, f)
    w = 1.0 / float(n - 2 * f)
    return tuple(w if f <= i < n - f else 0.0 for i in range(n))


def cwtm_pallas_batched(x: jnp.ndarray, f: int, *, block_d: int = 2048,
                        interpret: bool = False) -> jnp.ndarray:
    """Batched coordinate-wise trimmed mean: x [B, n, d] -> [B, d] — the
    grid engine's real shape (B = n_cells * n_seeds fusion lanes)."""
    return sorted_weighted_batched(x, cwtm_weights(x.shape[1], f),
                                   name="cwtm", block_d=block_d,
                                   interpret=interpret)


def cwtm_pallas(x: jnp.ndarray, f: int, *, block_d: int = 2048,
                interpret: bool = False) -> jnp.ndarray:
    """Coordinate-wise trimmed mean: x [n, d] -> [d]."""
    return cwtm_pallas_batched(x[None], f, block_d=block_d,
                               interpret=interpret)[0]
