"""Pallas TPU kernels for Block-RandK compression (DESIGN §3).

Three kernels around the wire format ``payload = alpha * g[selected blocks]``:

  * ``block_compress``   — gather + scale: one grid step per selected block;
    the block id is prefetched (scalar prefetch) and drives the input
    BlockSpec index_map, so the gather is a pure DMA pattern — no VMEM
    shuffle, each selected block streams HBM->VMEM->HBM once.
  * ``block_decompress`` — inverse scatter into a zeroed dense vector.
  * ``momentum_scatter`` — the fused RoSDHB step-5 update: decay the whole
    momentum row by beta while adding (1-beta)*payload into the selected
    blocks; one pass over the bank row, which is the server's dominant
    HBM-bandwidth term (see EXPERIMENTS §Perf).

Block size is a multiple of the 128-lane register width. Every array is
viewed as ``[blocks, 1, block_size]`` and tiled in ``(1, 1, block_size)``
blocks: the last two block dimensions then equal the array's own, which is
what the TPU tiling rule asks of a block that is one row high (a
``(1, block_size)`` block over ``[blocks, block_size]`` is refused).

Scalar-prefetched id and slot vectors live in SMEM (1 MiB on a v5e), which
holds the ids of a few hundred thousand blocks at most. Longer vectors run
as a chain of calls over segments of at most :data:`MAX_PREFETCH` grid
steps; each call writes its steps of one output buffer in place
(``input_output_aliases``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


#: Most scalar-prefetch entries (one per grid step) a single call takes.
MAX_PREFETCH = 1 << 16


def _segmented(make_call, prefetch: jnp.ndarray, operands, out_shape):
    """Run ``make_call(base, size, aliased)`` — a pallas_call over grid steps
    ``[base, base + size)`` whose scalar prefetch is ``prefetch[base:base +
    size]`` — over all of ``prefetch``. One segment is one plain call; more
    chain calls that each take the output so far as an aliased last operand
    and write their own steps of it."""
    total = prefetch.shape[0]
    if total <= MAX_PREFETCH:
        return make_call(0, total, False)(prefetch, *operands)
    out = jnp.zeros(out_shape.shape, out_shape.dtype)
    for base in range(0, total, MAX_PREFETCH):
        size = min(MAX_PREFETCH, total - base)
        out = make_call(base, size, True)(prefetch[base:base + size],
                                          *operands, out)
    return out


def _call(kernel, base_specs, out_spec, out_shape, grid: int,
          aliased: bool, interpret: bool, name: str):
    """pallas_call ``name`` with one scalar-prefetch operand; with
    ``aliased`` the output buffer rides as an extra operand left in HBM
    (``pl.ANY``) and aliased to the output."""
    in_specs = list(base_specs)
    aliases = {}
    if aliased:
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        aliases = {1 + len(base_specs): 0}
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(grid,), in_specs=in_specs,
            out_specs=out_spec),
        out_shape=out_shape,
        input_output_aliases=aliases,
        interpret=interpret,
        name=name,
    )


# --------------------------------------------------------------------------
# compress: payload[j] = alpha * g_blocks[idx[j]]
# --------------------------------------------------------------------------


def _compress_kernel(idx_ref, g_ref, *refs, alpha: float):
    # g_ref is the block selected by the index_map (scalar prefetch); an
    # aliased output buffer, when present, sits before o_ref untouched
    o_ref = refs[-1]
    o_ref[...] = (g_ref[...].astype(jnp.float32) * alpha).astype(o_ref.dtype)


def block_compress(g: jnp.ndarray, block_idx: jnp.ndarray, block_size: int,
                   alpha: float, *, interpret: bool = False) -> jnp.ndarray:
    """g: [d] (d % block_size == 0); block_idx: [kb] -> payload [kb*bs]."""
    d = g.shape[0]
    nb = d // block_size
    kb = block_idx.shape[0]
    blk = (1, 1, block_size)
    out_shape = jax.ShapeDtypeStruct((kb, 1, block_size), g.dtype)

    def make_call(base, size, aliased):
        return _call(
            functools.partial(_compress_kernel, alpha=alpha),
            [pl.BlockSpec(blk, lambda j, idx: (idx[j], 0, 0))],
            pl.BlockSpec(blk, lambda j, idx: (base + j, 0, 0)),
            out_shape, size, aliased, interpret, "compress")

    out = _segmented(make_call, block_idx,
                     (g.reshape(nb, 1, block_size),), out_shape)
    return out.reshape(kb * block_size)


# --------------------------------------------------------------------------
# decompress: dense[idx[j]] = payload[j]; zeros elsewhere
# --------------------------------------------------------------------------


def _decompress_kernel(sel_ref, p_ref, *refs):
    # grid over destination blocks; sel_ref[i] holds the payload slot for
    # the segment's block i (or -1 if unselected).
    o_ref = refs[-1]
    slot = sel_ref[pl.program_id(0)]

    @pl.when(slot >= 0)
    def _write():
        o_ref[...] = p_ref[...]

    @pl.when(slot < 0)
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)


def _slot_map(block_idx: jnp.ndarray, nb: int) -> jnp.ndarray:
    """Destination block -> payload row (-1 = not selected)."""
    slot = jnp.full((nb,), -1, jnp.int32)
    return slot.at[block_idx].set(
        jnp.arange(block_idx.shape[0], dtype=jnp.int32))


def block_decompress(payload: jnp.ndarray, block_idx: jnp.ndarray,
                     block_size: int, d: int, *,
                     interpret: bool = False) -> jnp.ndarray:
    """payload [kb*bs] + block ids -> dense [d]."""
    nb = d // block_size
    kb = block_idx.shape[0]
    blk = (1, 1, block_size)
    out_shape = jax.ShapeDtypeStruct((nb, 1, block_size), payload.dtype)

    def make_call(base, size, aliased):
        return _call(
            _decompress_kernel,
            [pl.BlockSpec(blk,
                          lambda i, sel: (jnp.maximum(sel[i], 0), 0, 0))],
            pl.BlockSpec(blk, lambda i, sel: (base + i, 0, 0)),
            out_shape, size, aliased, interpret, "decompress")

    out = _segmented(make_call, _slot_map(block_idx, nb),
                     (payload.reshape(kb, 1, block_size),), out_shape)
    return out.reshape(d)


# --------------------------------------------------------------------------
# fused momentum update: m = beta*m; m[sel] += (1-beta)*payload
# --------------------------------------------------------------------------


def _momentum_kernel(sel_ref, m_ref, p_ref, *refs, beta: float):
    o_ref = refs[-1]
    slot = sel_ref[pl.program_id(0)]
    m = m_ref[...].astype(jnp.float32) * beta

    @pl.when(slot >= 0)
    def _upd():
        o_ref[...] = (m + (1.0 - beta) * p_ref[...].astype(jnp.float32)
                      ).astype(o_ref.dtype)

    @pl.when(slot < 0)
    def _decay():
        o_ref[...] = m.astype(o_ref.dtype)


def momentum_scatter(bank_row: jnp.ndarray, payload: jnp.ndarray,
                     block_idx: jnp.ndarray, block_size: int, beta: float,
                     *, interpret: bool = False) -> jnp.ndarray:
    """Fused Algorithm-1 step 5 over one worker's momentum row [d]."""
    d = bank_row.shape[0]
    nb = d // block_size
    kb = block_idx.shape[0]
    blk = (1, 1, block_size)
    out_shape = jax.ShapeDtypeStruct((nb, 1, block_size), bank_row.dtype)

    def make_call(base, size, aliased):
        return _call(
            functools.partial(_momentum_kernel, beta=beta),
            [pl.BlockSpec(blk, lambda i, sel: (base + i, 0, 0)),
             pl.BlockSpec(blk,
                          lambda i, sel: (jnp.maximum(sel[i], 0), 0, 0))],
            pl.BlockSpec(blk, lambda i, sel: (base + i, 0, 0)),
            out_shape, size, aliased, interpret, "momentum_update")

    out = _segmented(make_call, _slot_map(block_idx, nb),
                     (bank_row.reshape(nb, 1, block_size),
                      payload.reshape(kb, 1, block_size)), out_shape)
    return out.reshape(d)
