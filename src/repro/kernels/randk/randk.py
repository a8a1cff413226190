"""Pallas TPU kernels for Block-RandK compression (DESIGN §3).

Three kernels around the wire format ``payload = alpha * g[selected blocks]``:

  * ``block_compress`` — gather + scale over a whole ``[n, d]`` bank in one
    call, a grid over (chunk of kept ids, id row). A step DMAs its chunk's
    blocks from the bank, which stays in HBM (``pl.ANY``), into its
    ``[n, chunk * B]`` output tile, then scales the tile by alpha in f32.
  * ``block_decompress`` — the inverse over the bank in one call, a grid
    over (tile of destination blocks, id row). A step zeroes its
    ``[n, tile * B]`` output tile in VMEM and DMAs the tile's kept blocks
    in from the payload in HBM. With each id row sorted those are the
    payload slots ``[off[t], off[t + 1])``, ``off`` one ``searchsorted`` of
    the tile bounds. The last tile may be ragged: its write-back is clipped
    to the array.
  * ``momentum_scatter`` — the fused RoSDHB step-5 update: decay the whole
    momentum row by beta while adding (1-beta)*payload into the selected
    blocks; one pass over the bank row, which is the server's dominant
    HBM-bandwidth term (see EXPERIMENTS §Perf).

A DMA copies a column window of whole row tiles of a 2-D array, so each
moves one block of every row of the bank or payload; their rows are padded
only where the TPU's tiling asks (:func:`_dma_rows`), never for n = 4.
Ids ``[k]`` (one mask for every row) make one id row: its DMAs land in the
output tile directly. Ids ``[n, k]`` (a mask per row) make n id rows, the
inner grid axis, which revisits the same output tile: id row r's DMAs land
in a VMEM staging tile, and only row r of it is taken into the output. That
path reads n times the bytes, and makes no copy of the bank.

A step's output tile holds :data:`TILE_BYTES`. SMEM holds the tile
offsets, one scalar-prefetch vector of ``tiles + 1`` int32 per id row
(1,589 for a [4, 416M] bf16 bank of 512-blocks: 6.4 KB), and
the ids as blocked inputs of :data:`ID_WINDOW` entries. Compress reads its
chunk from one window; decompress a tile's ids from the window holding
``off[t]`` and the one after it, a tile holding at most
:data:`ID_WINDOW` kept blocks. No vector outgrows SMEM and no call is
split.

``momentum_scatter`` keeps the per-block scheme: one grid step per block of
``[blocks, 1, block_size]`` views, its scalar-prefetched slot vector split
into a chain of calls over segments of at most :data:`MAX_PREFETCH` steps
that write one aliased output.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# --------------------------------------------------------------------------
# the bank-wide round trip: ids [k] (one mask for every row) or [n, k],
# each id row sorted ascending
# --------------------------------------------------------------------------

#: Ids in one SMEM block: the TPU lays a 1-D int32 array out in tiles of
#: 1,024, and an SMEM block of another length is refused.
ID_WINDOW = 1024
#: Bytes of one grid step's output tile, which sets how many blocks a step
#: covers. On a v5e at the [4, 416M] bf16 bank (512 blocks a step),
#: decompress took 5.84 ms against 8.92 ms at 128 blocks and 5.69 ms at
#: 1,024; compress 1.8 ms at any of 64 to 512.
TILE_BYTES = 2 << 20


def _blocks_per_step(rows: int, block: int, dtype) -> int:
    """Blocks of ``rows`` rows one step covers: :data:`TILE_BYTES` of
    them, rounded down to a power of two that divides :data:`ID_WINDOW`.
    Fewer than 4 rows count as 4: VMEM pads such a tile's rows (2 bf16
    rows of 1,024 blocks overflow it where 4 rows of 512 fit)."""
    fit = TILE_BYTES // (max(rows, 4) * block * jnp.dtype(dtype).itemsize)
    return min(ID_WINDOW, 1 << max(fit, 1).bit_length() - 1)


def _id_rows(block_idx: jnp.ndarray, windows: int) -> jnp.ndarray:
    """``block_idx`` ([kb] or [n, kb]) as int32 id rows, each padded to
    ``windows`` :data:`ID_WINDOW`-long windows, flattened."""
    ids = block_idx.astype(jnp.int32).reshape(-1, block_idx.shape[-1])
    pad = windows * ID_WINDOW - ids.shape[1]
    return jnp.pad(ids, ((0, 0), (0, pad))).reshape(-1)


def _dma_rows(rows: int, dtype) -> int:
    """The fewest rows, at least ``rows``, of which a DMA can copy a
    column window whole: the TPU tiles a ``[rows, m]`` array with up to 8
    rows a tile (a power of two under 8, at least the 4 // itemsize rows
    one 32-bit word packs), and a DMA moves whole tiles only."""
    if rows > 8:
        return -(-rows // 8) * 8
    least = max(rows, 4 // jnp.dtype(dtype).itemsize)
    return 1 << (least - 1).bit_length()


def _padded(x: jnp.ndarray) -> jnp.ndarray:
    """[n, m] with zero rows appended up to :func:`_dma_rows` rows."""
    pad = _dma_rows(x.shape[0], x.dtype) - x.shape[0]
    return jnp.pad(x, ((0, pad), (0, 0))) if pad else x


def _copy_all(lo, hi, copy) -> None:
    """Start the DMAs ``copy(i)`` for i in [lo, hi), then wait for each."""
    def start(i, carry):
        copy(i).start()
        return carry

    def wait(i, carry):
        copy(i).wait()
        return carry

    jax.lax.fori_loop(lo, hi, start, 0)
    jax.lax.fori_loop(lo, hi, wait, 0)


def _take_row(o_ref, staged_ref, row, make=lambda x: x) -> None:
    """Row ``row`` of the output tile: ``make`` of that row of
    ``staged_ref``; the other rows are left as they are."""
    for r in range(o_ref.shape[0]):
        @pl.when(row == r)
        def _take():
            o_ref[pl.ds(r, 1), :] = make(staged_ref[pl.ds(r, 1), :])


def _compress_kernel(ids_ref, g_hbm, o_ref, *scratch, k: int, chunk: int,
                     block: int, alpha: float, local: bool):
    # ids_ref: the id window holding this step's chunk. The chunk's blocks
    # are DMA'd from the bank into the output tile (one mask for every
    # row) or the staging tile (a mask per row), then scaled in f32.
    step, row = pl.program_id(0), pl.program_id(1)
    first = (step * chunk) % ID_WINDOW
    to = scratch[0] if local else o_ref

    def copy(j):
        src = pl.multiple_of(ids_ref[first + j] * block, block)
        dst = pl.multiple_of(j * block, block)
        return pltpu.make_async_copy(g_hbm.at[:, pl.ds(src, block)],
                                     to.at[:, pl.ds(dst, block)],
                                     scratch[-1])

    def scale(x):
        return (x.astype(jnp.float32) * alpha).astype(o_ref.dtype)

    _copy_all(0, jnp.minimum(chunk, k - step * chunk), copy)
    if local:
        _take_row(o_ref, to, row, scale)
    else:
        o_ref[...] = scale(o_ref[...])


def block_compress(g: jnp.ndarray, block_idx: jnp.ndarray, block_size: int,
                   alpha: float, *, interpret: bool = False) -> jnp.ndarray:
    """g: [n, d] (d % block_size == 0); block_idx: [kb] shared by every row
    or [n, kb] one row each, each row sorted ascending -> payload
    [n, kb * block_size], the blocks in ``block_idx`` order, each
    ``alpha * block`` computed in f32."""
    n, kb = g.shape[0], block_idx.shape[-1]
    local = block_idx.ndim == 2
    bank = _padded(g)
    tile = (bank.shape[0],
            _blocks_per_step(bank.shape[0], block_size, g.dtype) * block_size)
    chunk = tile[1] // block_size
    windows = pl.cdiv(kb, ID_WINDOW)
    out = pl.pallas_call(
        functools.partial(_compress_kernel, k=kb, chunk=chunk,
                          block=block_size, alpha=alpha, local=local),
        grid=(pl.cdiv(kb, chunk), n if local else 1),
        in_specs=[pl.BlockSpec(
                      (ID_WINDOW,),
                      lambda i, r: (r * windows + i * chunk // ID_WINDOW,),
                      memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(tile, lambda i, r: (0, i)),
        out_shape=jax.ShapeDtypeStruct((bank.shape[0], kb * block_size),
                                       g.dtype),
        scratch_shapes=[pltpu.VMEM(tile, g.dtype)] * local
        + [pltpu.SemaphoreType.DMA(())],
        interpret=interpret,
        name="compress",
    )(_id_rows(block_idx, windows), bank)
    return out[:n] if out.shape[0] > n else out


def _decompress_kernel(off_ref, here, ahead, p_hbm, o_ref, *scratch,
                       tiles: int, tile: int, block: int, local: bool):
    # off_ref[r * (tiles + 1) + t]: id row r's first payload slot at or
    # past tile t; `here` and `ahead`: the id window holding that slot
    # and the next one. The tile is zeroed (with a mask per row, at its
    # first id row), then its kept blocks, the payload slots
    # [off[t], off[t + 1]), are DMA'd in: straight into the tile, or into
    # a zeroed staging tile whose row r is then taken.
    t, row = pl.program_id(0), pl.program_id(1)
    lo = off_ref[row * (tiles + 1) + t]
    hi = off_ref[row * (tiles + 1) + t + 1]
    base = (lo // ID_WINDOW) * ID_WINDOW
    to = scratch[0] if local else o_ref

    @pl.when(row == 0)
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    if local:
        to[...] = jnp.zeros_like(to)

    def copy(i):
        rel = i - base
        blk = jnp.where(rel < ID_WINDOW, here[jnp.minimum(rel, ID_WINDOW - 1)],
                        ahead[jnp.maximum(rel - ID_WINDOW, 0)])
        src = pl.multiple_of(i * block, block)
        dst = pl.multiple_of((blk - t * tile) * block, block)
        return pltpu.make_async_copy(p_hbm.at[:, pl.ds(src, block)],
                                     to.at[:, pl.ds(dst, block)],
                                     scratch[-1])

    _copy_all(lo, hi, copy)
    if local:
        _take_row(o_ref, to, row)


def block_decompress(payload: jnp.ndarray, block_idx: jnp.ndarray,
                     block_size: int, d: int, *,
                     interpret: bool = False) -> jnp.ndarray:
    """payload [n, kb * block_size] + ids [kb] or [n, kb], each id row
    sorted ascending -> dense [n, d]: payload block j at block
    ``block_idx[..., j]`` of its row, zeros elsewhere."""
    n, kb = payload.shape[0], block_idx.shape[-1]
    local = block_idx.ndim == 2
    id_rows = n if local else 1
    p = _padded(payload)
    tl = min(_blocks_per_step(p.shape[0], block_size, payload.dtype),
             d // block_size)
    tiles = pl.cdiv(d // block_size, tl)
    # off[t] <= kb: the window holding it and the one after exist
    windows = kb // ID_WINDOW + 2
    bounds = jnp.arange(tiles + 1, dtype=jnp.int32) * tl
    off = jax.vmap(lambda ids: jnp.searchsorted(ids, bounds))(
        block_idx.astype(jnp.int32).reshape(id_rows, kb))

    def window(ahead):
        return pl.BlockSpec(
            (ID_WINDOW,),
            lambda t, r, o: (r * windows + o[r * (tiles + 1) + t] // ID_WINDOW
                             + ahead,),
            memory_space=pltpu.SMEM)

    tile = (p.shape[0], tl * block_size)
    ids = _id_rows(block_idx, windows)
    out = pl.pallas_call(
        functools.partial(_decompress_kernel, tiles=tiles, tile=tl,
                          block=block_size, local=local),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(tiles, id_rows),
            in_specs=[window(0), window(1), pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(tile, lambda t, r, o: (0, t)),
            scratch_shapes=[pltpu.VMEM(tile, payload.dtype)] * local
            + [pltpu.SemaphoreType.DMA(())]),
        out_shape=jax.ShapeDtypeStruct((p.shape[0], d), payload.dtype),
        interpret=interpret,
        name="decompress",
    )(off.reshape(-1).astype(jnp.int32), ids, ids, p)
    return out[:n] if out.shape[0] > n else out


# --------------------------------------------------------------------------
# fused momentum update: m = beta*m; m[sel] += (1-beta)*payload
# --------------------------------------------------------------------------

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


def _slot_map(block_idx: jnp.ndarray, nb: int) -> jnp.ndarray:
    """Destination block -> payload row (-1 = not selected)."""
    slot = jnp.full((nb,), -1, jnp.int32)
    return slot.at[block_idx].set(
        jnp.arange(block_idx.shape[0], dtype=jnp.int32))


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
