"""Pure-jnp oracle for the Block-RandK compress/decompress kernels."""

from __future__ import annotations

import jax.numpy as jnp


def _ids_per_row(block_idx: jnp.ndarray, n: int) -> jnp.ndarray:
    """Ids ``[kb]`` (one mask for every row) or ``[n, kb]`` as ``[n, kb]``."""
    return jnp.broadcast_to(block_idx, (n, block_idx.shape[-1]))


def block_compress_ref(g: jnp.ndarray, block_idx: jnp.ndarray,
                       block_size: int, alpha: float) -> jnp.ndarray:
    """Gather the selected blocks of each row of ``g``, scaled by alpha in
    f32.

    g: [n, d] with d % block_size == 0; block_idx: [kb] shared by every row
    or [n, kb] one row each. Returns [n, kb * block_size], the wire payload.
    """
    n = g.shape[0]
    gb = g.reshape(n, -1, block_size)
    ids = _ids_per_row(block_idx, n)
    sel = jnp.take_along_axis(gb, ids[:, :, None], axis=1)
    return (alpha * sel.astype(jnp.float32)).astype(g.dtype).reshape(n, -1)


def block_decompress_ref(payload: jnp.ndarray, block_idx: jnp.ndarray,
                         block_size: int, d: int) -> jnp.ndarray:
    """Scatter each payload row back to a dense [d] row (zeros elsewhere):
    [n, kb * block_size] -> [n, d]."""
    n = payload.shape[0]
    out = jnp.zeros((n, d // block_size, block_size), payload.dtype)
    out = out.at[jnp.arange(n)[:, None], _ids_per_row(block_idx, n)].set(
        payload.reshape(n, -1, block_size))
    return out.reshape(n, d)


def momentum_scatter_ref(bank_row: jnp.ndarray, payload: jnp.ndarray,
                         block_idx: jnp.ndarray, block_size: int,
                         beta: float) -> jnp.ndarray:
    """Fused RoSDHB momentum update (Algorithm 1, step 5):
       m <- beta * m              (all blocks)
       m[sel] += (1 - beta) * payload   (selected blocks)
    """
    nb = bank_row.shape[0] // block_size
    m = (beta * bank_row.astype(jnp.float32)).reshape(nb, block_size)
    upd = (1.0 - beta) * payload.astype(jnp.float32).reshape(-1, block_size)
    m = m.at[block_idx].add(upd)
    return m.reshape(-1).astype(bank_row.dtype)
