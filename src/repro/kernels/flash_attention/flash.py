"""Pallas TPU kernel: blocked causal flash attention (GQA + sliding window).

Grid ``(B, H, NQ, NK)`` with the K dimension innermost and "arbitrary"
(sequential) so the online-softmax accumulators live in VMEM scratch across
K steps. Per grid step the kernel sees:

    q   [block_q, d]   (VMEM, selected by the (b, h, iq) index map)
    k,v [block_k, d]   (VMEM, GQA: kv head = h // (H / KV))

and maintains f32 scratch ``acc [block_q, d]``, ``m/l [block_q, 128]``
(stat lanes). Causal/sliding-window masking is positional, computed from the
grid ids — no mask tensors are materialised. The matmuls hit the MXU at
(block_q x d) x (d x block_k) with d a multiple of 128 (callers pad).

``block_q/block_k`` default to 512: VMEM per step =
(512 + 2*512) * d * 2B + 512*d*4B ≈ 0.6 MiB at d=128 — well inside the
~16 MiB VMEM budget while large enough to amortise the DMA pipeline.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, block_q: int, block_k: int, n_k: int,
                  causal: bool, window: Optional[int], q_offset: int,
                  kv_len: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)           # [bq, d]
    k = k_ref[0, 0].astype(jnp.float32)           # [bk, d]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    qpos = q_offset + iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    kpos = ik * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = kpos < kv_len
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[:, 0]                          # [bq]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur[:, None])               # [bq, bk]
    l_cur = alpha * l_ref[:, 0] + jnp.sum(p, axis=1)

    v = v_ref[0, 0].astype(jnp.float32)           # [bk, d]
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = jnp.broadcast_to(m_cur[:, None], m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_cur[:, None], l_ref.shape)

    @pl.when(ik == n_k - 1)
    def _finish():
        l = l_ref[:, 0]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / safe[:, None]).astype(o_ref.dtype)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, window: Optional[int] = None,
                    q_offset: int = 0, block_q: int = 512,
                    block_k: int = 512, interpret: bool = False
                    ) -> jnp.ndarray:
    """q: [B, Sq, H, D]; k/v: [B, Sk, KV, D] -> [B, Sq, H, D]."""
    b, sq, h, d = q.shape
    sk, kv = k.shape[1], k.shape[2]
    assert h % kv == 0
    rep = h // kv
    scale = 1.0 / math.sqrt(d)

    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    pad_q = (-sq) % block_q
    pad_k = (-sk) % block_k
    qt = jnp.moveaxis(q, 2, 1)  # [B, H, Sq, D]
    kt = jnp.moveaxis(k, 2, 1)
    vt = jnp.moveaxis(v, 2, 1)
    if pad_q:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    sq_p, sk_p = sq + pad_q, sk + pad_k
    n_q, n_k = sq_p // block_q, sk_p // block_k

    kernel = functools.partial(
        _flash_kernel, scale=scale, block_q=block_q, block_k=block_k,
        n_k=n_k, causal=causal, window=window, q_offset=q_offset,
        kv_len=sk)

    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"))
    out = pl.pallas_call(
        kernel,
        grid=(b, h, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda ib, ih, iq, ik: (ib, ih // rep, ik, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda ib, ih, iq, ik: (ib, ih // rep, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq_p, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        interpret=interpret,
        name="attention",
        **kwargs,
    )(qt, kt, vt)
    out = jnp.moveaxis(out, 1, 2)[:, :sq]
    return out
