"""Coordinated and local gradient sparsification (the paper's Section 2/3).

Three unbiased sparsifiers are provided, all with the ``d/k`` unbiasedness
scaling of RandK:

* ``randk``      — exact RandK: ``k`` distinct uniformly-random coordinates
                   (permutation-based; intended for small ``d``, e.g. the
                   paper's 11.8k-parameter CNN).
* ``bernoulli``  — per-coordinate Bernoulli(k/d) mask. Unbiased with the same
                   scaling; the expected payload is ``k``. Cheap at any ``d``.
* ``block``      — Block-RandK (TPU adaptation, see DESIGN §3): sample
                   ``k/B`` of the ``d/B`` aligned blocks of size ``B``.
                   Contiguous payload, VMEM/lane-aligned; still a coordinated
                   unbiased sparsifier.

Masks come in two flavours matching the paper:
* **global** (Algorithm 1, step 1): one mask per round, shared by all
  workers — realised with a replicated PRNG key (0-byte broadcast).
* **local** (§3.3 RoSDHB-Local): each worker draws its own mask.

Compression is *simulated* densely: the wire format would carry only the
``k`` selected values; here ``compress`` returns the reconstructed estimate
``(d/k) * (g ⊙ mask)`` directly (what the server computes in step 4), while
``payload_bytes`` accounts for the real communication volume used by the
benchmark harness.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.kernels import backend_label, resolve_kernel_backend


@dataclasses.dataclass(frozen=True)
class SparsifierConfig:
    """Configuration of the RandK-family sparsifier.

    Attributes:
      kind: ``randk`` | ``bernoulli`` | ``block`` | ``block_hash`` |
        ``natural`` | ``none``. ``natural`` is the paper's Appendix-C
        generalisation to arbitrary unbiased compressors: stochastic
        power-of-two rounding (Horvath et al. [20]), alpha = 9/8,
        ~9 bits/coordinate on the wire.
      ratio: compression ratio ``k/d`` in (0, 1]. ``alpha = 1/ratio``.
      block_size: block width for ``kind='block'``.
      local: if True, each worker samples its own mask (RoSDHB-Local);
        otherwise one global mask is shared (RoSDHB).
      use_pallas: Block-RandK compressor backend — ``None`` (default)
        auto-selects the ``repro.kernels.randk`` Pallas kernels on TPU and
        the jnp sparsifier elsewhere; ``True`` forces the kernel path
        (interpret mode off-TPU — parity testing); ``False`` forces jnp.
        Only ``kind='block'`` with a static ratio and
        ``d % block_size == 0`` has a kernel; everything else always runs
        the jnp path (same contract as ``AggregatorConfig.use_pallas``).
    """

    kind: str = "bernoulli"
    ratio: float = 1.0
    block_size: int = 512
    local: bool = False
    use_pallas: Optional[bool] = None

    @property
    def alpha(self) -> float:
        return 1.0 / self.ratio

    def k(self, d: int) -> int:
        return max(1, int(round(self.ratio * d)))


def _randk_mask(key: jax.Array, d: int, k: int, dtype) -> jnp.ndarray:
    """Exact RandK mask: k distinct coordinates set to 1."""
    idx = jax.random.permutation(key, d)[:k]
    return jnp.zeros((d,), dtype).at[idx].set(1)


def _bernoulli_mask(key: jax.Array, d: int, ratio: float, dtype) -> jnp.ndarray:
    return jax.random.bernoulli(key, ratio, (d,)).astype(dtype)


def _block_mask(key: jax.Array, d: int, ratio: float, block: int,
                dtype) -> jnp.ndarray:
    nb = -(-d // block)
    kb = max(1, int(round(ratio * nb)))
    bmask = jnp.zeros((nb,), dtype).at[jax.random.permutation(key, nb)[:kb]].set(1)
    full = jnp.repeat(bmask, block)[:d]
    return full


def _block_hash_mask(key: jax.Array, d: int, ratio: float, block: int,
                     dtype) -> jnp.ndarray:
    """Counter-based Bernoulli(ratio) block mask (§Perf iter 3).

    The permutation-based ``block`` mask materialises an UNSHARDED [d/B]
    vector (a 246M-element sort at 123B params) and a replicated repeat —
    at LLM scale GSPMD replicates ~[d] f32 per chip. This variant derives
    each block's keep/drop decision from a murmur-style integer hash of
    (block_id, per-round seed): pure elementwise ops over an iota, so GSPMD
    partitions it perfectly with zero communication and zero sort.

    Each block is kept independently with probability ``ratio`` — an
    unbiased coordinated sparsifier with the same (d/k) scaling (the exact-k
    guarantee of RandK is relaxed to E[k], as with ``bernoulli``).
    """
    seed = jax.random.bits(key, (), jnp.uint32)
    ids = jax.lax.iota(jnp.uint32, d) // jnp.uint32(block)
    h = ids * jnp.uint32(0x9E3779B1) + seed
    h ^= h >> 16
    h *= jnp.uint32(0x85EBCA6B)
    h ^= h >> 13
    h *= jnp.uint32(0xC2B2AE35)
    h ^= h >> 16
    u = h.astype(jnp.float32) * (1.0 / 4294967296.0)
    return (u < ratio).astype(dtype)


#: Sparsifier kinds whose keep-ratio may be a *traced* scalar — the mask
#: sampling and the unbiased rescale are pure elementwise functions of the
#: ratio, so a grid of ratios can join the vmapped fusion axis of
#: ``repro.core.sweep`` (the static-shape kinds randk/block cannot: their
#: ``k`` fixes index-array shapes at trace time).
TRACED_RATIO_KINDS = ("bernoulli", "block_hash")


def make_mask(key: jax.Array, d: int, cfg: SparsifierConfig,
              dtype=jnp.float32,
              ratio: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Sample one sparsification mask of shape ``[d]``.

    For ``kind='natural'`` the "mask" is the uniform rounding randomness
    u ~ U[0,1) consumed by :func:`compress`.

    ``ratio``, when given, is a traced scalar overriding ``cfg.ratio``
    (only for :data:`TRACED_RATIO_KINDS`)."""
    if ratio is not None:
        if cfg.kind == "bernoulli":
            return _bernoulli_mask(key, d, ratio, dtype)
        if cfg.kind == "block_hash":
            return _block_hash_mask(key, d, ratio, cfg.block_size, dtype)
        raise ValueError(
            f"sparsifier kind {cfg.kind!r} does not support a traced ratio "
            f"(supported: {TRACED_RATIO_KINDS})")
    if cfg.kind == "natural":
        return jax.random.uniform(key, (d,), dtype)
    if cfg.kind == "none" or cfg.ratio >= 1.0:
        return jnp.ones((d,), dtype)
    if cfg.kind == "randk":
        return _randk_mask(key, d, cfg.k(d), dtype)
    if cfg.kind == "bernoulli":
        return _bernoulli_mask(key, d, cfg.ratio, dtype)
    if cfg.kind == "block":
        return _block_mask(key, d, cfg.ratio, cfg.block_size, dtype)
    if cfg.kind == "block_hash":
        return _block_hash_mask(key, d, cfg.ratio, cfg.block_size, dtype)
    raise ValueError(f"unknown sparsifier kind: {cfg.kind!r}")


def make_masks(key: jax.Array, n_workers: int, d: int, cfg: SparsifierConfig,
               dtype=jnp.float32,
               ratio: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Sample masks ``[n_workers, d]``.

    With ``cfg.local=False`` (global sparsification, Algorithm 1) all rows are
    the *same* mask; with ``cfg.local=True`` (RoSDHB-Local, §3.3) each worker
    gets an independent mask. ``ratio`` optionally overrides ``cfg.ratio``
    with a traced scalar (see :func:`make_mask`).
    """
    if not cfg.local:
        m = make_mask(key, d, cfg, dtype, ratio=ratio)
        return jnp.broadcast_to(m, (n_workers, d))
    keys = jax.random.split(key, n_workers)
    return jax.vmap(lambda k: make_mask(k, d, cfg, dtype, ratio=ratio))(keys)


def compress(g: jnp.ndarray, mask: jnp.ndarray, cfg: SparsifierConfig,
             ratio: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Server-side unbiased reconstruction ``g̃ = (d/k)(g ⊙ mask)``.

    ``g`` may be ``[d]`` or ``[n, d]`` (with ``mask`` broadcastable).
    ``ratio`` optionally overrides ``cfg.ratio`` with a traced scalar; the
    unbiased rescale then uses the traced ``alpha = 1/ratio``.
    """
    if ratio is not None:
        return (g / ratio) * mask
    if cfg.kind == "natural":
        # stochastic power-of-two rounding: |x| in [2^e, 2^{e+1}) rounds up
        # with prob (|x|/2^e - 1); unbiased, E||C(x)||^2 <= (9/8)||x||^2.
        a = jnp.abs(g)
        safe = jnp.where(a > 0, a, 1.0)
        e = jnp.floor(jnp.log2(safe))
        lo = jnp.exp2(e)
        p = safe / lo - 1.0
        up = (mask < p).astype(g.dtype)
        out = jnp.sign(g) * lo * jnp.exp2(up)
        return jnp.where(a > 0, out, 0.0).astype(g.dtype)
    if cfg.kind == "none" or cfg.ratio >= 1.0:
        return g
    return (cfg.alpha * g) * mask


# --------------------------------------------------------------------------
# Pallas kernel backend (repro.kernels.randk) — Block-RandK round trip
# --------------------------------------------------------------------------


def _kernel_eligible(cfg: SparsifierConfig, d: int,
                     ratio: Optional[jnp.ndarray]) -> bool:
    """Only exact Block-RandK with a static keep-ratio and block-aligned
    ``d`` has a kernel; anything else stays on the jnp sparsifier."""
    return (cfg.kind == "block" and ratio is None and cfg.ratio < 1.0
            and d % cfg.block_size == 0)


def _kernel_backend(cfg: SparsifierConfig, d: int,
                    ratio: Optional[jnp.ndarray]
                    ) -> Optional[Dict[str, bool]]:
    """The backend :func:`compressed_estimate` runs for a ``[n, d]`` bank:
    ``cfg.use_pallas`` resolved against the live backend
    (``repro.kernels.resolve_kernel_backend``), and ``None`` (jnp) for a
    config with no kernel (:func:`_kernel_eligible`)."""
    if not _kernel_eligible(cfg, d, ratio):
        return None
    return resolve_kernel_backend(cfg.use_pallas)


def kernel_backend_label(cfg: SparsifierConfig, d: int,
                         ratio: Optional[jnp.ndarray] = None) -> str:
    """Backend :func:`compressed_estimate` runs for this config on a
    ``[n, d]`` bank (with the same optional traced ``ratio``): ``pallas`` |
    ``pallas-interpret`` | ``jnp`` — ``jnp`` also where the kernels are
    selected but the config is not kernel-eligible."""
    return backend_label(_kernel_backend(cfg, d, ratio))


def compressed_estimate(grads: jnp.ndarray, mask_key: jax.Array,
                        cfg: SparsifierConfig,
                        ratio: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Steps 1+4 in one call: sample the round's masks from ``mask_key`` and
    return the server-side unbiased reconstruction ``(d/k)(g ⊙ mask)`` for a
    ``[n, d]`` gradient bank.

    The jnp path is literally :func:`make_masks` + :func:`compress` — the
    trajectory graph is unchanged. When the resolved backend
    (:func:`_kernel_backend`, reported by :func:`kernel_backend_label`)
    selects the Pallas kernels and the config is kernel-eligible, the dense
    mask-multiply is replaced by the ``repro.kernels.randk``
    compress → decompress round trip over the REAL wire payload
    (``[k_blocks * block_size]`` values + block ids): block ids are sampled
    with exactly the ``_block_mask`` permutation (same key, same
    ``round(ratio * nb)`` count — global masks share one id vector, local
    masks split the key per worker), and the scatter of ``alpha * g`` is
    bitwise the f32 mask-multiply on finite gradients. Each kernel is one
    call over the whole bank.
    """
    n, d = grads.shape
    kb = _kernel_backend(cfg, d, ratio)
    if kb is None:
        masks = make_masks(mask_key, n, d, cfg, dtype=grads.dtype,
                           ratio=ratio)
        return compress(grads, masks, cfg, ratio=ratio)

    from repro.kernels.randk import ops as RK
    nb = d // cfg.block_size
    k_blocks = max(1, int(round(cfg.ratio * nb)))

    def block_ids(key: jax.Array) -> jnp.ndarray:
        # identical sampling to _block_mask: permutation prefix of the
        # block index set (order is irrelevant to the reconstruction)
        return jax.random.permutation(key, nb)[:k_blocks].astype(jnp.int32)

    if cfg.local:
        ids = jax.vmap(block_ids)(jax.random.split(mask_key, n))
    else:
        ids = block_ids(mask_key)
    payload = RK.compress(grads, ids, block_size=cfg.block_size,
                          alpha=cfg.alpha, use_pallas=True,
                          interpret=kb["interpret"])
    return RK.decompress(payload, ids, block_size=cfg.block_size, d=d,
                         use_pallas=True, interpret=kb["interpret"])


def payload_floats(d: int, cfg: SparsifierConfig) -> int:
    """Number of float values one worker sends per round (wire payload)."""
    if cfg.kind == "none" or cfg.ratio >= 1.0:
        return d
    return cfg.k(d)


def index_bytes(d: int) -> int:
    """Bytes needed to address one of ``d`` coordinates:
    ``ceil(log2(d) / 8)``, at least 1.

    A flat 4 bytes per index (the old accounting) overstates the index
    overhead by 4x for models under 2^8 coordinates and by 2x under 2^16 —
    at the paper's 11.8k-parameter CNN that error dominates the
    comm-to-threshold comparison for small keep-ratios.
    """
    if d < 2:
        return 1
    return max(1, math.ceil(math.log2(d) / 8.0))


def payload_bytes(d: int, cfg: SparsifierConfig, bytes_per_value: int = 4,
                  with_mask_indices: bool = False) -> int:
    """Per-worker uplink bytes per round.

    With global sparsification the mask is derived from a shared PRNG, so no
    index bits are sent. With local sparsification the worker must identify
    its coordinates; we charge :func:`index_bytes` — ``ceil(log2(d)/8)`` —
    bytes per index when requested (the minimal fixed-width index encoding,
    so comm-to-threshold curves stay honest for small models).
    """
    if cfg.kind == "natural":
        # sign + 8-bit exponent per coordinate
        return int(d * 9 / 8 / 4 * bytes_per_value)
    k = payload_floats(d, cfg)
    b = k * bytes_per_value
    if with_mask_indices and cfg.local and cfg.ratio < 1.0:
        b += k * index_bytes(d)
    return b
