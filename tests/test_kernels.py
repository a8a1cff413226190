"""Per-kernel interpret-mode sweeps against the pure-jnp oracles
(shape x dtype grids per the deliverable-c requirement)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.cwtm import cwtm_pallas, cwtm_pallas_batched, cwtm_ref
from repro.kernels.flash_attention import attention_ref, flash_attention
from repro.kernels.median import median_pallas_batched, median_ref
from repro.kernels.pairdist import pairdist_pallas_batched, pairdist_ref
from repro.kernels.randk import (
    block_compress, block_compress_ref, block_decompress,
    block_decompress_ref, momentum_scatter, momentum_scatter_ref,
)

KEY = jax.random.PRNGKey(0)


# --------------------------------------------------------------------------
# cwtm
# --------------------------------------------------------------------------


@pytest.mark.parametrize("n,f", [(4, 1), (10, 2), (16, 3), (19, 9), (32, 7)])
@pytest.mark.parametrize("d", [128, 300, 4096])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_cwtm_sweep(n, f, d, dtype):
    x = (jax.random.normal(jax.random.PRNGKey(n * d + f), (n, d)) * 3
         ).astype(dtype)
    got = cwtm_pallas(x, f, block_d=256, interpret=True)
    want = cwtm_ref(x, f)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol,
                               rtol=tol)


def test_cwtm_handles_outliers_like_ref():
    x = jax.random.normal(KEY, (10, 512))
    x = x.at[:3].set(1e9)
    got = cwtm_pallas(x, 3, block_d=256, interpret=True)
    assert float(jnp.max(jnp.abs(got))) < 10.0


# --------------------------------------------------------------------------
# batched aggregation kernels (the grid engine's [B, n, d] layout)
# --------------------------------------------------------------------------

# awkward-shape sweep: n odd / not a power of two (bitonic padding path),
# d not a multiple of the 128-lane tile (block padding path), f=0 (cwtm
# degenerates to the mean), n-2f=1 (single surviving rank)
AWKWARD = [(3, 13, 3, 300), (2, 7, 0, 130), (4, 5, 2, 257),
           (1, 19, 9, 128), (5, 4, 1, 64), (2, 16, 3, 1024)]


@pytest.mark.parametrize("b,n,f,d", AWKWARD)
def test_cwtm_batched_sweep(b, n, f, d):
    x = jax.random.normal(jax.random.PRNGKey(b * d + n), (b, n, d)) * 3
    got = cwtm_pallas_batched(x, f, block_d=256, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(cwtm_ref(x, f)),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("b,n,f,d", AWKWARD)
def test_median_batched_sweep(b, n, f, d):
    x = jax.random.normal(jax.random.PRNGKey(b * d + n + 1), (b, n, d)) * 3
    got = median_pallas_batched(x, block_d=256, interpret=True)
    # rank selection out of the same sort network is exact, even-n midpoint
    # averaging matches jnp.median's convention bit-for-bit in f32
    np.testing.assert_allclose(np.asarray(got), np.asarray(median_ref(x)),
                               atol=1e-6, rtol=1e-6)


@pytest.mark.parametrize("b,n,f,d", AWKWARD)
def test_pairdist_batched_sweep(b, n, f, d):
    x = jax.random.normal(jax.random.PRNGKey(b * d + n + 2), (b, n, d)) * 3
    got = pairdist_pallas_batched(x, block_d=256, interpret=True)
    want = pairdist_ref(x)
    assert got.shape == (b, n, n)
    # atol covers blocked-Gram sum reordering against the oracle's single
    # Gram product (sq_i + sq_j - 2 G_ij, a difference of large terms)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=5e-2, rtol=1e-5)
    # self-distances are exactly zero (diag of the same accumulated Gram)
    diag = np.asarray(got)[:, np.arange(n), np.arange(n)]
    np.testing.assert_array_equal(diag, np.zeros_like(diag))


def test_batched_matches_vmapped_2d():
    """The explicit [B, n, d] launch equals vmap of the per-lane kernel —
    the equivalence `repro.kernels.batchable` relies on."""
    x = jax.random.normal(KEY, (4, 10, 300))
    b1 = cwtm_pallas_batched(x, 2, block_d=256, interpret=True)
    b2 = jax.vmap(lambda r: cwtm_pallas(r, 2, block_d=256, interpret=True))(x)
    np.testing.assert_allclose(np.asarray(b1), np.asarray(b2), atol=1e-6)


# --------------------------------------------------------------------------
# randk (block compress / decompress / fused momentum)
# --------------------------------------------------------------------------


@pytest.mark.parametrize("d,bs,kb", [(2048, 128, 4), (4096, 256, 7),
                                     (8192, 512, 3)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_randk_roundtrip_sweep(d, bs, kb, dtype):
    n, nb = 4, d // bs
    g = jax.random.normal(KEY, (n, d)).astype(dtype)
    idx = jnp.sort(jax.random.permutation(jax.random.PRNGKey(d), nb)[:kb])
    alpha = float(nb) / kb
    p = block_compress(g, idx, bs, alpha, interpret=True)
    p_ref = block_compress_ref(g, idx, bs, alpha)
    np.testing.assert_array_equal(np.asarray(p), np.asarray(p_ref))
    dn = block_decompress(p, idx, bs, d, interpret=True)
    dn_ref = block_decompress_ref(p_ref, idx, bs, d)
    np.testing.assert_array_equal(np.asarray(dn), np.asarray(dn_ref))


def _kept_ids(pattern, nb, kb, tile, key):
    """Sorted kept block ids: ``random`` a permutation prefix; ``runs``
    whole tiles kept around tiles with none (kb a multiple of tile)."""
    if pattern == "random":
        return jnp.sort(jax.random.permutation(key, nb)[:kb])
    first = int(jax.random.randint(key, (), 0, nb // tile - kb // tile))
    return jnp.arange(first * tile, first * tile + kb)


# (n, nb, kb, step, window, pattern): step is the blocks a grid step
# covers, set through TILE_BYTES; nb % step != 0 leaves a ragged last
# tile; "runs" gives tiles with no kept block and tiles all kept; an id
# window as short as the tile splits some tiles' ids over two windows, and
# several chunks share a longer one; window None keeps the TPU's own
BANK_CASES = [
    (4, 37, 11, 4, None, "random"),
    (3, 64, 30, 8, 8, "random"),
    (2, 41, 16, 8, 8, "runs"),
    (4, 40, 24, 8, 16, "runs"),
    (4, 40, 24, 4, 4, "random"),
    (1, 19, 19, 16, None, "random"),
    (5, 130, 40, 128, None, "random"),
]


@pytest.mark.parametrize("local", [False, True], ids=["global", "local"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("n,nb,kb,step,window,pattern", BANK_CASES)
def test_randk_bank_round_trip(monkeypatch, n, nb, kb, step, window, pattern,
                               dtype, local):
    """compress and decompress over a whole [n, D] bank, for one shared id
    vector or one per row, match the oracles bit for bit."""
    from repro.kernels.randk import randk as K
    if window is not None:
        monkeypatch.setattr(K, "ID_WINDOW", window)
    bs = 128
    rows = max(K._dma_rows(n, dtype), 4)
    monkeypatch.setattr(K, "TILE_BYTES",
                        step * rows * bs * jnp.dtype(dtype).itemsize)
    assert K._blocks_per_step(rows, bs, dtype) == step
    d = nb * bs
    g = jax.random.normal(jax.random.PRNGKey(n * nb + kb), (n, d)
                          ).astype(dtype)
    keys = jax.random.split(jax.random.PRNGKey(kb), n)
    tile = min(step, nb)
    idx = (jnp.stack([_kept_ids(pattern, nb, kb, tile, k) for k in keys])
           if local else _kept_ids(pattern, nb, kb, tile, keys[0]))
    idx = idx.astype(jnp.int32)
    p = K.block_compress(g, idx, bs, 3.0, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(p), np.asarray(block_compress_ref(g, idx, bs, 3.0)))
    dn = K.block_decompress(p, idx, bs, d, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(dn), np.asarray(block_decompress_ref(p, idx, bs, d)))


@pytest.mark.parametrize("local", [False, True], ids=["global", "local"])
def test_randk_ops_ids_are_a_set(local):
    """ops.compress / ops.decompress take ids in any order: both backends
    send the kept blocks in ascending order and rebuild the same bank."""
    from repro.kernels.randk import ops as RK
    n, bs, nb, kb = 4, 128, 24, 7
    g = jax.random.normal(KEY, (n, nb * bs))
    keys = jax.random.split(jax.random.PRNGKey(9), n)
    perm = [jax.random.permutation(k, nb)[:kb] for k in keys]
    idx = (jnp.stack(perm) if local else perm[0]).astype(jnp.int32)
    out = {}
    for pallas in (False, True):
        p = RK.compress(g, idx, block_size=bs, alpha=2.0, use_pallas=pallas,
                        interpret=pallas)
        out[pallas] = (p, RK.decompress(p, idx, block_size=bs, d=nb * bs,
                                        use_pallas=pallas, interpret=pallas))
    np.testing.assert_array_equal(np.asarray(out[True][0]),
                                  np.asarray(out[False][0]))
    np.testing.assert_array_equal(np.asarray(out[True][1]),
                                  np.asarray(out[False][1]))
    kept = jnp.broadcast_to(jnp.zeros(nb, bool).at[idx].set(True)
                            if not local else
                            jax.vmap(lambda i: jnp.zeros(nb, bool).at[i]
                                     .set(True))(idx), (n, nb))
    want = jnp.where(jnp.repeat(kept, bs, axis=1), 2.0 * g, 0.0)
    np.testing.assert_array_equal(np.asarray(out[True][1]), np.asarray(want))


@pytest.mark.parametrize("beta", [0.0, 0.9, 0.99])
def test_momentum_scatter_sweep(beta):
    d, bs, kb = 4096, 256, 5
    nb = d // bs
    row = jax.random.normal(KEY, (d,))
    idx = jnp.sort(jax.random.permutation(jax.random.PRNGKey(1), nb)[:kb])
    payload = jax.random.normal(jax.random.PRNGKey(2), (kb * bs,))
    got = momentum_scatter(row, payload, idx, bs, beta, interpret=True)
    want = momentum_scatter_ref(row, payload, idx, bs, beta)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


@pytest.mark.parametrize("max_prefetch", [3, 8])
def test_randk_segmented_prefetch(monkeypatch, max_prefetch):
    """Slot vectors longer than one call may prefetch run the momentum
    scatter as a chain of calls writing one aliased output; it matches
    its oracle."""
    from repro.kernels.randk import randk as K
    monkeypatch.setattr(K, "MAX_PREFETCH", max_prefetch)
    d, bs, kb = 4096, 128, 10
    nb = d // bs
    g = jax.random.normal(KEY, (d,))
    p = jax.random.normal(jax.random.PRNGKey(4), (kb * bs,))
    idx = jax.random.permutation(jax.random.PRNGKey(3), nb)[:kb]
    idx = idx.astype(jnp.int32)
    np.testing.assert_allclose(
        np.asarray(momentum_scatter(g, p, idx, bs, 0.9, interpret=True)),
        np.asarray(momentum_scatter_ref(g, p, idx, bs, 0.9)), atol=1e-6)


def test_interpret_mode_refused_on_tpu(monkeypatch):
    import repro.kernels
    from repro.kernels.cwtm import cwtm
    monkeypatch.setattr(repro.kernels, "on_tpu", lambda: True)
    with pytest.raises(ValueError, match="interpret mode"):
        repro.kernels.use_kernel(True, True)
    with pytest.raises(ValueError, match="interpret mode"):
        cwtm.__wrapped__(jnp.ones((5, 256)), 1, use_pallas=True,
                         interpret=True)
    assert repro.kernels.resolve_kernel_backend(None) == {"interpret": False}


def test_compress_unbiased_with_decompress():
    """decompress(compress(g)) is the paper's unbiased estimate (d/k scaled
    selected blocks, zeros elsewhere)."""
    d, bs = 1024, 128
    nb = d // bs
    g = jax.random.normal(KEY, (2, d))
    idx = jnp.array([0, 3], jnp.int32)
    alpha = nb / 2
    est = block_decompress(block_compress(g, idx, bs, alpha, interpret=True),
                           idx, bs, d, interpret=True)
    dense = np.zeros((2, d), np.float32)
    dense[:, :bs] = np.asarray(g[:, :bs]) * alpha
    dense[:, 3 * bs:4 * bs] = np.asarray(g[:, 3 * bs:4 * bs]) * alpha
    np.testing.assert_allclose(np.asarray(est), dense, rtol=1e-5)


# --------------------------------------------------------------------------
# flash attention
# --------------------------------------------------------------------------


@pytest.mark.parametrize("sq,sk,h,kv,d", [
    (128, 128, 4, 2, 64),
    (256, 256, 4, 1, 128),
    (64, 192, 4, 4, 64),
    (96, 96, 2, 2, 64),     # non-multiple of block -> padding path
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_sweep(sq, sk, h, kv, d, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, sq, h, d)).astype(dtype)
    k = jax.random.normal(ks[1], (2, sk, kv, d)).astype(dtype)
    v = jax.random.normal(ks[2], (2, sk, kv, d)).astype(dtype)
    got = flash_attention(q, k, v, block_q=64, block_k=64, interpret=True)
    want = attention_ref(q, k, v)
    tol = 2e-3 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol)


@pytest.mark.parametrize("window", [32, 96])
def test_flash_sliding_window(window):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 256, 2, 64))
    k = jax.random.normal(ks[1], (1, 256, 2, 64))
    v = jax.random.normal(ks[2], (1, 256, 2, 64))
    got = flash_attention(q, k, v, window=window, block_q=64, block_k=64,
                          interpret=True)
    want = attention_ref(q, k, v, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-3)


def test_flash_q_offset_decode_chunk():
    """Continuation chunk: q at positions [128, 192) against 192 keys."""
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, 64, 4, 64))
    k = jax.random.normal(ks[1], (2, 192, 4, 64))
    v = jax.random.normal(ks[2], (2, 192, 4, 64))
    got = flash_attention(q, k, v, q_offset=128, block_q=64, block_k=64,
                          interpret=True)
    want = attention_ref(q, k, v, q_offset=128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-3)


def test_flash_matches_model_attention_path():
    """The XLA attention used by the models equals the kernel's math."""
    from repro.models.layers import causal_attention
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, 128, 4, 64))
    k = jax.random.normal(ks[1], (2, 128, 2, 64))
    v = jax.random.normal(ks[2], (2, 128, 2, 64))
    got = flash_attention(q, k, v, block_q=64, block_k=64, interpret=True)
    want = causal_attention(q, k, v, q_offset=0, chunk=64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-3)


def test_ops_attention_pads_non_lane_head_dim():
    """ops.attention zero-pads D=64 -> 128 for the kernel and rescales q so
    the softmax temperature stays 1/sqrt(64); must match the XLA reference
    (the head dim every reduced() config uses)."""
    from repro.kernels.flash_attention.ops import attention
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, 96, 4, 64))
    k = jax.random.normal(ks[1], (2, 96, 2, 64))
    v = jax.random.normal(ks[2], (2, 96, 2, 64))
    got = attention(q, k, v, use_pallas=True, interpret=True)
    want = attention(q, k, v, use_pallas=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-3)
    # sliding-window + offset through the same padding path
    got_w = attention(q, k, v, window=32, q_offset=64, use_pallas=True,
                      interpret=True)
    want_w = attention(q, k, v, window=32, q_offset=64, use_pallas=False)
    np.testing.assert_allclose(np.asarray(got_w), np.asarray(want_w),
                               atol=2e-3)


def test_flash_flag_through_transformer_forward():
    """use_flash_attention=True (interpret mode off-TPU) reproduces the
    chunked-XLA train-mode forward of a reduced dense config within bf16
    accumulation noise."""
    from repro.configs.base import get_arch
    from repro.models import transformer as TR

    cfg_ref = get_arch("stablelm_3b").model.reduced(
        n_layers=2, d_model=256).with_overrides(use_flash_attention=False)
    cfg_flash = cfg_ref.with_overrides(use_flash_attention=True)
    params = TR.model_init(jax.random.PRNGKey(0), cfg_ref)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0,
                                cfg_ref.vocab_size)
    batch = {"tokens": tokens}
    loss_ref = float(TR.lm_loss(params, cfg_ref, batch))
    loss_flash = float(TR.lm_loss(params, cfg_flash, batch))
    assert abs(loss_flash - loss_ref) < 1e-2, (loss_flash, loss_ref)
    h_ref, _, _ = TR.forward(params, cfg_ref, batch, mode="train")
    h_flash, _, _ = TR.forward(params, cfg_flash, batch, mode="train")
    np.testing.assert_allclose(np.asarray(h_flash, np.float32),
                               np.asarray(h_ref, np.float32), atol=0.1)
