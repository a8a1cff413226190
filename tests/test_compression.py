"""Sparsifier properties: unbiasedness, variance envelope, payload, masks —
under both static-config and traced keep-ratios (the fused grid axis)."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import compression as C
from repro.kernels.randk import randk as K


@pytest.mark.parametrize("kind", ["randk", "bernoulli", "block", "block_hash"])
def test_mask_shape_and_dtype(kind):
    cfg = C.SparsifierConfig(kind=kind, ratio=0.25, block_size=8)
    m = C.make_mask(jax.random.PRNGKey(0), 64, cfg)
    assert m.shape == (64,)
    assert set(np.unique(np.asarray(m))) <= {0.0, 1.0}


def test_randk_exact_k():
    cfg = C.SparsifierConfig(kind="randk", ratio=0.25)
    m = C.make_mask(jax.random.PRNGKey(1), 100, cfg)
    assert int(np.asarray(m).sum()) == 25


def test_block_mask_is_blockwise():
    cfg = C.SparsifierConfig(kind="block", ratio=0.25, block_size=16)
    m = np.asarray(C.make_mask(jax.random.PRNGKey(2), 128, cfg))
    blocks = m.reshape(-1, 16)
    # every block entirely 0 or entirely 1
    assert np.all((blocks.sum(1) == 0) | (blocks.sum(1) == 16))
    assert blocks.sum() == 32  # 2 of 8 blocks


def test_global_masks_identical_local_differ():
    g = C.SparsifierConfig(kind="randk", ratio=0.2, local=False)
    l = C.SparsifierConfig(kind="randk", ratio=0.2, local=True)
    mg = np.asarray(C.make_masks(jax.random.PRNGKey(3), 6, 50, g))
    ml = np.asarray(C.make_masks(jax.random.PRNGKey(3), 6, 50, l))
    assert np.all(mg == mg[0])
    assert not np.all(ml == ml[0])


@pytest.mark.parametrize("kind", ["randk", "bernoulli", "block", "block_hash"])
def test_unbiasedness(kind):
    """E[(d/k)(g o mask)] = g over mask randomness (paper, Section 2)."""
    d = 64
    cfg = C.SparsifierConfig(kind=kind, ratio=0.25, block_size=8)
    g = jax.random.normal(jax.random.PRNGKey(0), (d,))
    keys = jax.random.split(jax.random.PRNGKey(42), 4000)
    est = jax.vmap(
        lambda k: C.compress(g, C.make_mask(k, d, cfg), cfg))(keys)
    mean = jnp.mean(est, axis=0)
    assert float(jnp.max(jnp.abs(mean - g))) < 0.15 * float(
        jnp.max(jnp.abs(g)) + 0.3)


def test_randk_variance_envelope():
    """E||g_tilde - g||^2 <= (alpha - 1)||g||^2 for exact RandK."""
    d = 60
    cfg = C.SparsifierConfig(kind="randk", ratio=0.2)
    g = jax.random.normal(jax.random.PRNGKey(0), (d,))
    keys = jax.random.split(jax.random.PRNGKey(7), 3000)
    est = jax.vmap(
        lambda k: C.compress(g, C.make_mask(k, d, cfg), cfg))(keys)
    var = float(jnp.mean(jnp.sum(jnp.square(est - g[None]), axis=1)))
    bound = (cfg.alpha - 1.0) * float(jnp.sum(jnp.square(g)))
    assert var <= 1.1 * bound


@given(d=st.integers(8, 300), ratio=st.floats(0.05, 1.0))
@settings(max_examples=25, deadline=None)
def test_payload_counts(d, ratio):
    cfg = C.SparsifierConfig(kind="randk", ratio=ratio)
    k = C.payload_floats(d, cfg)
    assert 1 <= k <= d
    # global sparsification sends no index bits (shared PRNG)
    assert C.payload_bytes(d, cfg, with_mask_indices=True) == 4 * k
    # local sparsification charges ceil(log2(d)/8) bytes per index — NOT a
    # flat 4 — so comm-to-threshold curves stay honest for small models
    loc = C.SparsifierConfig(kind="randk", ratio=ratio, local=True)
    idx = max(1, math.ceil(math.log2(d) / 8.0))
    expected = (4 + idx) * k if ratio < 1.0 else 4 * k
    assert C.payload_bytes(d, loc, with_mask_indices=True) == expected


def test_index_bytes_scales_with_log_dimension():
    assert C.index_bytes(1) == 1
    assert C.index_bytes(200) == 1
    assert C.index_bytes(256) == 1  # 8 bits address 0..255
    assert C.index_bytes(257) == 2
    assert C.index_bytes(11_800) == 2  # the paper's CNN scale
    assert C.index_bytes(1 << 16) == 2
    assert C.index_bytes((1 << 16) + 1) == 3
    assert C.index_bytes(1 << 26) == 4  # LLM scale: 4 bytes, the old flat rate
    # small-d local payloads are strictly cheaper than the old accounting
    loc = C.SparsifierConfig(kind="randk", ratio=0.25, local=True)
    k = C.payload_floats(200, loc)
    assert C.payload_bytes(200, loc, with_mask_indices=True) == 5 * k < 8 * k


def test_compress_none_identity():
    cfg = C.SparsifierConfig(kind="none")
    g = jnp.arange(10.0)
    assert jnp.all(C.compress(g, jnp.ones(10), cfg) == g)


def test_block_hash_deterministic_and_blockwise():
    """The TPU-scale coordinated mask: same key -> identical mask on every
    worker (0-byte broadcast), different round keys -> different masks,
    decisions constant within blocks."""
    cfg = C.SparsifierConfig(kind="block_hash", ratio=0.3, block_size=8)
    m1 = np.asarray(C.make_mask(jax.random.PRNGKey(5), 256, cfg))
    m2 = np.asarray(C.make_mask(jax.random.PRNGKey(5), 256, cfg))
    m3 = np.asarray(C.make_mask(jax.random.PRNGKey(6), 256, cfg))
    assert np.array_equal(m1, m2)
    assert not np.array_equal(m1, m3)
    blocks = m1.reshape(-1, 8)
    assert np.all((blocks.sum(1) == 0) | (blocks.sum(1) == 8))


def test_natural_compression_unbiased_and_bounded():
    """Appendix C: stochastic power-of-two rounding is an unbiased
    compressor with E||C(x)||^2 <= (9/8)||x||^2."""
    cfg = C.SparsifierConfig(kind="natural")
    g = jnp.asarray([0.75, -1.3, 3.0, 0.0, 1e-4, -2.0, 5.5, 0.001])
    keys = jax.random.split(jax.random.PRNGKey(0), 6000)
    est = jax.vmap(
        lambda k: C.compress(g, C.make_mask(k, g.shape[0], cfg), cfg))(keys)
    mean = jnp.mean(est, axis=0)
    np.testing.assert_allclose(np.asarray(mean), np.asarray(g), rtol=0.05,
                               atol=1e-6)
    # all outputs are signed powers of two (or zero)
    nz = np.asarray(est)[np.asarray(est) != 0]
    exps = np.log2(np.abs(nz))
    np.testing.assert_allclose(exps, np.round(exps), atol=1e-5)
    second = float(jnp.mean(jnp.sum(jnp.square(est), axis=1)))
    assert second <= 9 / 8 * float(jnp.sum(jnp.square(g))) * 1.05
    # wire cost ~9 bits/coordinate
    assert C.payload_bytes(1024, cfg) < 1024 * 4 / 3


# --------------------------------------------------------------------------
# Compressor contracts under static AND traced keep-ratios (satellite):
# the fused grid axis feeds the ratio in as data, so the keep-ratio,
# unbiasedness, and contraction properties must hold on both paths.
# --------------------------------------------------------------------------


@given(ratio=st.floats(0.1, 0.9))
@settings(max_examples=10, deadline=None)
def test_traced_ratio_mask_matches_static(ratio):
    """Contract: a traced ratio reproduces the static-config mask exactly
    (same key), so fusing the ratio axis cannot change trajectories."""
    d = 192
    for kind in C.TRACED_RATIO_KINDS:
        cfg = C.SparsifierConfig(kind=kind, ratio=ratio, block_size=8)
        neutral = C.SparsifierConfig(kind=kind, ratio=1.0, block_size=8)
        key = jax.random.PRNGKey(int(ratio * 1e6))
        m_static = C.make_mask(key, d, cfg)
        m_traced = C.make_mask(key, d, neutral, ratio=jnp.float32(ratio))
        np.testing.assert_array_equal(np.asarray(m_static),
                                      np.asarray(m_traced), err_msg=kind)


@given(ratio=st.floats(0.1, 0.9))
@settings(max_examples=5, deadline=None)
def test_keep_ratio_static_and_traced(ratio):
    """E[k]/d ~= ratio for the Bernoulli-family sparsifiers, on both the
    static and the traced path."""
    d = 256
    for kind in C.TRACED_RATIO_KINDS:
        cfg = C.SparsifierConfig(kind=kind, ratio=ratio, block_size=8)
        keys = jax.random.split(jax.random.PRNGKey(3), 400)
        dens_s = jax.vmap(lambda k: jnp.mean(C.make_mask(k, d, cfg)))(keys)
        dens_t = jax.vmap(lambda k: jnp.mean(C.make_mask(
            k, d, C.SparsifierConfig(kind=kind, ratio=1.0, block_size=8),
            ratio=jnp.float32(ratio))))(keys)
        assert abs(float(jnp.mean(dens_s)) - ratio) < 0.05, kind
        assert abs(float(jnp.mean(dens_t)) - ratio) < 0.05, kind


def test_randk_exact_keep_ratio_property():
    """randk's k is exact (not just in expectation) for every ratio/d."""
    for d in (17, 64, 201):
        for ratio in (0.1, 0.33, 0.8):
            cfg = C.SparsifierConfig(kind="randk", ratio=ratio)
            m = C.make_mask(jax.random.PRNGKey(d), d, cfg)
            assert int(np.asarray(m).sum()) == cfg.k(d)


@pytest.mark.parametrize("kind", C.TRACED_RATIO_KINDS)
def test_unbiasedness_under_traced_ratio(kind):
    """E[(1/r)(g o mask)] = g when the ratio arrives as traced data."""
    d, ratio = 64, 0.25
    neutral = C.SparsifierConfig(kind=kind, ratio=1.0, block_size=8)
    g = jax.random.normal(jax.random.PRNGKey(0), (d,))
    keys = jax.random.split(jax.random.PRNGKey(42), 4000)
    r = jnp.float32(ratio)
    est = jax.vmap(lambda k: C.compress(
        g, C.make_mask(k, d, neutral, ratio=r), neutral, ratio=r))(keys)
    mean = jnp.mean(est, axis=0)
    assert float(jnp.max(jnp.abs(mean - g))) < 0.15 * float(
        jnp.max(jnp.abs(g)) + 0.3)


@pytest.mark.parametrize("traced", [False, True])
def test_bernoulli_contraction_envelope(traced):
    """E||C(g) - g||^2 = (1/r - 1)||g||^2 for Bernoulli masks (the alpha-
    scaled variance bound of the paper's omega-compressor class), identical
    on the static and traced paths."""
    d, ratio = 80, 0.2
    g = jax.random.normal(jax.random.PRNGKey(1), (d,))
    keys = jax.random.split(jax.random.PRNGKey(9), 4000)
    if traced:
        cfg = C.SparsifierConfig(kind="bernoulli", ratio=1.0)
        r = jnp.float32(ratio)
        est = jax.vmap(lambda k: C.compress(
            g, C.make_mask(k, d, cfg, ratio=r), cfg, ratio=r))(keys)
    else:
        cfg = C.SparsifierConfig(kind="bernoulli", ratio=ratio)
        est = jax.vmap(
            lambda k: C.compress(g, C.make_mask(k, d, cfg), cfg))(keys)
    var = float(jnp.mean(jnp.sum(jnp.square(est - g[None]), axis=1)))
    bound = (1.0 / ratio - 1.0) * float(jnp.sum(jnp.square(g)))
    assert 0.85 * bound <= var <= 1.15 * bound


def test_clip_norm_bounds_worker_rows():
    from repro.core import (AlgorithmConfig, AggregatorConfig, AttackConfig,
                            init_state, server_round)
    cfg = AlgorithmConfig(name="rosdhb", n_workers=4, f=0, beta=0.0,
                          clip_norm=1.0,
                          sparsifier=C.SparsifierConfig(kind="none"),
                          aggregator=AggregatorConfig(name="mean"),
                          attack=AttackConfig(name="none"))
    st = init_state(cfg, 8)
    g = jnp.ones((4, 8)) * 100.0
    r, _, _ = server_round(cfg, st, g, jax.random.PRNGKey(0))
    # each row clipped to norm 1 -> mean direction has norm <= 1
    assert float(jnp.linalg.norm(r)) <= 1.0 + 1e-5


# --------------------------------------------------------------------------
# Pallas rand-k kernel dispatch (compressed_estimate use_pallas path)
# --------------------------------------------------------------------------


@pytest.mark.parametrize("local", [False, True])
def test_compressed_estimate_kernel_matches_jnp(local):
    """The Pallas block-rand-k round trip (interpret mode off-TPU) must be
    bit-for-bit the jnp mask-multiply: the traced-mask contract samples the
    SAME blocks from the same key, and keep/zero is exact in f32."""
    n, d, block = 6, 512, 128
    cfg = C.SparsifierConfig(kind="block", ratio=0.25, block_size=block,
                             local=local)
    grads = jax.random.normal(jax.random.PRNGKey(3), (n, d))
    key = jax.random.PRNGKey(7)
    ref = C.compressed_estimate(grads, key, dataclasses.replace(cfg, use_pallas=False))
    got = C.compressed_estimate(grads, key, dataclasses.replace(cfg, use_pallas=True))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    # sanity: it actually compressed (3/4 of blocks zeroed)
    kept = float(jnp.mean(jnp.any(ref.reshape(n, -1, block) != 0, axis=-1)))
    assert kept <= 0.5


@pytest.mark.parametrize("local", [False, True])
def test_compressed_estimate_kernel_matches_jnp_ragged_tile(local):
    """1,100 blocks a row, 1,024 a decompress tile: a ragged last tile of
    76, and the round trip is still the mask-multiply."""
    n, block = 4, 128
    d = 1100 * block
    assert K._blocks_per_step(n, block, jnp.float32) == 1024
    cfg = C.SparsifierConfig(kind="block", ratio=0.1, block_size=block,
                             local=local)
    grads = jax.random.normal(jax.random.PRNGKey(4), (n, d))
    key = jax.random.PRNGKey(8)
    ref = C.compressed_estimate(grads, key, dataclasses.replace(cfg, use_pallas=False))
    got = C.compressed_estimate(grads, key, dataclasses.replace(cfg, use_pallas=True))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_compressed_estimate_kernel_ineligible_falls_back():
    """d not a block multiple / traced ratio / kind != block all dispatch to
    the jnp path even with use_pallas=True — identical results, no crash."""
    key = jax.random.PRNGKey(0)
    # d % block_size != 0
    cfg = C.SparsifierConfig(kind="block", ratio=0.25, block_size=128,
                             use_pallas=True)
    g = jax.random.normal(key, (4, 200))
    np.testing.assert_array_equal(
        np.asarray(C.compressed_estimate(g, key, cfg)),
        np.asarray(C.compressed_estimate(g, key, dataclasses.replace(
            cfg, use_pallas=False))))
    # ratio=1.0 (no compression) stays on the mask path
    cfg2 = C.SparsifierConfig(kind="block", ratio=1.0, block_size=64,
                              use_pallas=True)
    g2 = jax.random.normal(key, (4, 256))
    np.testing.assert_array_equal(
        np.asarray(C.compressed_estimate(g2, key, cfg2)),
        np.asarray(C.compressed_estimate(g2, key, dataclasses.replace(
            cfg2, use_pallas=False))))
    # non-block kinds never hit the kernel
    cfg3 = C.SparsifierConfig(kind="randk", ratio=0.25, use_pallas=True)
    np.testing.assert_array_equal(
        np.asarray(C.compressed_estimate(g2, key, cfg3)),
        np.asarray(C.compressed_estimate(g2, key, dataclasses.replace(
            cfg3, use_pallas=False))))


def test_kernel_backend_label_resolution():
    d = 4 * 512
    assert C.kernel_backend_label(C.SparsifierConfig(
        kind="block", ratio=0.25, use_pallas=False), d) == "jnp"
    lbl = C.kernel_backend_label(C.SparsifierConfig(
        kind="block", ratio=0.25, use_pallas=True), d)
    assert lbl in ("pallas", "pallas-interpret")
    auto = C.kernel_backend_label(C.SparsifierConfig(kind="block",
                                                     ratio=0.25), d)
    assert auto in ("jnp", "pallas")  # None -> TPU auto-detect
    # configs compressed_estimate runs on the jnp sparsifier even with the
    # kernels forced on report "jnp": a ragged d, a non-block kind, no
    # compression, and a traced keep-ratio
    forced = C.SparsifierConfig(kind="block", ratio=0.25, use_pallas=True)
    assert C.kernel_backend_label(forced, d + 8) == "jnp"
    assert C.kernel_backend_label(
        dataclasses.replace(forced, kind="randk"), d) == "jnp"
    assert C.kernel_backend_label(
        dataclasses.replace(forced, ratio=1.0), d) == "jnp"
    assert C.kernel_backend_label(forced, d, ratio=jnp.float32(0.1)) == "jnp"
