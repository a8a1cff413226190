"""Seeds, weights and token batches made by the benchmark from ``--seed``.

The program under test and the plain reference both take these, so the
reference never reads anything the program made. Every key is drawn from
``numpy.random.SeedSequence`` so that any whole seed works, also one that
needs more than 32 bits.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

#: Streams drawn from one seed.
WEIGHTS, ROUND_KEY, BATCHES, SAMPLE = 0, 1, 2, 3


def key_words(seed: int, stream: int) -> np.ndarray:
    """A raw threefry key (uint32[2]) for ``stream`` of ``seed``."""
    return np.random.SeedSequence([seed, stream]).generate_state(2, np.uint32)


def _leaf_init(path, leaf, key):
    """By the leaf's name: norm scales 1, biases 0, embedding and LM head
    N(0, 0.02^2), other matrices N(0, 1/fan_in)."""
    name = jax.tree_util.keystr(path)
    if name.endswith("['scale']"):
        return jnp.ones(leaf.shape, leaf.dtype)
    if name.endswith("['bias']"):
        return jnp.zeros(leaf.shape, leaf.dtype)
    std = 0.02 if ("embed" in name or "lm_head" in name) else \
        1.0 / np.sqrt(leaf.shape[-2])
    return (jax.random.normal(key, leaf.shape, jnp.float32) * std
            ).astype(leaf.dtype)


def make_params(abstract: Any, seed: int, sharding=None) -> Any:
    """Weights for the tree of ShapeDtypeStructs ``abstract``, made on the
    device in one jitted call from ``seed``."""
    paths, treedef = jax.tree_util.tree_flatten_with_path(abstract)

    def make(words):
        key = jax.random.wrap_key_data(words)
        keys = jax.random.split(key, len(paths))
        return jax.tree_util.tree_unflatten(
            treedef, [_leaf_init(p, l, k)
                      for (p, l), k in zip(paths, keys)])

    fn = jax.jit(make, out_shardings=sharding)
    return fn(jnp.asarray(key_words(seed, WEIGHTS)))


def token_batch(seed: int, step: int, n_workers: int, local_batch: int,
                seq_len: int, vocab: int) -> Dict[str, np.ndarray]:
    """Round ``step``'s ``[n_workers, local_batch, seq_len]`` tokens: uniform
    ids with a learnable successor rule at odd positions (as the trainer's
    ``synthetic_batch``), from its own stream so that every round's rows
    differ and any round can be made again."""
    gen = np.random.default_rng([seed, BATCHES, step])
    toks = gen.integers(0, vocab, (n_workers, local_batch, seq_len))
    toks[..., 1::2] = (toks[..., 0::2] + 1) % vocab
    return {"tokens": toks.astype(np.int32)}
