"""Plain reference of the ``stablelm_3b`` training cell: the model, its loss
and gradients, and the RoSDHB round (coordinated Block-RandK, ALIE, CWTM,
per-worker momentum, SGD step), in straightforward ``jax.numpy``.

It imports nothing of the system under test. It takes the weights, the
batches and the round key that the benchmark made from the seed, and the
layout of the flat gradient vector (leaves in ``jax.tree_util`` order, then
zeros to a whole number of blocks), which is part of what the cell states.

``rnd`` is where the computation rounds. The reference passes the identity
and computes in float32 with ``Precision.HIGHEST``; the lower-precision
control passes :func:`fp8_round` at the points where a bfloat16 program
rounds: every parameter at use, every matmul, norm, rope and residual
output, the attention probabilities, the logits, the gradients, the wire,
the momentum bank and the aggregate.
"""

from __future__ import annotations

import functools
import math
import statistics
from typing import Any, Callable, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
Round = Callable[[jnp.ndarray], jnp.ndarray]


def identity(x):
    return x


def _fp8(x):
    """Per-tensor scaled float8_e4m3fn rounding: the largest magnitude maps
    to the format's largest finite value (448)."""
    amax = jnp.max(jnp.abs(x)).astype(jnp.float32)
    scale = jnp.where(amax > 0, 448.0 / amax, 1.0)
    # clipped: a value the product rounds past 448 would convert to NaN
    y = jnp.clip(x.astype(jnp.float32) * scale, -448.0, 448.0)
    q = y.astype(jnp.float8_e4m3fn)
    return (q.astype(jnp.float32) / scale).astype(x.dtype)


@jax.custom_vjp
def fp8_round(x):
    """Scaled fp8 rounding of a value, and of its cotangent on the way
    back: what computing each tensor in fp8 does to a forward and a
    backward pass."""
    return _fp8(x)


fp8_round.defvjp(lambda x: (_fp8(x), None), lambda _, g: (_fp8(g),))


# --------------------------------------------------------------------------
# the model
# --------------------------------------------------------------------------


def _mm(a, b, rnd: Round):
    return rnd(jnp.matmul(a, b, precision=HIGHEST))


def _layer_norm(x, p, eps: float, rnd: Round):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return rnd(y * rnd(p["scale"]) + rnd(p["bias"]))


def _rope(x, theta: float, rotary_dim: int, rnd: Round):
    """Rotary embedding on the first ``rotary_dim`` of each head, pairs
    interleaved (x[2i], x[2i+1]). x: [B, S, H, Dh]."""
    s = x.shape[1]
    freqs = 1.0 / (theta ** (np.arange(0, rotary_dim, 2, dtype=np.float64)
                             / rotary_dim))
    ang = np.arange(s, dtype=np.float64)[:, None] * freqs[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    rot, rest = x[..., :rotary_dim], x[..., rotary_dim:]
    x1, x2 = rot[..., 0::2], rot[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return rnd(jnp.concatenate([out.reshape(rot.shape), rest], axis=-1))


def _attention(q, k, v, rnd: Round):
    """Causal softmax attention. q, k, v: [B, S, H, Dh]."""
    dh = q.shape[-1]
    s = q.shape[1]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        precision=HIGHEST) / math.sqrt(dh)
    causal = np.tril(np.ones((s, s), bool))
    logits = jnp.where(causal, logits, -jnp.inf)
    probs = rnd(jax.nn.softmax(logits, axis=-1))
    return rnd(jnp.einsum("bhqk,bkhd->bqhd", probs, v, precision=HIGHEST))


def _block(p, x, cfg: Dict, rnd: Round):
    b, s, d = x.shape
    h = cfg["num_attention_heads"]
    dh = d // h
    w = lambda name: rnd(p["attn"][name]["w"])  # noqa: E731
    a = _layer_norm(x, p["norm1"], cfg["norm_eps"], rnd)
    q = _mm(a, w("wq"), rnd).reshape(b, s, h, dh)
    k = _mm(a, w("wk"), rnd).reshape(b, s, h, dh)
    v = _mm(a, w("wv"), rnd).reshape(b, s, h, dh)
    rot = int(round(cfg["rope_pct"] * dh))
    q = _rope(q, cfg["rope_theta"], rot, rnd)
    k = _rope(k, cfg["rope_theta"], rot, rnd)
    o = _attention(q, k, v, rnd).reshape(b, s, d)
    x = rnd(x + _mm(o, w("wo"), rnd))
    m = _layer_norm(x, p["norm2"], cfg["norm_eps"], rnd)
    mw = lambda name: rnd(p["mlp"][name]["w"])  # noqa: E731
    gate = rnd(jax.nn.silu(_mm(m, mw("wg"), rnd)))
    up = _mm(m, mw("wi"), rnd)
    return rnd(x + _mm(rnd(gate * up), mw("wo"), rnd))


def loss(params, tokens, cfg: Dict, rnd: Round = identity):
    """Mean next-token cross entropy of one worker's ``[B, S]`` tokens."""
    x = rnd(params["embed"])[tokens]
    n_layers = params["blocks"]["norm1"]["scale"].shape[0]
    for i in range(n_layers):
        p = jax.tree_util.tree_map(lambda a, i=i: a[i], params["blocks"])
        x = _block(p, x, cfg, rnd)
    x = _layer_norm(x, params["final_norm"], cfg["norm_eps"], rnd)
    logits = _mm(x[:, :-1], rnd(params["lm_head"]), rnd)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(nll)


# --------------------------------------------------------------------------
# the round
# --------------------------------------------------------------------------


def alie_z(n: int, f: int) -> float:
    """ALIE's z (Baruch et al. 2019): Phi^-1((h - s) / h), h = n - f honest
    workers, s = floor(n/2 + 1) - f supporters."""
    h = n - f
    s = math.floor(n / 2 + 1) - f
    return statistics.NormalDist().inv_cdf((h - s) / h)


def trimmed_mean(x, f: int):
    """Coordinate-wise mean of the middle n - 2f values. x: [n, C]."""
    n = x.shape[0]
    return jnp.mean(jnp.sort(x, axis=0)[f:n - f], axis=0)


class Layout:
    """The flat layout of a parameter tree: leaves in ``jax.tree_util``
    order, concatenated, then zeros up to a whole number of blocks."""

    def __init__(self, params, block: int):
        leaves = jax.tree_util.tree_leaves(params)
        self.sizes = [int(np.prod(l.shape)) for l in leaves]
        self.offsets = [int(o) for o in np.cumsum([0] + self.sizes)[:-1]]
        self.size = int(sum(self.sizes))
        self.block = block
        self.padded = -(-self.size // block) * block
        self.n_blocks = self.padded // block

    def ravel(self, tree):
        parts = [jnp.ravel(l).astype(jnp.float32)
                 for l in jax.tree_util.tree_leaves(tree)]
        parts.append(jnp.zeros((self.padded - self.size,), jnp.float32))
        return jnp.concatenate(parts)

    def leaf_of(self, coords):
        """Leaf index of each flat coordinate (-1 for the padding)."""
        idx = jnp.searchsorted(jnp.asarray(self.offsets[1:] + [self.size]),
                               coords, side="right")
        return jnp.where(coords < self.size, idx, -1)


def leaf_norms_of_blocks(layout: Layout, rows, ids):
    """Per-leaf l2 norm of ``rows`` [n, kb, block] placed at block ``ids``
    (zeros elsewhere): what the dense rows' norms per leaf would be."""
    coords = ids[:, None] * layout.block + jnp.arange(layout.block)[None, :]
    leaf = layout.leaf_of(coords.reshape(-1))
    sq = jnp.sum(jnp.square(rows.astype(jnp.float32)), axis=0).reshape(-1)
    n = len(layout.sizes)
    sums = jax.ops.segment_sum(jnp.where(leaf >= 0, sq, 0.0),
                               jnp.maximum(leaf, 0), num_segments=n)
    return jnp.sqrt(sums)


def n_selected(ratio: float, n_blocks: int) -> int:
    """Blocks that Block-RandK keeps in a round."""
    return max(1, int(round(ratio * n_blocks)))


@functools.partial(jax.jit, static_argnums=(1, 2))
def _next_round(key, n_blocks: int, kb: int):
    key, round_key = jax.random.split(key)
    mask_key, _ = jax.random.split(round_key)
    ids = jax.random.permutation(mask_key, n_blocks)[:kb].astype(jnp.int32)
    return key, ids


def round_blocks(key, n_blocks: int, kb: int, rounds: int
                 ) -> List[np.ndarray]:
    """The block ids that each of the first ``rounds`` rounds keeps, drawn
    from the round key (uint32[2]) as the round draws them: the key splits
    into the next key and the round's key, whose first half is the mask
    key; the round keeps the first ``kb`` of a permutation of the blocks."""
    key = jnp.asarray(key, jnp.uint32)
    out = []
    for _ in range(rounds):
        key, ids = _next_round(key, n_blocks, kb)
        out.append(np.asarray(ids))
    return out


@functools.lru_cache(maxsize=4)
def _round_fns(shapes: tuple, treedef, cfg_items: tuple, algo_items: tuple,
               rnd: Round, n_steps: int):
    """The jitted pieces of one round, built once per model, algorithm and
    rounding (a process that reads many seeds compiles them once).

    The momentum bank is held only on the blocks that some checked round
    selects (``n_steps * kb`` slots, unused ones pointing past the last
    block): elsewhere every row stays zero, and so does the aggregate."""
    cfg, algo = dict(cfg_items), dict(algo_items)
    n, f = algo["n_workers"], algo["f"]
    ratio, block = algo["ratio"], algo["block_size"]
    beta, gamma = algo["beta"], algo["gamma"]
    alpha = 1.0 / ratio
    z = alie_z(n, f)
    layout = Layout(jax.tree_util.tree_unflatten(
        treedef, [jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes]),
        block)
    nb = layout.n_blocks
    slots = n_steps * n_selected(ratio, nb)
    value_and_grad = jax.value_and_grad(lambda p, t: loss(p, t, cfg, rnd))

    @jax.jit
    def gather(p, toks, ids):
        lv, g = value_and_grad(p, toks)
        rows = layout.ravel(g).reshape(nb, block)[ids]
        return lv, rnd(alpha * rows)

    @jax.jit
    def attack(honest):
        mu = jnp.mean(honest, axis=0)
        sd = jnp.std(honest, axis=0)
        byz = rnd(mu - z * sd)
        return jnp.concatenate(
            [jnp.broadcast_to(byz, (f,) + byz.shape), honest], axis=0)

    @jax.jit
    def momentum(bank, wire, pos):
        return rnd((beta * bank).at[:, pos].add((1.0 - beta) * wire))

    @jax.jit
    def step(p, bank, blocks):
        r = rnd(trimmed_mean(bank.reshape(n, slots * block), f))
        flat = jnp.zeros((nb + 1, block), jnp.float32).at[blocks].set(
            r.reshape(slots, block))[:nb].reshape(-1)
        leaves, tdef = jax.tree_util.tree_flatten(p)
        new = [l - gamma * flat[o:o + s].reshape(l.shape)
               for l, o, s in zip(leaves, layout.offsets, layout.sizes)]
        return jax.tree_util.tree_unflatten(tdef, new)

    @jax.jit
    def norms(wire, ids):
        return leaf_norms_of_blocks(layout, wire, ids)

    @jax.jit
    def change_norms(p, p0):
        # in float32, as the program's master weights move: an update under
        # a weight's rounding step moves it by that step or not at all
        return jnp.stack([jnp.linalg.norm((a - b).ravel())
                          for a, b in zip(jax.tree_util.tree_leaves(p),
                                          jax.tree_util.tree_leaves(p0))])

    return layout, slots, gather, attack, momentum, step, norms, \
        change_norms


def train_readings(params, batches: Sequence[np.ndarray], key, cfg: Dict,
                   algo: Dict, rnd: Round = identity,
                   sample: Sequence[int] = ()) -> Dict[str, Any]:
    """Run ``len(batches)`` RoSDHB rounds from ``params`` and the round key
    ``key`` (uint32[2]); ``batches[t]`` is ``[n_workers, B, S]`` tokens.

    Returns, per step, the mean loss over the honest workers; per leaf, the
    norm of the round-1 gradient bank as the server receives it (Byzantine
    row included) and the norm of the parameters' change after the last
    step; per leaf the norm of the received gradients summed over the
    steps (for the rule that drops leaves the reference never moves); and
    ``first_rows``, the round-1 bank as received on the ``sample``-th of
    the round's kept blocks, ``[n_workers, len(sample), block]``.

    Worker rows [0, f) are Byzantine: their gradients are never computed,
    since the attack replaces them. Gradients are kept only on the round's
    selected blocks, so a step holds one dense gradient at a time.
    """
    n, f = algo["n_workers"], algo["f"]
    params = jax.tree_util.tree_map(lambda p: jnp.asarray(p, jnp.float32),
                                    params)
    leaves, treedef = jax.tree_util.tree_flatten(params)
    (layout, slots, gather, attack, momentum, step, norms,
     change_norms) = _round_fns(
        tuple(tuple(l.shape) for l in leaves), treedef,
        tuple(sorted((k, v) for k, v in cfg.items()
                     if isinstance(v, (bool, int, float, str)))),
        tuple(sorted(algo.items())), rnd, len(batches))
    ids = [jnp.asarray(i) for i in round_blocks(
        key, layout.n_blocks, n_selected(algo["ratio"], layout.n_blocks),
        len(batches))]
    used = np.unique(np.concatenate([np.asarray(i) for i in ids]))
    blocks = np.full((slots,), layout.n_blocks, np.int32)
    blocks[:used.size] = used
    params0 = params
    bank = jnp.zeros((n, slots, layout.block), jnp.float32)
    losses, first, received = [], None, 0.0
    for t, toks in enumerate(batches):
        honest, lsum = [], 0.0
        for i in range(f, n):
            lv, rows = gather(params, jnp.asarray(toks[i]), ids[t])
            lsum += float(lv)
            honest.append(rows)
        losses.append(lsum / (n - f))
        wire = attack(jnp.stack(honest))
        del honest
        leaf = norms(wire, ids[t])
        if first is None:
            first = leaf
            first_rows = np.asarray(wire[:, np.asarray(sample, np.int32)])
        received = received + leaf
        pos = jnp.asarray(np.searchsorted(used, np.asarray(ids[t])),
                          jnp.int32)
        bank = momentum(bank, wire, pos)
        params = step(params, bank, jnp.asarray(blocks))
    return {"loss": losses,
            "first_grad": [float(x) for x in first],
            "change": [float(x) for x in change_norms(params, params0)],
            "received": [float(x) for x in received],
            "first_rows": first_rows}
