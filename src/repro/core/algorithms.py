"""Server-side distributed learning algorithms on flat gradient banks.

Everything here operates on flat stacked vectors ``[n_workers, D]`` — the
launcher (``repro/launch``) is responsible for producing per-worker gradients
from the sharded model and for resharding; these functions are pure math and
are shared between the paper-scale simulator and the LLM-scale pjit path.

Algorithms:
  * ``rosdhb``       — the paper's Algorithm 1 (global or local sparsification
                       chosen by the sparsifier config).
  * ``dasha``        — Byz-DASHA-PAGE [29] with p=1 (full-gradient PAGE
                       branch): per-worker MVR momentum + compressed-difference
                       server mirrors + robust aggregation.
  * ``robust_dgd``   — robust DGD, no compression (SOTA-without-compression
                       corner, [3]).
  * ``dgd``          — plain compressed DGD, non-robust (SOTA-without-
                       robustness corner, [1]).
  * ``bank``         — the switch-based **algorithm bank**
                       (:func:`make_algorithm_bank`): a ``lax.switch`` over
                       the four update rules above, selected per grid cell by
                       the traced ``ScenarioParams.algo_idx`` — the paper's
                       whole Table-1 cross-algorithm comparison as ONE
                       compiled XLA program (see ``repro.core.sweep``).

The Byzantine adversary is simulated *on the wire quantity* each algorithm
actually transmits: compressed gradients for rosdhb/dgd, raw gradients for
robust_dgd, compressed differences (applied at the mirror level) for dasha.
:func:`algo_payload_bytes` accounts for those wire formats individually.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core import attacks as A
from repro.core import aggregators as G
from repro.core import compression as C
from repro.core import stages
from repro.core import wire as W


# --------------------------------------------------------------------------
# Config
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class StateLayout:
    """Which optional ``ServerState`` slots a program's carry materialises.

    The ``mirror``/``prev_grad`` banks exist only for DASHA's
    variance-reduction state (Byz-DASHA-PAGE carries a server-side gradient
    mirror h_i and the previous-round gradients for its MVR correction);
    RoSDHB and the DGD variants never read them. Carrying them anyway costs
    ``n*D`` momentum-dtype + ``n*D`` f32 floats per trajectory — exactly the
    per-client memory overhead the paper's comparison charges DASHA and NOT
    RoSDHB — so the plan layer prunes the slots whenever a program provably
    contains no dasha cell (:meth:`for_algorithms`), and keeps the full
    width for mixed banks. Pruned slots are ``None`` in the state pytree
    (no leaves), which is bit-for-bit neutral: the non-dasha update rules
    pass the slots through untouched either way (property-tested in
    tests/test_state_layout.py).
    """

    mirror: bool = True
    prev_grad: bool = True

    @classmethod
    def full(cls) -> "StateLayout":
        """Every slot materialised (the pre-specialisation padded layout)."""
        return cls(mirror=True, prev_grad=True)

    @classmethod
    def pruned(cls) -> "StateLayout":
        """The dasha-free layout: mirror/prev_grad dropped from the carry."""
        return cls(mirror=False, prev_grad=False)

    @classmethod
    def for_algorithms(cls, names: Sequence[str]) -> "StateLayout":
        """The minimal layout for a program running exactly ``names``:
        full width iff any branch is dasha."""
        needs = "dasha" in tuple(names)
        return cls(mirror=needs, prev_grad=needs)

    @property
    def is_full(self) -> bool:
        return self.mirror and self.prev_grad


@dataclasses.dataclass(frozen=True)
class AlgorithmConfig:
    """Full specification of a Byzantine-robust compressed training run.

    Attributes:
      name: ``rosdhb`` | ``dasha`` | ``robust_dgd`` | ``dgd`` | ``bank``
        (the switch-based algorithm bank; branch selected per grid cell by a
        traced ``ScenarioParams.algo_idx``, see :func:`make_algorithm_bank`).
      n_workers: total workers n.
      f: number of Byzantine workers (the first ``f`` indices).
      gamma: learning rate.
      beta: momentum coefficient; ``None`` -> Theorem 1 schedule
        ``sqrt(1 - 24 gamma L)`` using ``smoothness_L``.
      smoothness_L: Lipschitz constant estimate used by the beta schedule.
      mvr_a: DASHA's MVR coefficient ``a`` (only for ``dasha``).
      sparsifier: compression config.
      aggregator: robust-aggregation config.
      attack: Byzantine strategy.
      momentum_dtype: dtype of the server momentum bank (f32 default;
        bf16/fp8 are beyond-paper memory optimizations, see DESIGN §3).
      server_compute_dtype: dtype the server does its momentum/aggregation
        math in (f32 default; bf16 halves the per-round transient at LLM
        scale — a beyond-paper optimization ablated in EXPERIMENTS §Perf).
      bank: algorithm-branch tuple when ``name='bank'`` (``None`` means the
        full :data:`ALGO_BANK`). Per-cell hyperparameters (momentum beta,
        DASHA's ``a``, the step size) then arrive as traced
        ``ScenarioParams`` data, not from this config.
      state_layout: explicit :class:`StateLayout` override, or ``None``
        (default) for the plan-time automatic layout — pruned
        mirror/prev_grad slots whenever this config provably runs no dasha
        branch (:meth:`resolved_state_layout`). Forcing
        ``StateLayout.full()`` reproduces the legacy padded carry exactly
        (the parity baseline for the specialisation property tests).
    """

    name: str = "rosdhb"
    n_workers: int = 10
    f: int = 0
    gamma: float = 0.05
    beta: Optional[float] = 0.9
    smoothness_L: float = 1.0
    mvr_a: Optional[float] = None
    sparsifier: C.SparsifierConfig = dataclasses.field(
        default_factory=C.SparsifierConfig)
    aggregator: G.AggregatorConfig = dataclasses.field(
        default_factory=G.AggregatorConfig)
    attack: A.AttackConfig = dataclasses.field(
        default_factory=lambda: A.AttackConfig(name="none"))
    momentum_dtype: str = "float32"
    server_compute_dtype: str = "float32"
    clip_norm: Optional[float] = None  # per-worker L2 clip before compression
    bank: Optional[Tuple[str, ...]] = None
    state_layout: Optional[StateLayout] = None

    @property
    def honest(self) -> int:
        return self.n_workers - self.f

    def algorithms(self) -> Tuple[str, ...]:
        """The algorithm branches this config can execute: the bank's entry
        set for ``name='bank'``, else the single static algorithm."""
        if self.name == "bank":
            return tuple(self.bank) if self.bank else ALGO_BANK
        return (self.name,)

    def resolved_state_layout(self) -> StateLayout:
        """The carry layout this config runs under: the explicit
        ``state_layout`` if set, else the minimal layout for its algorithm
        branches (mirror/prev_grad pruned when no branch is dasha)."""
        if self.state_layout is not None:
            return self.state_layout
        return StateLayout.for_algorithms(self.algorithms())

    def resolved_beta(self) -> float:
        if self.beta is not None:
            return self.beta
        # Theorem 1: beta = sqrt(1 - 24 gamma L), requires gamma <= 1/(24 L).
        val = 1.0 - 24.0 * self.gamma * self.smoothness_L
        if val <= 0.0:
            raise ValueError(
                f"gamma={self.gamma} too large for Theorem-1 beta schedule "
                f"(needs gamma <= 1/(24 L) = {1.0 / (24 * self.smoothness_L)})")
        return math.sqrt(val)

    def resolved_mvr_a(self) -> float:
        """DASHA's MVR coefficient ``a`` (defaults to ``1 - beta``)."""
        if self.mvr_a is not None:
            return self.mvr_a
        return 1.0 - (self.beta if self.beta is not None else 0.9)


def theorem1_hparams(L: float, ratio: float,
                     c: float = 23200.0) -> Tuple[float, float]:
    """Theorem 1's (gamma, beta): gamma = (k/d)/(cL), beta = sqrt(1-24 gamma L).

    The constant c = 23200 is the paper's (very conservative) analysis
    constant; practical runs (the paper's own Section 4 included) use far
    larger gamma with beta = 0.9.
    """
    gamma = ratio / (c * L)
    beta = math.sqrt(1.0 - 24.0 * gamma * L)
    return gamma, beta


# --------------------------------------------------------------------------
# State
# --------------------------------------------------------------------------


class ScenarioParams(NamedTuple):
    """Traced per-cell scenario vector for the fused grid axis.

    Every component is optional (``None`` components contribute no pytree
    leaves, so a ``ScenarioParams`` batch vmaps cleanly whichever subset is
    fused); a present component overrides the corresponding static config:

    ``attack_coeffs``: ``[2]`` attack parameter vector — the linear-family
      ``(a, b)`` coefficients for ``cfg.attack.name == 'linear'`` (see
      ``attacks.linear_attack``), or the per-branch parameter vector of the
      attack bank for ``cfg.attack.name == 'bank'``.
    ``attack_idx``: scalar int32 branch index into the attack bank
      (``repro.adversary.make_attack_bank``; requires
      ``cfg.attack.name == 'bank'``).
    ``agg_idx``: scalar int32 branch index into the aggregator bank
      (``aggregators.make_aggregator_bank``) replacing the static rule.
    ``ratio``: scalar keep-ratio replacing ``cfg.sparsifier.ratio``
      (only for ``compression.TRACED_RATIO_KINDS``).
    ``algo_idx``: scalar int32 branch index into the **algorithm bank**
      (:func:`make_algorithm_bank`; requires ``cfg.name == 'bank'``) — the
      cross-algorithm fusion axis.
    ``hparams``: ``[4]`` per-cell algorithm hyperparameters
      ``(beta, mvr_a, 1-beta, 1-mvr_a)`` — the RoSDHB momentum coefficient
      and DASHA's MVR coefficient as traced data (branches read the slots
      they use and ignore the rest). The complements are carried
      *precomputed* (double-precision at plan time) so the traced branches
      consume exactly the constants the static path folds in — that is what
      keeps bank and standalone trajectories bit-for-bit equal.
    ``gamma``: scalar step size, consumed by the *simulator*'s parameter
      update (``apply_direction``), so cells with different learning rates
      share one compiled program too.
    """

    attack_coeffs: Optional[jnp.ndarray] = None
    attack_idx: Optional[jnp.ndarray] = None
    agg_idx: Optional[jnp.ndarray] = None
    ratio: Optional[jnp.ndarray] = None
    algo_idx: Optional[jnp.ndarray] = None
    hparams: Optional[jnp.ndarray] = None
    gamma: Optional[jnp.ndarray] = None


class ServerState(NamedTuple):
    """Server-side algorithm state — ONE shape per *program* (carry layout
    chosen at plan time, uniform across every cell the program runs).

    ``momentum``: RoSDHB per-worker momentum bank ``[n, D]`` (Algorithm 1,
      step 5) — also reused as DASHA's MVR momentum.
    ``mirror``: DASHA's server-side gradient mirrors ``h_i`` ``[n, D]``;
      ``None`` (no pytree leaves) under a pruned :class:`StateLayout`.
    ``prev_grad``: previous-round per-worker gradients ``[n, D]`` for
      DASHA's MVR correction; ``None`` under a pruned layout.
    ``step``: iteration counter t.
    ``attack``: the adversary's carried memory
      (``repro.adversary.AttackState``) for stateful attacks and attack
      banks; ``None`` (no pytree leaves) for stateless attacks, so legacy
      configs keep their exact state structure.

    When a program DOES carry ``mirror``/``prev_grad`` (any dasha branch
    present, or ``StateLayout.full()`` forced), the slots are *padded but
    inert* for rosdhb/dgd/robust_dgd: their update rules pass both through
    bit-for-bit untouched (property-tested in tests/test_algo_bank.py),
    exactly like the unused slots of the ``AttackState`` slab — which is
    also why pruning them for dasha-free programs cannot change a
    trajectory (tests/test_state_layout.py pins that bit-for-bit). The
    full-width cost, charged only where DASHA actually needs it, is
    ``n*D`` momentum-dtype + ``n*D`` f32 floats per trajectory
    (:func:`server_state_bytes`).
    """

    momentum: jnp.ndarray
    mirror: Optional[jnp.ndarray]
    prev_grad: Optional[jnp.ndarray]
    step: jnp.ndarray
    attack: Optional[Any] = None


def _adversary():
    # local import: repro.adversary.core imports repro.core.attacks, so a
    # module-level import here would be circular
    from repro.adversary import core as adv
    return adv


def _init_attack_state(cfg: AlgorithmConfig, d: int) -> Optional[Any]:
    """Adversary memory slab for stateful attacks / attack banks; ``None``
    (structure-preserving) for the stateless legacy attacks."""
    adv = _adversary()
    if adv.needs_attack_state(cfg.attack.name, cfg.f):
        return adv.init_attack_state(d)
    return None


def init_state(cfg: AlgorithmConfig, d: int) -> ServerState:
    """Initial server state under ``cfg``'s resolved :class:`StateLayout`:
    dasha-free configs (standalone or bank) get the specialised carry with
    ``mirror``/``prev_grad`` pruned to ``None``; any config that can run a
    dasha branch materialises the full width. A pruned layout forced onto a
    dasha-capable config raises loudly (the branch cannot run without its
    variance-reduction state)."""
    n = cfg.n_workers
    if cfg.name != "bank" and cfg.name not in ALGO_STEPS:
        raise ValueError(
            f"unknown algorithm: {cfg.name!r} (expected one of "
            f"{'|'.join(ALGO_BANK)} or 'bank')")
    layout = cfg.resolved_state_layout()
    if "dasha" in cfg.algorithms() and not layout.is_full:
        raise ValueError(
            "state layout prunes mirror/prev_grad but the config can run a "
            f"dasha branch (algorithms={cfg.algorithms()}): dasha's MVR "
            "mirror state cannot be pruned — use StateLayout.full() or drop "
            "dasha from the bank")
    mdt = jnp.dtype(cfg.momentum_dtype)
    zeros = jnp.zeros((n, d), mdt)
    atk = _init_attack_state(cfg, d)
    return ServerState(
        momentum=zeros,
        mirror=zeros if layout.mirror else None,
        prev_grad=jnp.zeros((n, d), jnp.float32) if layout.prev_grad
        else None,
        step=jnp.zeros((), jnp.int32), attack=atk)


# --------------------------------------------------------------------------
# One server round
# --------------------------------------------------------------------------


@stages.scope("attack")
def _byzantine_overwrite(cfg: AlgorithmConfig, atk_state: Optional[Any],
                         wire: jnp.ndarray, key: jax.Array,
                         attack_params: Optional[jnp.ndarray] = None,
                         attack_idx: Optional[jnp.ndarray] = None
                         ) -> Tuple[jnp.ndarray, Optional[Any]]:
    """Replace rows [0, f) of the wire payload with the attack vectors
    computed from the honest rows [f, n).

    Returns ``(wire, new_attack_state)``.  Dispatch: ``name='bank'`` runs
    the switch-based attack bank (``repro.adversary.make_attack_bank``)
    selected by the traced ``attack_idx``; statically configured *stateful*
    adversaries (mimic/spectral/ipm_greedy) run their registry step with the
    carried ``atk_state``; everything else stays on the stateless legacy
    ``attacks.apply_attack`` path.
    """
    name = cfg.attack.name
    if cfg.f == 0 or name == "none":
        return wire, atk_state
    honest = wire[cfg.f:]
    if name == "bank":
        adv = _adversary()
        if atk_state is None:
            raise ValueError(
                "attack bank needs the adversary memory slab: build the "
                "server state with init_state(cfg, d) (ServerState.attack)")
        entries = cfg.attack.bank or adv.DEFAULT_ATTACK_BANK
        if attack_idx is None or attack_params is None:
            raise ValueError(
                "bank attack needs traced branch selectors: pass a "
                "ScenarioParams with attack_idx and attack_coeffs "
                "(see sweep.FusedBank.scenario_params)")
        atk_state, byz = adv.make_attack_bank(entries, cfg.f)(
            atk_state, honest, key, attack_idx, attack_params)
    else:
        adv = _adversary()
        if adv.is_stateful(name):
            if atk_state is None:
                raise ValueError(
                    f"stateful attack {name!r} needs the adversary memory "
                    "slab: build the server state with init_state(cfg, d) "
                    "(ServerState.attack)")
            coeffs = (attack_params if attack_params is not None
                      else adv.static_coeffs(cfg.attack, cfg.n_workers,
                                             cfg.f))
            atk_state, byz = adv.ADVERSARIES[name].step(
                atk_state, honest, cfg.f, key, coeffs)
        else:
            byz = A.apply_attack(cfg.attack, honest, cfg.f, key=key,
                                 params=attack_params)
    return jnp.concatenate([byz.astype(wire.dtype), honest], axis=0), atk_state


# --------------------------------------------------------------------------
# Per-algorithm update branches (uniform signature — the algorithm bank
# switches between these on a traced index; the static path calls the same
# functions directly, so bank and standalone rounds share ONE code path)
# --------------------------------------------------------------------------

# step(cfg, agg, state, grads, mask_key, atk_key, hparams, attack_params,
#      attack_idx, ratio) -> (direction [D], new ServerState).
# ``hparams`` is indexable as (beta, mvr_a, 1-beta, 1-mvr_a) — a tuple of
# python floats on the static path, a traced [4] vector inside a bank; each
# branch reads the slots it uses. Every branch preserves the uniform
# ServerState structure and leaves the slots it does not own bit-for-bit
# untouched.
#
# Each memoryless branch (rosdhb / dgd / robust_dgd) is split into a WIRE
# half (what the clients jointly put on the uplink: sparsified unbiased
# reconstructions, with Byzantine rows overwritten) and an APPLY half (what
# the server does with a received wire bank: momentum, aggregation, state
# update). The step functions compose the two halves in the original op
# order, so the fused simulator graph is unchanged; the streaming parameter
# server (repro.serve) runs the same halves in separate programs — the
# clients the wire half, the server the apply half — which is what makes
# server <-> simulator trajectories bit-for-bit comparable. The apply
# halves additionally accept a ``present``/``discount`` row masking for
# partial participation + staleness discounting; ``None`` (the simulator
# path) compiles to exactly the legacy graph.
AlgoStepFn = Callable[..., Tuple[jnp.ndarray, ServerState]]


def _compressed_wire(cfg, atk_state, grads, mask_key, atk_key,
                     attack_params=None, attack_idx=None, ratio=None):
    # Steps 1-4: masks (global or local) + unbiased reconstruction, then the
    # Byzantine overwrite on the wire quantity.
    # compressed_estimate dispatches between the jnp sparsifier (identical
    # make_masks + compress graph) and the repro.kernels.randk Block-RandK
    # round trip per SparsifierConfig.use_pallas
    with stages.scope("sparsify"):
        g_tilde = C.compressed_estimate(grads, mask_key, cfg.sparsifier,
                                        ratio=ratio)
    return _byzantine_overwrite(cfg, atk_state, g_tilde, atk_key,
                                attack_params, attack_idx)


def _row_mask(wire, prev, present, discount):
    """Stale-discounted participation masking: rows with ``present`` False
    keep ``prev``; present rows contribute ``discount * wire`` (discount is
    1.0 for fresh updates — an exact multiply, so full participation is
    bit-for-bit the unmasked path)."""
    eff = wire * discount[:, None].astype(wire.dtype)
    return jnp.where(present[:, None], eff, prev)


@stages.scope("aggregate")
def _rosdhb_apply(cfg, agg, state, wire, hparams,
                  present=None, discount=None):
    # Step 5: per-worker server momentum (math dtype configurable — bf16
    # halves the per-round transient at LLM scale, EXPERIMENTS §Perf).
    beta, one_m_beta = hparams[0], hparams[2]
    cdt = jnp.dtype(cfg.server_compute_dtype)
    m_prev = state.momentum.astype(cdt)
    w = wire.astype(cdt)
    if discount is not None:
        w = w * discount[:, None].astype(cdt)
    m = beta * m_prev + one_m_beta * w
    if present is not None:
        # absent clients: momentum frozen (neither decayed nor fed) — the
        # streaming server's padding of clients that missed the round
        m = jnp.where(present[:, None], m, m_prev)
    # Step 6: robust aggregation of momenta.
    r = agg(m)
    new = state._replace(momentum=m.astype(jnp.dtype(cfg.momentum_dtype)),
                         step=state.step + 1)
    return r, new


@stages.scope("aggregate")
def _dgd_apply(cfg, agg, state, wire, present=None, discount=None):
    # Compressed DGD, non-robust: plain mean of unbiased estimates (the
    # defining non-robust corner — the aggregator config is ignored).
    del agg
    if present is None:
        return jnp.mean(wire, axis=0), state._replace(step=state.step + 1)
    # Streaming partial participation: the momentum slot doubles as the
    # last-received-wire bank; absent clients keep their frozen row.
    bank = _row_mask(wire, state.momentum.astype(wire.dtype), present,
                     discount)
    r = jnp.mean(bank, axis=0)
    return r, state._replace(
        momentum=bank.astype(jnp.dtype(cfg.momentum_dtype)),
        step=state.step + 1)


@stages.scope("aggregate")
def _robust_dgd_apply(cfg, agg, state, wire, present=None, discount=None):
    # Robust DGD without compression: aggregate raw gradients (the
    # sparsifier config is ignored).
    if present is None:
        return agg(wire), state._replace(step=state.step + 1)
    bank = _row_mask(wire, state.momentum.astype(wire.dtype), present,
                     discount)
    r = agg(bank)
    return r, state._replace(
        momentum=bank.astype(jnp.dtype(cfg.momentum_dtype)),
        step=state.step + 1)


def _rosdhb_step(cfg, agg, state, grads, mask_key, atk_key, hparams,
                 attack_params, attack_idx, ratio):
    g_tilde, atk = _compressed_wire(cfg, state.attack, grads, mask_key,
                                    atk_key, attack_params, attack_idx,
                                    ratio)
    return _rosdhb_apply(cfg, agg, state._replace(attack=atk), g_tilde,
                         hparams)


def _dgd_step(cfg, agg, state, grads, mask_key, atk_key, hparams,
              attack_params, attack_idx, ratio):
    g_tilde, atk = _compressed_wire(cfg, state.attack, grads, mask_key,
                                    atk_key, attack_params, attack_idx,
                                    ratio)
    return _dgd_apply(cfg, agg, state._replace(attack=atk), g_tilde)


def _robust_dgd_step(cfg, agg, state, grads, mask_key, atk_key, hparams,
                     attack_params, attack_idx, ratio):
    g, atk = _byzantine_overwrite(cfg, state.attack, grads, atk_key,
                                  attack_params, attack_idx)
    return _robust_dgd_apply(cfg, agg, state._replace(attack=atk), g)


def _dasha_step(cfg, agg, state, grads, mask_key, atk_key, hparams,
                attack_params, attack_idx, ratio):
    # Byz-DASHA-PAGE, p=1 branch.
    #   MVR momentum: m_i^t = g_i^t + (1-a)(m_i^{t-1} - g_i^{t-1})
    #   wire:         c_i^t = C((m_i^t - m_i^{t-1})
    #                          + b (m_i^{t-1} - h_i^{t-1}))
    #                 — compressed momentum difference plus DASHA's
    #                 mirror-drift correction with b = 1/(2 alpha), which
    #                 contracts E[h - m] at rate b while keeping the
    #                 alpha-scaled compression variance bounded.
    #   mirror:       h_i^t = h_i^{t-1} + c_i^t
    #   direction:    R^t = F(h_1^t ... h_n^t)
    if state.mirror is None or state.prev_grad is None:
        raise ValueError(
            "dasha needs the mirror/prev_grad state slots but the carry was "
            "built with a pruned StateLayout: init the state with a config "
            "whose algorithms() include 'dasha' (or StateLayout.full())")
    n, d = grads.shape
    # Byz-DASHA-PAGE runs an INDEPENDENT unbiased compressor per worker
    # (the analysis of [29] requires independent randomness; there is no
    # coordinated-mask trick — that is RoSDHB's contribution), so each
    # worker draws its own mask regardless of the grid-shared sparsifier's
    # ``local`` flag. algo_payload_bytes prices the matching wire format:
    # k values + coordinate indices.
    sp = dataclasses.replace(cfg.sparsifier, local=True)
    one_m_a = hparams[3]
    # the wire half (MVR momentum, compressed difference, mirror) is the
    # round's sparsify stage; the server's aggregate of the mirrors its
    # aggregate stage
    with stages.scope("sparsify"):
        first = state.step == 0
        m_prev = state.momentum.astype(jnp.float32)
        h_prev = state.mirror.astype(jnp.float32)
        g32 = grads.astype(jnp.float32)
        m = jnp.where(first, g32, g32 + one_m_a * (m_prev - state.prev_grad))
        masks = C.make_masks(mask_key, n, d, sp, dtype=grads.dtype,
                             ratio=ratio)
        alpha = (1.0 / ratio) if ratio is not None else sp.alpha
        b = 1.0 / (2.0 * alpha)
        diff = C.compress((m - m_prev) + b * (m_prev - h_prev), masks, sp,
                          ratio=ratio)
        h = h_prev + diff
    h, atk = _byzantine_overwrite(cfg, state.attack, h, atk_key,
                                  attack_params, attack_idx)
    with stages.scope("aggregate"):
        r = agg(h)
        mdt = jnp.dtype(cfg.momentum_dtype)
        new = ServerState(momentum=m.astype(mdt), mirror=h.astype(mdt),
                          prev_grad=g32, step=state.step + 1, attack=atk)
    return r, new


#: Branch order of the full algorithm bank (and the set of known algorithms).
ALGO_BANK: Tuple[str, ...] = ("rosdhb", "dasha", "robust_dgd", "dgd")

ALGO_STEPS = {
    "rosdhb": _rosdhb_step,
    "dasha": _dasha_step,
    "robust_dgd": _robust_dgd_step,
    "dgd": _dgd_step,
}

#: Algorithms the streaming parameter server (``repro.serve``) can run:
#: the memoryless-wire rules, whose client payload depends only on the
#: current gradient + broadcast round keys. ``dasha`` is excluded by
#: construction — Byz-DASHA-PAGE's wire is a compressed *difference*
#: against server-side mirrors and per-client MVR momentum, so its
#: per-client control variates go stale the moment a client misses a round
#: (the failure mode the paper's momentum-based RoSDHB avoids).
SERVE_ALGORITHMS: Tuple[str, ...] = ("rosdhb", "robust_dgd", "dgd")

_SERVE_APPLY = {
    "rosdhb": _rosdhb_apply,
    "robust_dgd": _robust_dgd_apply,
    "dgd": _dgd_apply,
}


def _check_serveable(name: str) -> None:
    if name not in SERVE_ALGORITHMS:
        raise ValueError(
            f"algorithm {name!r} cannot run as a streaming service "
            f"(serveable: {'|'.join(SERVE_ALGORITHMS)})"
            + (": dasha's wire is a compressed difference against "
               "server-side mirrors — its per-client control variates go "
               "stale under partial participation" if name == "dasha"
               else ""))


def make_wire_fn(cfg: AlgorithmConfig):
    """The client-side half of a serveable algorithm's round:
    ``wire_fn(atk_state, grads, mask_key, atk_key) -> (wire [n, D],
    new_atk_state)`` — exactly the op sequence the simulator's step runs
    before the server-side apply, so a client pool streaming these rows to
    ``repro.serve`` reproduces simulator trajectories bit-for-bit."""
    _check_serveable(cfg.name)
    if cfg.name == "robust_dgd":
        def wire_fn(atk_state, grads, mask_key, atk_key):
            del mask_key  # raw gradients: no compression
            return _byzantine_overwrite(cfg, atk_state, grads, atk_key)
    else:
        def wire_fn(atk_state, grads, mask_key, atk_key):
            return _compressed_wire(cfg, atk_state, grads, mask_key, atk_key)
    return wire_fn


def make_serve_apply_fn(cfg: AlgorithmConfig, agg):
    """The server-side half: ``apply_fn(state, wire, present, discount) ->
    (direction [D], new ServerState)``.

    ``present`` is a ``[n]`` bool row mask (clients that reported this
    round) and ``discount`` a ``[n]`` f32 staleness weight — both traced
    data, so one compiled program covers every participation level. With
    all rows present and ``discount == 1.0`` the graph computes exactly the
    simulator's full-participation round (multiply-by-1.0 and
    ``where(True, ...)`` are exact), which is the parity gate
    ``benchmarks/bench_serve.py`` enforces."""
    _check_serveable(cfg.name)
    hparams = static_hparams(cfg)
    apply_half = _SERVE_APPLY[cfg.name]

    def apply_fn(state: ServerState, wire: jnp.ndarray,
                 present: jnp.ndarray, discount: jnp.ndarray
                 ) -> Tuple[jnp.ndarray, ServerState]:
        if cfg.name == "rosdhb":
            return apply_half(cfg, agg, state, wire, hparams,
                              present=present, discount=discount)
        return apply_half(cfg, agg, state, wire,
                          present=present, discount=discount)

    return apply_fn


def algo_index(name: str, entries: Optional[Sequence[str]] = None) -> int:
    """Branch index of algorithm ``name`` inside ``entries`` (default the
    full :data:`ALGO_BANK`)."""
    entries = tuple(entries) if entries is not None else ALGO_BANK
    try:
        return entries.index(name)
    except ValueError:
        raise ValueError(
            f"algorithm {name!r} is not a branch of the algorithm bank "
            f"{entries}") from None


def static_hparams(cfg: AlgorithmConfig) -> Tuple[float, float, float, float]:
    """The ``(beta, mvr_a, 1-beta, 1-mvr_a)`` hyperparameter vector of a
    statically configured algorithm — the values a fused bank carries as its
    traced ``ScenarioParams.hparams`` cell vector. Slots an algorithm does
    not use are 0/1 (inert). The complements are computed here in python
    double precision so the traced branches see the exact f32 constants the
    static path folds in (bank == standalone bit-for-bit)."""
    beta = cfg.resolved_beta() if cfg.name == "rosdhb" else 0.0
    a = cfg.resolved_mvr_a() if cfg.name == "dasha" else 0.0
    return (beta, a, 1.0 - beta, 1.0 - a)


def make_algorithm_bank(cfg: AlgorithmConfig,
                        entries: Optional[Sequence[str]] = None):
    """Build the switch-based algorithm bank
    ``step(state, grads, mask_key, atk_key, agg, algo_idx, hparams, ...)``.

    A ``lax.switch`` over uniformly-shaped algorithm branches — every branch
    maps the shared :class:`ServerState` + per-worker gradients to a descent
    direction + the same state shape — selected by the *traced* integer
    ``algo_idx``. Per-branch hyperparameters (RoSDHB's momentum ``beta``,
    DASHA's MVR ``a``) arrive as the traced ``hparams`` ``[4]`` vector, so
    the paper's entire cross-algorithm Table-1 comparison compiles to ONE
    XLA program per fused bank (see ``repro.core.sweep.plan_grid``).

    ``entries`` (default ``cfg.bank`` or the full :data:`ALGO_BANK`) is the
    branch set; as with the attack/aggregator banks, under ``vmap`` a switch
    computes every branch per lane — restrict ``entries`` to the algorithms
    the grid actually uses. Static config (sparsifier kind, aggregator
    ``f``, dtypes, ``n_workers``/``f``) is shared by every branch, and so is
    the carry's :class:`StateLayout`: a dasha-free entry set runs on the
    pruned (mirror/prev_grad-less) state; any dasha entry requires the full
    width (validated here, loudly).
    """
    entries = tuple(entries if entries is not None
                    else (cfg.bank or ALGO_BANK))
    if not entries:
        raise ValueError("algorithm bank needs at least one entry")
    unknown = [e for e in entries if e not in ALGO_STEPS]
    if unknown:
        raise ValueError(
            f"unknown algorithm-bank entries {unknown} (known algorithms: "
            f"{'|'.join(ALGO_BANK)})")
    if "dasha" in entries and not cfg.resolved_state_layout().is_full:
        raise ValueError(
            "algorithm bank contains a dasha branch but cfg's StateLayout "
            "prunes mirror/prev_grad — dasha's variance-reduction state "
            "cannot be pruned (use StateLayout.full() or drop dasha)")

    def apply(state: ServerState, grads: jnp.ndarray, mask_key: jax.Array,
              atk_key: jax.Array, agg, algo_idx: jnp.ndarray,
              hparams: jnp.ndarray,
              attack_params: Optional[jnp.ndarray] = None,
              attack_idx: Optional[jnp.ndarray] = None,
              ratio: Optional[jnp.ndarray] = None
              ) -> Tuple[jnp.ndarray, ServerState]:
        branches = tuple(
            (lambda step: lambda st, g: step(
                cfg, agg, st, g, mask_key, atk_key, hparams,
                attack_params, attack_idx, ratio))(ALGO_STEPS[e])
            for e in entries)
        if len(branches) == 1:
            return branches[0](state, grads)
        return jax.lax.switch(algo_idx, branches, state, grads)

    return apply


# --------------------------------------------------------------------------
# Per-algorithm uplink + state-memory accounting
# --------------------------------------------------------------------------


def server_state_bytes(cfg: AlgorithmConfig, d: int) -> int:
    """Bytes of the ``[n, D]`` server banks one trajectory carries under
    ``cfg``'s resolved :class:`StateLayout` (momentum, plus mirror/prev_grad
    when materialised; the O(1) step counter and the attack slab are
    excluded).

    This is the paper's per-client *memory* comparison made executable:
    RoSDHB keeps one momentum vector per worker, while Byz-DASHA-PAGE
    additionally carries the gradient mirror h_i and the previous gradient
    for its MVR correction — so a dasha(-capable) config costs
    ``n*D*(2*momentum_dtype + 4)`` bytes against RoSDHB's ``n*D*dtype``
    (3x at f32). The carry specialisation makes the engine charge each
    algorithm exactly its own footprint instead of padding everyone to
    DASHA's width.
    """
    n = cfg.n_workers
    layout = cfg.resolved_state_layout()
    mdt_bytes = jnp.dtype(cfg.momentum_dtype).itemsize
    total = n * d * mdt_bytes                      # momentum bank
    if layout.mirror:
        total += n * d * mdt_bytes                 # dasha mirrors h_i
    if layout.prev_grad:
        total += n * d * 4                         # f32 previous gradients
    return total


def algo_payload_bytes(cfg: AlgorithmConfig, d: int,
                       bytes_per_value: int = 4) -> int:
    """Per-worker uplink bytes per round under ``cfg``'s ACTUAL wire format.

    Delegates to :mod:`repro.core.wire` — the one accounting shared with the
    streaming server's ``repro.serve.protocol``, so simulator and service
    can never disagree on what a round costs (see that module for the
    per-algorithm formats). Raises ``ValueError`` for bank configs — a bank
    mixes wire formats; account per cell with each cell's own config.
    """
    return W.per_worker_payload_bytes(cfg.name, d, cfg.sparsifier,
                                      bytes_per_value=bytes_per_value)


def _bank_payload_floats(entries: Sequence[str], d: int,
                         sp: C.SparsifierConfig,
                         ratio: Optional[jnp.ndarray]) -> jnp.ndarray:
    """Traced ``[n_entries]`` per-branch payload-float counts (the bank's
    per-round aux must stay uniform across branches)."""
    if ratio is not None:
        k = jnp.maximum(1.0, jnp.round(ratio * d))
    else:
        k = float(C.payload_floats(d, sp))
    vals = [jnp.asarray(float(d) if e == "robust_dgd" else k, jnp.float32)
            for e in entries]
    return jnp.stack(vals)


def server_round(cfg: AlgorithmConfig, state: ServerState,
                 grads: jnp.ndarray, key: jax.Array,
                 attack_params: Optional[jnp.ndarray] = None,
                 scenario: Optional[ScenarioParams] = None,
                 aggregator: Optional[G.Aggregator] = None
                 ) -> Tuple[jnp.ndarray, ServerState, dict]:
    """Execute one server round.

    Args:
      cfg: algorithm configuration.
      state: current server state.
      grads: honest-computed per-worker gradients ``[n, D]`` (f32). Rows of
        Byzantine workers are ignored and replaced by the attack.
      key: PRNG key for this round (mask sampling + stochastic attacks).
      attack_params: traced attack parameters — the ``[2]`` coefficient
        vector for ``attack.name='linear'`` (or the per-branch parameter
        vector for ``attack.name='bank'``); lets a grid of attacks share
        one compiled program (see ``repro.core.sweep``).
      scenario: traced :class:`ScenarioParams` cell vector — the fused grid
        axis. Its ``attack_coeffs`` supersede ``attack_params``;
        ``attack_idx`` selects the attack-bank branch
        (``attack.name='bank'``); ``agg_idx`` switches the aggregator bank;
        ``ratio`` overrides the sparsifier keep-ratio; ``algo_idx`` selects
        the algorithm-bank branch (``cfg.name='bank'``) with per-cell
        ``hparams``. Static config fills in whatever is ``None``. Stateful
        adversaries carry their memory in ``state.attack`` (threaded
        through the scan like every other server-state component).
      aggregator: the robust rule to apply in place of the one built from
        ``cfg.aggregator`` (the trainer passes that rule mapped over its
        device mesh); it takes precedence over a traced ``agg_idx``.

    Returns:
      (direction R [D] to descend, next state, aux dict).
    """
    n, d = grads.shape
    assert n == cfg.n_workers, (n, cfg.n_workers)
    # what the wire is made from: clipped rows and the round's mask and
    # attack keys
    with stages.scope("sparsify"):
        if cfg.clip_norm is not None:
            norms = jnp.linalg.norm(grads.astype(jnp.float32), axis=1,
                                    keepdims=True)
            scale = jnp.minimum(1.0,
                                cfg.clip_norm / jnp.maximum(norms, 1e-12))
            grads = (grads * scale.astype(grads.dtype))
        mask_key, atk_key = jax.random.split(key)
    ratio = attack_idx = hparams = None
    if scenario is not None:
        if scenario.attack_coeffs is not None:
            attack_params = scenario.attack_coeffs
        attack_idx = scenario.attack_idx
        ratio = scenario.ratio
        hparams = scenario.hparams
    if aggregator is not None:
        agg = aggregator
    elif scenario is not None and scenario.agg_idx is not None:
        bank = G.make_aggregator_bank(cfg.aggregator)
        agg = lambda x: bank(x, scenario.agg_idx)  # noqa: E731
    else:
        agg = G.make_aggregator(cfg.aggregator)
    sp = cfg.sparsifier

    if cfg.name == "bank":
        # The cross-algorithm fusion axis: lax.switch over update rules on
        # the traced algo_idx, per-cell hyperparameters as traced data.
        if scenario is None or scenario.algo_idx is None:
            raise ValueError(
                "algorithm bank needs a traced branch selector: pass a "
                "ScenarioParams with algo_idx (and hparams) — see "
                "sweep.FusedBank.scenario_params")
        if hparams is None:
            raise ValueError(
                "algorithm bank needs per-cell hyperparameters: pass a "
                "ScenarioParams with hparams=[beta, mvr_a, 1-beta, 1-mvr_a] "
                "(see algorithms.static_hparams)")
        entries = tuple(cfg.bank or ALGO_BANK)
        r, new = make_algorithm_bank(cfg, entries)(
            state, grads, mask_key, atk_key, agg, scenario.algo_idx,
            hparams, attack_params, attack_idx, ratio)
        payload = _bank_payload_floats(entries, d, sp,
                                       ratio)[scenario.algo_idx]
        return r, new, {"payload_floats_per_worker": payload}

    try:
        step = ALGO_STEPS[cfg.name]
    except KeyError:
        raise ValueError(f"unknown algorithm: {cfg.name!r}") from None
    if hparams is None:
        hparams = static_hparams(cfg)
    r, new = step(cfg, agg, state, grads, mask_key, atk_key, hparams,
                  attack_params, attack_idx, ratio)
    aux = {"payload_floats_per_worker": (d if cfg.name == "robust_dgd"
                                         else C.payload_floats(d, sp))}
    return r, new, aux


def compression_backend_label(cfg: AlgorithmConfig, d: int) -> str:
    """Backend of the round's compression on a ``[n, d]`` bank: the
    sparsifier's (:func:`repro.core.compression.kernel_backend_label`) for
    the algorithms that send :func:`_compressed_wire`; ``jnp`` for dasha's
    masked differences and robust_dgd's raw gradients, which no kernel
    takes."""
    if cfg.name in ("rosdhb", "dgd"):
        return C.kernel_backend_label(cfg.sparsifier, d)
    return "jnp"


def apply_direction(params_flat: jnp.ndarray, r: jnp.ndarray,
                    gamma) -> jnp.ndarray:
    """Step 7: theta^t = theta^{t-1} - gamma R^t (``gamma`` may be a traced
    per-cell scalar inside a fused bank)."""
    return params_flat - gamma * r
