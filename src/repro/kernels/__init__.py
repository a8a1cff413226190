"""Pallas TPU kernels for the perf-critical compute of the virtual server
(the robust-aggregation families cwtm / median / pairdist plus the randk
compressor) and the attention hot loop (flash_attention).

Each kernel ships as <name>.py (pl.pallas_call + BlockSpec), ops.py (jitted
wrapper with TPU/XLA backend selection) and ref.py (pure-jnp oracle used by
the interpret-mode test sweeps). The aggregation kernels additionally ship
explicitly *batched* entry points over the grid engine's fused
``[n_cells * n_seeds]`` leading axis; :func:`batchable` routes ``jax.vmap``
of the per-lane rule onto them.

Each ``pallas_call`` is named after its kernel (``name=``): the compiled
custom-call instruction, and so a profiler trace, carries that name
(``compress``, ``decompress``, ``cwtm``, ``attention``, ...), whatever
function or scope encloses the call.

Every ``use_pallas`` flag of the package resolves the same way
(:func:`use_kernel`, :func:`resolve_kernel_backend`): ``None`` means the
compiled kernel on a TPU and the jnp oracle elsewhere; ``True`` forces the
kernel, in interpret mode off-TPU. Interpret mode is refused on a TPU, so a
TPU run never executes a kernel through the interpreter.
"""

from __future__ import annotations

from typing import Callable, Dict, Optional

import jax
from jax.custom_batching import custom_vmap


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def use_kernel(use_pallas: Optional[bool], interpret: bool) -> bool:
    """Resolve an ops wrapper's ``use_pallas`` (``None``: kernel on TPU)
    and refuse ``interpret=True`` on a TPU."""
    if interpret and on_tpu():
        raise ValueError("Pallas interpret mode is refused on a TPU backend: "
                         "the compiled kernel runs there")
    return on_tpu() if use_pallas is None else bool(use_pallas)


def resolve_kernel_backend(use_pallas: Optional[bool]
                           ) -> Optional[Dict[str, bool]]:
    """Resolve a config's ``use_pallas`` against the live backend: ``None``
    for the pure-jnp path, else ``{"interpret": bool}`` for the kernel
    path — interpret mode exactly when the backend is not a TPU, so
    ``use_pallas=True`` on CPU exercises the real kernel bodies (the
    parity-test path) instead of failing to lower."""
    if not use_kernel(use_pallas, False):
        return None
    return {"interpret": not on_tpu()}


def backend_label(kernel_backend: Optional[Dict[str, bool]]) -> str:
    """``jnp`` | ``pallas`` | ``pallas-interpret`` for a resolved backend."""
    if kernel_backend is None:
        return "jnp"
    return "pallas-interpret" if kernel_backend["interpret"] else "pallas"


def batchable(fn2d: Callable, fn3d: Callable) -> Callable:
    """Route ``jax.vmap`` of a per-lane ``[n, d]`` rule onto an explicitly
    batched ``[B, n, d]`` kernel.

    The grid engine runs aggregation per vmap lane of the fused
    ``[n_cells * n_seeds]`` axis; without this wrapper, ``vmap`` of a
    ``pallas_call`` falls back to Pallas's generic batching rule. With it,
    the engine's vmap lands on the hand-laid batched grid (one
    (B, d/block_d) launch, batch as the leading grid dimension). An
    unbatched call — or a vmap that does not map the stacked argument —
    just runs ``fn2d``.
    """
    op = custom_vmap(fn2d)

    @op.def_vmap
    def _batch_rule(axis_size, in_batched, x):  # noqa: ANN001
        if not in_batched[0]:
            return fn2d(x), False
        return fn3d(x), True

    return op
