"""jit'd dispatch wrapper for the fused tick-step kernel.

This is the seam ``repro.core.engine.make_tick`` routes the worker phase
through when ``EngineConfig.tick_impl`` resolves to the fused path: the
pure-jnp oracle (``ref``) and the Pallas kernel (``pallas``) run the same
op sequence per draw, so ``impl`` changes where the tick runs, never what
it returns — pinned per scheduler by ``tests/test_tick_step.py``.
"""
from __future__ import annotations

import functools

import jax

from .kernel import tick_step_pallas
from .ref import MODES, tick_step_ref  # noqa: F401  (MODES re-exported)

IMPLS = ("auto", "ref", "pallas")


def resolve_impl(impl: str) -> str:
    """Normalize an ``impl`` request: ``auto`` means Pallas on TPU, the jnp
    oracle elsewhere.  Unknown names fail loudly with the vocabulary."""
    if impl not in IMPLS:
        raise ValueError(f"unknown impl {impl!r}; one of {IMPLS}")
    if impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "ref"
    return impl


@functools.partial(jax.jit, static_argnames=("mode", "impl"))
def tick_step(shares, qcount, window, free, u, *, mode: str = "themis",
              impl: str = "auto"):
    """The whole worker phase of one engine tick, fused.

    shares, qcount: [S, J]; window: [S, J, W] in fifo mode (themis mode
    reads none: pass None); free, u: [S, W].  Under ``jax.vmap`` the Pallas
    path folds the lanes into the rows of one kernel invocation.
    Returns ``(sel i32[S,W], valid bool[S,W], demand_any bool[S,W],
    qcount_out i32[S,J], pops i32[S,J])`` — semantics in ref.py.
    """
    impl = resolve_impl(impl)
    if impl == "pallas":
        return tick_step_pallas(shares, qcount, window, free, u, mode=mode,
                                interpret=jax.default_backend() != "tpu")
    sel, valid, dany, qout, pops = tick_step_ref(shares, qcount, window,
                                                 free, u, mode=mode)
    return sel, valid, dany, qout, pops
