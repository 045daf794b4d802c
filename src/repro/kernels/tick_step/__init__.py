"""Fused per-tick worker phase: the W sequential select/pop draws of the
engine's tick inner loop in one kernel invocation (see ops.tick_step)."""
from .kernel import tick_step_grid  # noqa: F401
from .ops import tick_step, resolve_impl  # noqa: F401
