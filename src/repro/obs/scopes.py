"""Names of the phases of one ALS sweep, as ``jax.named_scope`` scopes.

The production sweep (``core.cpals.make_als_sweep``) is one jitted
``shard_map``: no host span can split it. These scopes name its phases
inside the program instead. They reach the compiled HLO's
``metadata={op_name="..."}``, and through it every device op of a
``jax.profiler`` trace can be put down to a phase::

    jit(inner)/als_mode0/mttkrp/jit(mttkrp_device_step)/jit(build_block_layout)/layout/...
    jit(inner)/als_mode1/mttkrp/jit(mttkrp_device_step)/row_gather/...
    jit(inner)/als_mode0/solve/...
    jit(inner)/als_mode0/remap/...
    jit(inner)/fit/...

A scope changes metadata only, never the computation. :data:`SCOPES` is
a pure literal: ``tests/check_docs.py`` reads it with ``ast`` and syncs
it with the scope table in ``docs/observability.md``. stdlib only.
"""
from __future__ import annotations

__all__ = ["FIT", "LAYOUT", "MODE", "MTTKRP", "REMAP", "ROW_GATHER", "SCOPES",
           "SOLVE", "mode_scope"]

# MODE is a prefix: mode n's work runs under ``f"{MODE}{n}"``.
SCOPES = ("als_mode", "mttkrp", "layout", "solve", "remap", "fit",
          "row_gather")
MODE, MTTKRP, LAYOUT, SOLVE, REMAP, FIT, ROW_GATHER = SCOPES


def mode_scope(mode: int) -> str:
    """The scope that holds mode ``mode``'s work in one sweep."""
    return f"{MODE}{mode}"
