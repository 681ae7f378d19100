"""Graceful-degradation retry policy — the residency ladder as a safety net.

``repro.oocore.planner`` built the residency ladder (factors whole in
VMEM → one rank slab → streamed tile window → fused → rank-tiled →
segment-sum ``ref``) for *performance*: pick the fastest rung that fits.
This module walks the same ladder as a *fallback* structure: when a rung
fails with a resource-class fault (VMEM OOM, failed lowering, an
injected :class:`~repro.resilience.faults.ResourceFault`), the dispatch
steps one rung **down** — every lower rung computes the same MTTKRP with
a strictly smaller working set — and when the compiled path itself is
the problem it flips compiled → interpret (an explicit override through
``runtime.execution.resolve_interpret``) — except under
``execution_mode="compiled"``, where the fault propagates. Transient
faults get bounded
retry with exponential backoff. Corruption faults are never retried and
never degraded through — a wrong answer must not be computable from bad
bytes, so they propagate.

Every decision is counted in the ``resilience.*`` namespace of the
closed ``repro.obs`` registry (``retries`` / ``degradations`` /
``interpret_fallbacks``), so a chaos run can assert
injected == handled: **zero silent fallbacks**.

This module deliberately imports nothing from the kernel stack (backend
names are string literals, validated against ``ops.BACKENDS`` by
``tests/test_resilience.py``) so ``ops.py`` can import it without a
cycle; the stack reaches the active policy through
:func:`get_policy` / :func:`use_policy`.
"""
from __future__ import annotations

import contextlib
import dataclasses
import logging
import time
from typing import Callable

from ..obs import counters as _obs
from .faults import (
    CorruptionFault,
    InjectedFault,
    ResourceFault,
    TransientFault,
)

__all__ = [
    "DEGRADATION_LADDER",
    "ResilienceExhausted",
    "RetryPolicy",
    "get_policy",
    "next_rung",
    "use_policy",
]

_LOG = logging.getLogger(__name__)

# The dispatch-level degradation ladder, fastest/tightest rung first —
# the same order ``oocore.planner.plan_residency`` prefers, down to the
# segment-sum reference. Every rung computes the same mode step from the
# same inputs, up to fp32 accumulation order, so a step down trades only
# performance, never correctness.
DEGRADATION_LADDER = (
    "pallas_fused_gather",
    "pallas_fused_gather_tiled",
    "pallas_fused_gather_stream",
    "pallas_fused",
    "pallas_fused_tiled",
    "ref",
)


def next_rung(backend: str) -> str | None:
    """The rung below ``backend`` (``None`` at/below the bottom).

    Backends outside the ladder (the bf16 aliases resolve before
    dispatch; ``ref`` is the floor) have nowhere to go.
    """
    try:
        i = DEGRADATION_LADDER.index(backend)
    except ValueError:
        return None
    return DEGRADATION_LADDER[i + 1] if i + 1 < len(DEGRADATION_LADDER) \
        else None


class ResilienceExhausted(RuntimeError):
    """Retries and the degradation ladder are both spent — the fault was
    real and unrecoverable. Chained to the last underlying fault; never
    raised in place of a *silent* wrong answer."""


@dataclasses.dataclass
class RetryPolicy:
    """Bounded retry + ladder degradation configuration.

    ``backoff_base_s=0`` (the default) disables sleeping — CI chaos runs
    replay deterministically without wall-clock cost; production sets a
    real base. ``sleep`` is injectable for tests.
    """

    max_retries: int = 3
    backoff_base_s: float = 0.0
    backoff_factor: float = 2.0
    sleep: Callable[[float], None] = time.sleep

    def _backoff(self, attempt: int) -> None:
        if self.backoff_base_s > 0:
            self.sleep(self.backoff_base_s
                       * self.backoff_factor ** (attempt - 1))

    def run(self, site: str, thunk: Callable):
        """Host-level bounded retry of ``thunk`` on transient faults.

        The driver-side wrapper: a per-mode jitted call (MTTKRP, remap)
        or a chunk launch that raises :class:`TransientFault` is retried
        up to ``max_retries`` times with backoff, each retry counted
        under ``resilience.retries{site=...}``. Resource and corruption
        faults propagate — they are handled at the dispatch layer (or
        not at all).
        """
        attempt = 0
        while True:
            try:
                return thunk()
            except TransientFault as e:
                attempt += 1
                _obs.add("resilience.retries", site=site)
                _LOG.warning("transient fault at %s (attempt %d/%d): %s",
                             site, attempt, self.max_retries, e)
                if attempt > self.max_retries:
                    raise ResilienceExhausted(
                        f"site {site!r}: {attempt} transient faults in a "
                        f"row exceeded max_retries={self.max_retries}"
                    ) from e
                self._backoff(attempt)

    def dispatch(self, call: Callable[[str, bool | None], object],
                 backend: str, interpret: bool | None):
        """Degradation-aware kernel dispatch: retry, flip, step down.

        ``call(backend, interpret)`` runs one concrete mode step (host
        Python — under jit this is trace time, where lowering/OOM
        failures actually surface). The walk:

        * :class:`TransientFault` — bounded retry at the same rung;
        * ``ExecutionModeError`` (from ``runtime.execution``) or a
          :class:`ResourceFault` while the compiled path is in play —
          first flip to an explicit ``interpret=True`` override at the
          same rung (counted ``resilience.interpret_fallbacks``);
        * :class:`ResourceFault` under interpret — step one rung down
          the ladder (counted ``resilience.degradations{from,to}``);
        * under ``execution_mode="compiled"`` the flip is never taken:
          the fault propagates, since that mode promises compiled
          kernels or an error;
        * :class:`CorruptionFault` — propagate immediately;
        * ladder/retries exhausted — :class:`ResilienceExhausted`
          chained to the last fault. Never a silent wrong answer.
        """
        from ..runtime.execution import (ExecutionModeError,
                                         get_execution_mode,
                                         resolve_interpret)

        current = backend
        cur_interpret = interpret
        retries = 0
        while True:
            try:
                return call(current, cur_interpret)
            except CorruptionFault:
                raise
            except TransientFault as e:
                retries += 1
                _obs.add("resilience.retries", site="ops.kernel")
                if retries > self.max_retries:
                    raise ResilienceExhausted(
                        f"backend {current!r}: {retries} transient faults "
                        f"exceeded max_retries={self.max_retries}") from e
                self._backoff(retries)
            except (ResourceFault, ExecutionModeError) as e:
                # Effective flag the failing attempt ran with: an
                # explicit override wins; otherwise ask the policy (an
                # ExecutionModeError from resolution means "compiled
                # requested, impossible" — also not yet interpreting).
                if cur_interpret is not None:
                    was_interpret = cur_interpret
                elif isinstance(e, ExecutionModeError):
                    was_interpret = False
                else:
                    try:
                        was_interpret = resolve_interpret()
                    except (ExecutionModeError, InjectedFault):
                        # The probe itself goes through the
                        # execution.resolve fault site; an injected
                        # fault here means "resolution is broken" —
                        # same answer as ExecutionModeError.
                        was_interpret = False
                if not was_interpret:
                    if get_execution_mode() == "compiled":
                        raise
                    cur_interpret = True
                    _obs.add("resilience.interpret_fallbacks",
                             backend=current)
                    _LOG.warning("compiled path failed at %s (%s); "
                                 "falling back to interpret", current, e)
                    continue
                if isinstance(e, ExecutionModeError):
                    raise           # interpret already forced; unrecoverable
                nxt = next_rung(current)
                if nxt is None:
                    raise ResilienceExhausted(
                        f"resource fault at the bottom of the degradation "
                        f"ladder (backend {current!r})") from e
                _obs.add("resilience.degradations", **{"from": current,
                                                       "to": nxt})
                _LOG.warning("resource fault at %s (%s); degrading to %s",
                             current, e, nxt)
                current = nxt


# ---------------------------------------------------------------------------
# The process-wide active policy — how the dispatch layer finds it
# ---------------------------------------------------------------------------

_policy: RetryPolicy | None = None


def get_policy() -> RetryPolicy | None:
    """The active policy, or ``None`` (the default: fail fast, exactly
    the pre-resilience behavior)."""
    return _policy


@contextlib.contextmanager
def use_policy(policy: RetryPolicy | None = None):
    """Activate a resilience policy for the block; restores on exit.

    ``None`` activates a default :class:`RetryPolicy`. While active,
    ``ops.mttkrp_device_step`` routes through :meth:`RetryPolicy.dispatch`
    and the oocore executor retries chunk launches — drivers
    (``cp_als_distributed(resilience=...)``) enter this scope for the
    whole decomposition.
    """
    global _policy
    scoped = RetryPolicy() if policy is None else policy
    previous = _policy
    _policy = scoped
    try:
        yield scoped
    finally:
        _policy = previous
