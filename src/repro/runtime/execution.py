"""Execution-mode policy: the one interpret / compiled / auto switch.

Every Pallas entry point in this repo takes ``interpret: bool | None``
and resolves ``None`` here, so "does this kernel run in the interpreter
or compile to Mosaic?" is a single session-wide policy instead of a
per-call hardcode scattered through the stack (kernel.py → ops.py →
oocore/executor.py → tune/microbench.py → core/distributed.py all
defer).

Three modes (:data:`EXECUTION_MODES`):

  * ``"interpret"`` — always run the Pallas interpreter. Works on any
    backend; this is what CPU-only CI executes.
  * ``"compiled"`` — always compile to Mosaic. Raises
    :class:`ExecutionModeError` (with the probe's reason) when the host
    cannot execute Mosaic kernels, rather than silently interpreting —
    a wall-clock claim made under this mode is honest by construction.
  * ``"auto"`` (default) — compiled when the capability probe finds an
    attached TPU, otherwise interpret (the fallback reason is logged
    once and recorded in :func:`describe_meta`).

The capability probe runs once, lazily, the first time a mode is
resolved (:func:`capability`) — importing the package touches no JAX
backend — answering "can a ``pallas_call(interpret=False)`` *execute*
here?". Note the distinction from *lowering*: StableHLO +
Mosaic lowering works on any host via the AOT path
(``jax.jit(f).trace(...).lower(lowering_platforms=("tpu",))``) — that is
what ``repro.kernels.mttkrp.lowering`` validates on CPU-only CI.

Mode changes clear jax's compilation caches: the resolved interpret
flag is baked into traces as a static argument, so a cached jit entry
from the previous mode would otherwise keep executing the old policy.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import logging

from ..obs import counters as _obs
from ..resilience import faults as _faults

__all__ = [
    "EXECUTION_MODES",
    "Capability",
    "ExecutionModeError",
    "capability",
    "compiles",
    "probe_capability",
    "get_execution_mode",
    "set_execution_mode",
    "execution_mode",
    "resolve_interpret",
    "default_interpret",
    "describe_meta",
]

_LOG = logging.getLogger(__name__)

EXECUTION_MODES = ("interpret", "compiled", "auto")


class ExecutionModeError(RuntimeError):
    """``execution_mode="compiled"`` on a host that cannot run Mosaic."""


@dataclasses.dataclass(frozen=True)
class Capability:
    """What the capability probe found on this host.

    ``can_compile`` answers "can a ``pallas_call(interpret=False)``
    execute here?" — i.e. is a TPU attached. ``reason`` is the
    human-readable explanation when it cannot (empty when it can); it is
    surfaced in the ``"compiled"``-mode error and in the logged
    ``"auto"`` fallback.
    """

    platform: str
    can_compile: bool
    reason: str


def probe_capability() -> Capability:
    """Probe once whether compiled (Mosaic) Pallas execution is possible.

    The probe is deliberately cheap and deterministic: Mosaic kernels
    execute only on TPU backends, so ``jax.default_backend()`` is the
    whole story — there is no speculative trial compilation to a device
    that may be busy.
    """
    import jax

    platform = jax.default_backend()
    if platform == "tpu":
        return Capability(platform=platform, can_compile=True, reason="")
    return Capability(
        platform=platform, can_compile=False,
        reason=(f"jax default backend is {platform!r}, not 'tpu': Mosaic "
                "(compiled Pallas) kernels cannot execute on this host — "
                "only lowering validation is possible "
                "(repro.kernels.mttkrp.lowering)"))


@functools.cache
def capability() -> Capability:
    """The probe's answer for this process, probed on first use."""
    return probe_capability()


_mode: str = "auto"
_fallback_logged: bool = False


def get_execution_mode() -> str:
    """The session's current execution mode."""
    return _mode


def set_execution_mode(mode: str) -> str:
    """Set the session execution mode; returns the previous mode.

    Clears jax's compilation caches (see module docstring): traces bake
    the resolved interpret flag in, so stale entries from the previous
    mode must not survive. Set the mode at configuration time, not in an
    inner loop.
    """
    global _mode
    if mode not in EXECUTION_MODES:
        raise ValueError(
            f"unknown execution_mode {mode!r}: expected one of "
            f"{EXECUTION_MODES}")
    previous = _mode
    if mode != previous:
        _mode = mode
        import jax

        jax.clear_caches()
        _LOG.info("execution_mode: %s -> %s", previous, mode)
    return previous


@contextlib.contextmanager
def execution_mode(mode: str):
    """Context manager: run a block under ``mode``, then restore."""
    previous = set_execution_mode(mode)
    try:
        yield capability()
    finally:
        set_execution_mode(previous)


def resolve_interpret(override: bool | None = None,
                      mode: str | None = None) -> bool:
    """Resolve the effective ``interpret`` flag for one kernel call.

    ``override`` is a caller's explicit bool (wins unconditionally;
    ``None`` defers to the policy). ``mode`` defaults to the session
    mode. Raises :class:`ExecutionModeError` for ``"compiled"`` on an
    incapable host — never silently interprets under that mode.
    """
    global _fallback_logged
    if override is not None:
        return bool(override)
    # Registered failure boundary (repro.resilience): resolution can
    # discover mid-job that the compiled path is gone. The hook sits
    # after the override check so a degradation policy's explicit
    # ``interpret=True`` fallback bypasses the faulty resolution.
    _faults.fault_site("execution.resolve")
    if mode is None:
        mode = _mode
    if mode not in EXECUTION_MODES:
        raise ValueError(
            f"unknown execution_mode {mode!r}: expected one of "
            f"{EXECUTION_MODES}")
    if mode == "interpret":
        _obs.add("execution.resolve", mode=mode, interpret=True)
        return True
    if mode == "compiled":
        if not capability().can_compile:
            raise ExecutionModeError(
                "execution_mode='compiled' but compiled Pallas execution "
                f"is unavailable: {capability().reason}. Use "
                "execution_mode='interpret' (or 'auto', which falls back "
                "with this reason) on this host.")
        _obs.add("execution.resolve", mode=mode, interpret=False)
        return False
    # auto
    if capability().can_compile:
        _obs.add("execution.resolve", mode=mode, interpret=False)
        return False
    if not _fallback_logged:
        # Strictly once per process, and as an obs event first: the
        # counted `execution.fallback` record survives into traces and
        # reports even when nobody configured logging.
        _obs.add("execution.fallback", platform=capability().platform)
        _LOG.info("execution_mode='auto' resolves to interpret: %s",
                  capability().reason)
        _fallback_logged = True
    _obs.add("execution.resolve", mode=mode, interpret=True)
    return True


def compiles() -> bool:
    """Will kernels compile (not interpret) under the session mode?

    For static choices made before any kernel runs (the runtime's block
    size). Unlike :func:`resolve_interpret` it counts nothing, passes no
    fault site and never raises: ``"compiled"`` answers True even where
    the first kernel call will then refuse to run.
    """
    if _mode == "auto":
        return capability().can_compile
    return _mode == "compiled"


def default_interpret() -> bool:
    """The policy's answer with no per-call override — kernel.py's hook."""
    return resolve_interpret()


def describe_meta() -> dict:
    """Fingerprint of the active policy, for calibration-table metadata.

    ``interpret`` is the resolved flag the session's kernel calls use
    (``None`` if the mode cannot resolve on this host — a ``"compiled"``
    setting that would raise); ``execution_probe`` carries the probe's
    fallback reason so a saved table records *why* it was measured the
    way it was.
    """
    try:
        interpret = resolve_interpret()
    except ExecutionModeError:
        interpret = None
    return dict(
        execution_mode=_mode,
        interpret=interpret,
        execution_probe=capability().reason or "tpu",
    )
