"""Process-wide counter registry — one namespace for every counted metric.

Before this module, counted metrics lived in per-layer one-offs: the
oocore executor returned a ``StreamStats`` struct, remap exchange bytes
were re-derived inside individual benches, dispatch decisions were only
visible as the backend a kernel happened to run, and the execution-mode
fallback was a bare ``logging`` line. Nothing could correlate them. The
:class:`CounterRegistry` is the shared sink: every layer emits into one
flat dotted namespace (``oocore.dma.scheduled_bytes``,
``remap.a2a.bytes{transition=0}``, ``dispatch.backend{...}``), and
tooling — the span tracer's per-span counter deltas, ``python -m
repro.obs report``, and the CI baseline gate
(:mod:`repro.obs.baseline`) — reads it back uniformly.

Design rules:

* **Closed namespace.** Every counter's base name must be a member of
  :data:`NAMESPACES` (a pure literal, parsed by ``tests/check_docs.py``
  with ``ast`` and synced against the table in
  ``docs/observability.md``). An undocumented counter is a
  ``ValueError`` at the emit site, the same stance ``ops.BACKENDS``
  takes with the kernel matrix.
* **Labels, not name explosions.** Dimensional breakdowns attach as
  sorted ``{key=value}`` label suffixes — ``dispatch.backend{backend=
  pallas_fused_gather,source=static}`` — so the base name stays a
  stable aggregation key (:meth:`CounterRegistry.total`).
* **Counted, not timed, unless suffixed ``_s``.** Byte/decision/count
  metrics are host-independent and eligible for the committed baseline
  (``repro.obs.baseline.COUNTED_PREFIXES``); wall-time counters carry a
  ``_s`` suffix and never enter the gate.
* **stdlib only.** This module imports nothing from the rest of the
  repo (and no jax), so any layer — the residency planner included —
  can emit without an import cycle.

Emission is a dict update behind a lock; hot paths that emit do so at
trace/plan time (dispatch, planner) or once per host-level step
(oocore, remap), never per nonzero.
"""
from __future__ import annotations

import contextlib
import threading

__all__ = [
    "NAMESPACES",
    "CounterRegistry",
    "add",
    "counter_key",
    "get_registry",
    "record_remap_exchange",
    "record_stream_stats",
    "split_key",
    "use_registry",
]

# The closed counter namespace. Pure literal — tests/check_docs.py reads
# it with ``ast`` and fails CI when docs/observability.md's counter
# table and this tuple disagree (either direction). Keep it sorted.
NAMESPACES = (
    "cpals.phase_s",
    "cpals.sweep_s",
    "cpals.sweeps",
    "dispatch.backend",
    "dryrun.compile_s",
    "dryrun.lower_s",
    "execution.fallback",
    "execution.resolve",
    "flycoo.build_s",
    "oocore.chunks",
    "oocore.dma.distinct_bytes",
    "oocore.dma.index_stream_bytes",
    "oocore.dma.pipelined_bytes",
    "oocore.dma.scheduled_bytes",
    "oocore.mode_step_s",
    "oocore.mode_steps",
    "ops.step.model_bytes",
    "ops.step_s",
    "planner.plans",
    "planner.vmem.plan_bytes",
    "remap.a2a.bytes",
    "remap.a2a.uniform_bytes",
    "remap.transitions",
    "reorder.dma.postsort_distinct_bytes",
    "reorder.dma.postsort_scheduled_bytes",
    "reorder.dma.presort_distinct_bytes",
    "reorder.dma.presort_scheduled_bytes",
    "reorder.perms",
    "resilience.checkpoint.restores",
    "resilience.checkpoint.saves",
    "resilience.degradations",
    "resilience.injected",
    "resilience.interpret_fallbacks",
    "resilience.retries",
    "resilience.site_calls",
    "resilience.solve.guards",
    "resilience.table_fallbacks",
    "runtime.prepare_s",
    "serve.decode_s",
    "serve.prefill_s",
    "serve.tokens",
    "tune.measure_s",
    "tune.points",
)

_NAMESPACE_SET = frozenset(NAMESPACES)


def counter_key(name: str, labels: dict | None = None) -> str:
    """Canonical registry key: ``name`` or ``name{k=v,...}`` (sorted keys)."""
    if not labels:
        return name
    inner = ",".join(f"{k}={labels[k]}" for k in sorted(labels))
    return f"{name}{{{inner}}}"


def split_key(key: str) -> tuple[str, dict]:
    """Inverse of :func:`counter_key`: ``(base_name, labels)``."""
    if not key.endswith("}") or "{" not in key:
        return key, {}
    name, _, inner = key.partition("{")
    labels = {}
    for part in inner[:-1].split(","):
        if part:
            k, _, v = part.partition("=")
            labels[k] = v
    return name, labels


class CounterRegistry:
    """A flat, labeled, validated counter store.

    Values accumulate with :meth:`add` (ints stay ints; a float emit
    makes the counter float). Thread-safe; snapshots are plain dicts so
    the tracer can diff them per span.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._counts: dict[str, float] = {}

    def add(self, name: str, value=1, **labels) -> None:
        """Accumulate ``value`` into ``name`` (with optional labels).

        ``name`` must be a member of :data:`NAMESPACES` — an
        undocumented counter fails loudly at the emit site.
        """
        if name not in _NAMESPACE_SET:
            raise ValueError(
                f"counter {name!r} is not in repro.obs.counters.NAMESPACES "
                "— add it there and document it in docs/observability.md")
        key = counter_key(name, labels)
        with self._lock:
            self._counts[key] = self._counts.get(key, 0) + value

    def get(self, name: str, default=0, **labels):
        return self._counts.get(counter_key(name, labels), default)

    def total(self, prefix: str) -> float:
        """Sum of every counter whose base name starts with ``prefix``."""
        with self._lock:
            return sum(v for k, v in self._counts.items()
                       if split_key(k)[0].startswith(prefix))

    def snapshot(self) -> dict[str, float]:
        """Point-in-time copy (sorted keys — deterministic serialization)."""
        with self._lock:
            return {k: self._counts[k] for k in sorted(self._counts)}

    def reset(self) -> None:
        with self._lock:
            self._counts.clear()

    def __len__(self) -> int:
        return len(self._counts)

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return f"CounterRegistry({len(self._counts)} counters)"


# The process-wide default registry. Emitters resolve it through
# get_registry() at emit time so ``use_registry`` can scope collection
# (the baseline gate runs inside a fresh scoped registry).
_REGISTRY = CounterRegistry()


def get_registry() -> CounterRegistry:
    """The currently active process-wide registry."""
    return _REGISTRY


def add(name: str, value=1, **labels) -> None:
    """Emit into the active registry — the one-liner every layer uses."""
    _REGISTRY.add(name, value, **labels)


@contextlib.contextmanager
def use_registry(registry: CounterRegistry | None = None):
    """Scope the active registry (fresh one by default), then restore.

    Everything emitted inside the block — from any module — lands in the
    scoped registry, which is how the baseline gate collects one run's
    counters without inheriting whatever the process did before.
    """
    global _REGISTRY
    scoped = CounterRegistry() if registry is None else registry
    previous = _REGISTRY
    _REGISTRY = scoped
    try:
        yield scoped
    finally:
        _REGISTRY = previous


# ---------------------------------------------------------------------------
# Absorbers: the previously scattered counted structs -> one namespace
# ---------------------------------------------------------------------------

def record_stream_stats(stats, *, registry: CounterRegistry | None = None
                        ) -> None:
    """Absorb an oocore ``StreamStats`` into the registry.

    Duck-typed on the stat fields so this module never imports the
    executor. The counted-byte ordering contract
    (``scheduled >= distinct >= pipelined``) survives the round-trip by
    construction — each field maps to exactly one counter —
    which ``tests/test_obs.py`` property-checks.
    """
    reg = _REGISTRY if registry is None else registry
    reg.add("oocore.mode_steps", 1, backend=stats.backend)
    reg.add("oocore.chunks", stats.chunks)
    reg.add("oocore.dma.scheduled_bytes", stats.scheduled_tile_bytes)
    reg.add("oocore.dma.distinct_bytes", stats.distinct_tile_bytes)
    reg.add("oocore.dma.pipelined_bytes", stats.pipelined_tile_bytes)
    reg.add("oocore.dma.index_stream_bytes", stats.index_stream_bytes)
    # Locality-reordered runs (repro.reorder) additionally record the
    # before/after tile traffic under the reorder.dma.* names, labeled
    # with the policy — presort is the counted cost the same stream
    # would have paid unsorted, postsort duplicates the oocore.dma.*
    # bytes so one namespace tells the whole before/after story.
    if getattr(stats, "ordering", "none") != "none":
        o = stats.ordering
        reg.add("reorder.dma.presort_scheduled_bytes",
                stats.presort_scheduled_tile_bytes, ordering=o)
        reg.add("reorder.dma.presort_distinct_bytes",
                stats.presort_distinct_tile_bytes, ordering=o)
        reg.add("reorder.dma.postsort_scheduled_bytes",
                stats.scheduled_tile_bytes, ordering=o)
        reg.add("reorder.dma.postsort_distinct_bytes",
                stats.distinct_tile_bytes, ordering=o)


def record_remap_exchange(caps, num_workers: int, nmodes: int, *,
                          uniform_cap: bool = False,
                          registry: CounterRegistry | None = None) -> None:
    """Absorb a runtime's per-transition all_to_all sizing.

    ``caps`` is ``remap_capacities(ft)`` — entry ``n`` bounds the mode
    ``n -> n+1`` exchange. Bytes per transition are the allocated
    payload ``D * D * cap * (4 * nmodes + 4)`` (coords + value), the
    same arithmetic ``benchmarks.common.exchange_sizing`` reports;
    recording it at ``prepare_runtime`` time means every driver that
    builds a runtime — CP-ALS, benches, the serving path — counts its
    collective allocation without bench-side re-derivation.
    """
    reg = _REGISTRY if registry is None else registry
    caps = [int(c) for c in caps]
    elem_bytes = 4 * nmodes + 4
    per_pair = num_workers * num_workers * elem_bytes
    cap_used = [max(caps)] * len(caps) if uniform_cap else caps
    for n, cap in enumerate(cap_used):
        reg.add("remap.a2a.bytes", cap * per_pair, transition=n)
    reg.add("remap.a2a.uniform_bytes", len(caps) * max(caps) * per_pair)
    reg.add("remap.transitions", len(caps))
