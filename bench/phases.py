"""Device time per phase of the sweep, from the program's named scopes.

The program names the phases of a sweep with ``jax.named_scope``:
``als_mode<n>/mttkrp`` (with ``layout`` inside it), ``als_mode<n>/solve``,
``als_mode<n>/remap``, then ``fit``. The names reach the compiled HLO's
``metadata={op_name="..."}``. A TPU trace names each op by its HLO
instruction, so the compiled program's text joins a trace op to its
scope. (The trace's own ``tf_op`` statistic is no substitute: it leaves
out the ``while`` ops and names copies after their neighbours.)

The harness compiles the sweep ahead of time and holds the
``jax.stages.Compiled`` while the per-layer readers run;
:func:`sweep_op_names` reads the op names from it. Where the program has
no scopes (before they were added), every op is ``unscoped`` and the
readers find nothing.

    python3 bench/phases.py <trace.xplane.pb> <compiled.hlo.txt> <sweeps>

prints the summary of a kept trace.
"""
from __future__ import annotations

import gc
import json
import re
import sys

import devtrace

PHASES = ("mttkrp", "solve", "remap", "fit")   # disjoint, cover a sweep
LAYOUT = "layout"                               # inside mttkrp
UNSCOPED = "unscoped"
HLO_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%(\S+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_MODE_SCOPE = re.compile(r"^als_mode\d+$")


def op_names(hlo_text: str) -> dict[str, str]:
    """``{instruction: op_name}`` for every instruction of every
    computation in an HLO module's text (``compiled.as_text()``) that
    carries an ``op_name``."""
    out = {}
    for line in hlo_text.splitlines():
        m = HLO_INSTRUCTION.match(line)
        if m:
            n = _OP_NAME.search(line)
            if n:
                out[m.group(1)] = n.group(1)
    return out


def sweep_op_names() -> dict[str, str]:
    """The op names of every compiled program alive in this process: in
    the harness, the sweep it traced."""
    import jax

    out = {}
    for obj in gc.get_objects():
        if isinstance(obj, jax.stages.Compiled):
            out.update(op_names(obj.as_text()))
    return out


def instruction(op: devtrace.Op) -> str:
    """The HLO instruction an op ran: ``%while.279 = ...`` gives
    ``while.279``."""
    return op.name.partition(" = ")[0].lstrip("%")


def phase_path(op_name: str) -> tuple[str, ...]:
    """The phase scopes of an ``op_name``, from the first ``;``-joined
    part that names one: ``jit(inner)/als_mode0/mttkrp/jit(f)/layout/sort``
    gives ``("als_mode0", "mttkrp", "jit(f)", "layout", "sort")``. Empty
    where no part opens with a mode scope or ``fit``."""
    for part in op_name.split(";"):
        parts = part.split("/")
        for i, p in enumerate(parts):
            if _MODE_SCOPE.match(p) or p == "fit":
                return tuple(parts[i:])
    return ()


def op_phases(trace: devtrace.Trace, names: dict[str, str]) -> dict:
    """Per device, ``(op, path)`` for every op. An op that runs inside
    another (in time: a ``while`` or ``conditional`` and its body) takes
    the phase of the outermost op around it that has one. XLA shares one
    body computation among identical loops (a sweep's stream-sized
    ``searchsorted`` loops, in the layout build and in the remap), so a
    body op's own ``op_name`` names one of its callers only; the loop op
    itself, an instruction of the entry computation, names the right one.
    An op with no such container keeps its own phase; with none it has
    the empty path."""
    out = {}
    for dev, ops in trace.devices.items():
        rows, stack = [], []          # stack: (end, path) of open ops
        for o in sorted(ops, key=lambda o: (o.start, -o.dur)):
            while stack and stack[-1][0] < o.end:
                stack.pop()
            path = stack[-1][1] if stack and stack[-1][1] else \
                phase_path(names.get(instruction(o), ""))
            rows.append((o, path))
            stack.append((o.end, path))
        out[dev] = rows
    return out


def _seconds(phases: dict, hit) -> dict[int, float]:
    """Seconds per device in which an op whose path satisfies ``hit`` ran
    (the union of their intervals)."""
    return {dev: sum(b - a for a, b in
                     devtrace.merged(o for o, p in rows if hit(p))) * 1e-9
            for dev, rows in phases.items()}


def phase_seconds(trace: devtrace.Trace, names: dict[str, str],
                  scope: str) -> dict[int, float]:
    """Seconds per device in which an op whose phase path holds ``scope``
    ran. ``scope`` ``"unscoped"`` takes the ops with no phase."""
    phases = op_phases(trace, names)
    if scope == UNSCOPED:
        return _seconds(phases, lambda p: not p)
    return _seconds(phases, lambda p: scope in p)


def slowest_per_sweep(ctx, scope: str):
    """A reader's number: ``scope``'s s/sweep on the chip that spent most,
    or None where no op of the trace is in it."""
    per = phase_seconds(ctx.trace, sweep_op_names(), scope)
    worst = max(per.values(), default=0.0)
    return worst / ctx.sweeps if worst > 0 else None


def program_counter(ctx, name: str):
    """A counter of the program's ``repro.obs`` registry after the window,
    or None where the program has none or the trace holds no device
    operation (a rehearsal off the chip)."""
    from repro.obs import counters

    if not any(ctx.trace.devices.values()):
        return None
    return counters.get_registry().snapshot().get(name)


def summary(trace: devtrace.Trace, names: dict[str, str], sweeps: int,
            top: int = 5) -> dict:
    """Seconds per sweep of each phase, of ``layout``, of the ops with no
    phase and of each phase per mode (mean over devices), and the ``top``
    unscoped ops by time."""
    phases = op_phases(trace, names)
    n_dev = max(1, len(phases))

    def per_sweep(hit):
        return sum(_seconds(phases, hit).values()) / n_dev / sweeps

    out = {s: per_sweep(lambda p, s=s: s in p) for s in PHASES + (LAYOUT,)}
    out[UNSCOPED] = per_sweep(lambda p: not p)
    modes = sorted({p[0] for rows in phases.values() for _, p in rows
                    if p and _MODE_SCOPE.match(p[0])},
                   key=lambda m: int(m[len("als_mode"):]))
    out["modes"] = {m: {s: per_sweep(lambda p, m=m, s=s: p[:1] == (m,)
                                     and s in p)
                        for s in ("mttkrp", LAYOUT, "solve", "remap")}
                    for m in modes}
    loose: dict[str, float] = {}
    for rows in phases.values():
        for o, p in rows:
            if not p:
                loose[o.name] = loose.get(o.name, 0.0) + o.dur
    out["top_unscoped"] = sorted(
        ([k[:120], v * 1e-9 / n_dev / sweeps] for k, v in loose.items()),
        key=lambda kv: -kv[1])[:top]
    return out


if __name__ == "__main__":
    trace_path, hlo_path, sweeps = sys.argv[1:4]
    with open(hlo_path) as f:
        names = op_names(f.read())
    print("phases: " + json.dumps(summary(devtrace.load(trace_path), names,
                                          int(sweeps))))
