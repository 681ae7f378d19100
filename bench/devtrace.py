"""Profiler capture, and the reduction from an ``.xplane.pb`` to numbers.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
In it each TPU is a plane named ``/device:TPU:<id>``; its ``XLA Ops`` line
holds one event per HLO operation that ran, with a start and a duration
in nanoseconds on the same clock as the host planes. The host plane
``/host:CPU`` holds the Python thread's events, among them the
benchmark's own ``TraceAnnotation`` spans (``WINDOW_SPAN`` around the
measured window).

Everything here reads the trace only; which operations belong to which
layer is decided by the predicates below, so that each per-layer metric
reads its operations the same way in every run.
"""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import os
import re

WINDOW_SPAN = "bench.window"
SWEEP_SPAN = "bench.sweep"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"


@dataclasses.dataclass(frozen=True)
class Op:
    """One device operation: its HLO instruction, start and duration in ns,
    and the ``long_name`` statistic where the trace gives one."""

    name: str
    start: float
    dur: float
    long_name: str = ""

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class Trace:
    """What the reduction reads: the window and the operations in it."""

    window: tuple[float, float]          # ns, host clock of the trace
    devices: dict[int, list[Op]]         # device id -> ops inside the window
    host: list[tuple[str, float, float]]  # (name, start, dur) host events

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


@contextlib.contextmanager
def capture(log_dir: str):
    """Trace everything inside the block into ``log_dir``."""
    import jax

    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _stats(obj) -> dict:
    try:
        return {k: v for k, v in obj.stats}
    except (TypeError, ValueError):
        return {}


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` and clip every device op to the window."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    devices: dict[int, list[Op]] = {}
    host: list[tuple[str, float, float]] = []
    window = None
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops = devices.setdefault(int(m.group(1)), [])
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    ops.append(Op(ev.name, float(ev.start_ns),
                                  float(ev.duration_ns),
                                  str(_stats(ev).get("long_name", ""))))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    item = (ev.name, float(ev.start_ns), float(ev.duration_ns))
                    host.append(item)
                    if ev.name == WINDOW_SPAN:
                        window = (item[1], item[1] + item[2])
    if window is None:
        raise ValueError(f"no {WINDOW_SPAN!r} span in {path}")
    lo, hi = window
    for dev, ops in devices.items():
        devices[dev] = sorted(
            (dataclasses.replace(o, start=max(o.start, lo),
                                 dur=min(o.end, hi) - max(o.start, lo))
             for o in ops if o.end > lo and o.start < hi),
            key=lambda o: o.start)
    return Trace(window, devices, host)


def merged(ops) -> list[tuple[float, float]]:
    """Union of the ops' intervals, as sorted disjoint (start, end) pairs."""
    out: list[list[float]] = []
    for o in sorted(ops, key=lambda o: o.start):
        if out and o.start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], o.end)
        else:
            out.append([o.start, o.end])
    return [(a, b) for a, b in out]


def busy_s(trace: Trace) -> float:
    """Seconds in which some operation ran, averaged over the devices."""
    if not trace.devices:
        return 0.0
    total = sum(b - a for ops in trace.devices.values()
                for a, b in merged(ops))
    return total * 1e-9 / len(trace.devices)


def gaps(trace: Trace, device: int) -> list[tuple[float, float]]:
    """Idle intervals of one device inside the window."""
    lo, hi = trace.window
    out, t = [], lo
    for a, b in merged(trace.devices.get(device, [])):
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def host_activity(trace: Trace, t: float) -> str:
    """The innermost host event that spans time ``t``, or ``"none"``."""
    best = None
    for name, start, dur in trace.host:
        if start <= t <= start + dur and (best is None or dur < best[1]):
            best = (name, dur)
    return best[0] if best else "none"


# -- which operations belong to which layer ---------------------------------

def opcode(op: Op) -> str:
    """The HLO opcode of an op, from its trace name.

    A TPU trace names an op by its whole HLO instruction,
    ``%sort.12 = (s32[8]{0}, f32[8]{0}) sort(...), dimensions={0}``: the
    opcode is the word after the result type, which may hold spaces
    inside its brackets. A bare name such as ``sort.12`` gives ``sort``.
    """
    _, sep, rest = op.name.partition(" = ")
    if not sep:
        return op.name.lstrip("%").split(".")[0]
    depth = 0
    for i, ch in enumerate(rest):
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        elif ch == " " and depth == 0:
            return rest[i + 1:].split("(", 1)[0]
    return rest.split("(", 1)[0]


def is_pallas(op: Op) -> bool:
    """A Pallas (Mosaic) kernel: a custom call whose target is
    ``tpu_custom_call``. XLA's own custom calls (``Cholesky``,
    ``AssumeGatherIndicesInBound``, ...) are not Pallas kernels."""
    return "tpu_custom_call" in op.name + " " + op.long_name


def is_sort(op: Op) -> bool:
    return opcode(op) == "sort"


def is_collective(op: Op) -> bool:
    """An all-to-all or all-gather, synchronous or either half of an
    asynchronous pair."""
    return opcode(op).startswith(("all-to-all", "all-gather"))


def is_loop(op: Op) -> bool:
    """An XLA while loop. Its event spans its body's ops, which the trace
    lists on the same line."""
    return opcode(op) == "while"


def per_device_seconds(trace: Trace, pred) -> dict[int, float]:
    """Seconds in which an op matching ``pred`` ran, per device: the union
    of their intervals, so an op nested in another is counted once."""
    return {dev: sum(b - a for a, b in merged(o for o in ops if pred(o)))
            * 1e-9 for dev, ops in trace.devices.items()}


def breakdown(trace: Trace, top: int = 10) -> dict:
    """The device ops that took most time (seconds per device, averaged)
    and the longest idle gaps by what the host was doing."""
    per_name: dict[str, float] = {}
    for ops in trace.devices.values():
        for o in ops:
            per_name[o.name] = per_name.get(o.name, 0.0) + o.dur
    n_dev = max(1, len(trace.devices))
    device_ops = sorted(([k, v * 1e-9 / n_dev] for k, v in per_name.items()),
                        key=lambda kv: -kv[1])[:top]
    idle = []
    for dev in sorted(trace.devices):
        for a, b in gaps(trace, dev):
            idle.append([f"{host_activity(trace, (a + b) / 2)} (TPU {dev})",
                         (b - a) * 1e-9])
    idle.sort(key=lambda kv: -kv[1])
    return {"device_ops": device_ops, "idle_gaps": idle[:top]}
