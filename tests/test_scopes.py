"""Phase names inside the production sweep, and their clock.

* ``make_als_sweep`` names every phase with a ``jax.named_scope``
  (``repro.obs.scopes``); the names must reach the compiled program's
  ``op_name`` metadata, where a device trace is joined to them.
* An enabled tracer's spans land, by name, on the host plane of a
  ``jax.profiler`` trace, on the device ops' clock.
* ``build_flycoo`` and ``prepare_runtime`` count their own wall time once
  per call.
"""
import glob
import os
import re
import subprocess
import sys

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.core import distributed as dist
from repro.core.cpals import make_als_sweep
from repro.core.flycoo import build_flycoo
from repro.core.tensors import random_sparse_tensor
from repro.obs import counters as obs_counters
from repro.obs import scopes
from repro.obs.tracer import NULL, Tracer, use_tracer

RANK = 16
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _compiled_op_names(shape, nnz=1500, backend="auto"):
    """``op_name``s of the sweep compiled as the benchmark compiles it:
    ahead of time, on one device, ``backend="auto"`` by default."""
    t = random_sparse_tensor(shape, nnz, seed=3)
    ft = build_flycoo(t, num_workers=1)
    rt, stream = dist.prepare_runtime(ft, RANK)
    mesh = Mesh(np.array(jax.devices()[:1]), (dist.AXIS,))
    factors = [np.zeros((p, RANK), np.float32) for p in rt.i_pad]
    compiled = make_als_sweep(rt, mesh, backend=backend).lower(
        *stream, np.ones(1, np.float32), *factors,
        np.ones(RANK, np.float32), np.bool_(True)).compile()
    return set(_OP_NAME.findall(compiled.as_text()))


@pytest.mark.parametrize("shape", [(30, 20, 40), (12, 9, 15, 7)],
                         ids=["3-mode", "4-mode"])
def test_sweep_phases_reach_compiled_metadata(shape):
    names = _compiled_op_names(shape)
    for n in range(len(shape)):
        mode = scopes.mode_scope(n)
        layout = re.compile(rf"(^|/){mode}/{scopes.MTTKRP}/(.*/)?"
                            rf"{scopes.LAYOUT}/")
        assert any(layout.search(s) for s in names), mode
        for phase in (scopes.SOLVE, scopes.REMAP):
            assert any(f"{mode}/{phase}/" in s for s in names), (mode, phase)
    assert any(re.search(rf"(^|/){scopes.FIT}/", s) for s in names)
    # No mode scope past the tensor's order.
    assert not any(scopes.mode_scope(len(shape)) + "/" in s for s in names)


@pytest.mark.parametrize("backend,scoped", [("pallas_fused", True),
                                            ("auto", False)])
def test_row_gather_scope_only_on_row_gathering_rungs(backend, scoped):
    """The row-gathering rung's operand build runs under ``row_gather``,
    inside its mode's ``mttkrp``; the resident gather rung, which ``auto``
    picks at these widths, has none."""
    shape = (12, 9, 15, 7)
    names = _compiled_op_names(shape, backend=backend)
    for n in range(len(shape)):
        row_gather = re.compile(rf"(^|/){scopes.mode_scope(n)}/"
                                rf"{scopes.MTTKRP}/(.*/)?"
                                rf"{scopes.ROW_GATHER}/")
        assert any(row_gather.search(s) for s in names) == scoped, n


def test_tracer_span_lands_on_profiler_host_plane(tmp_path):
    tracer = Tracer()
    with jax.profiler.trace(str(tmp_path)):
        with tracer.span("obs.bridge_probe"):
            jax.block_until_ready(jax.numpy.arange(8) + 1)
    (path,) = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                        recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    host = [p for p in data.planes if p.name == "/host:CPU"]
    assert host
    names = {ev.name for p in host for line in p.lines for ev in line.events}
    assert "obs.bridge_probe" in names
    assert tracer.records[0].name == "obs.bridge_probe"


def test_tracer_without_jax_stays_jax_free():
    code = ("import sys\n"
            "from repro.obs.tracer import Tracer\n"
            "t = Tracer()\n"
            "with t.span('a'):\n"
            "    pass\n"
            "assert 'jax' not in sys.modules, 'tracer imported jax'\n"
            "assert [r.name for r in t.records] == ['a']\n")
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "src")]
        + os.environ.get("PYTHONPATH", "").split(os.pathsep)))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr


def test_null_tracer_opens_no_annotation(monkeypatch):
    def refuse(*a, **k):
        raise AssertionError("NULL tracer touched the profiler")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    with NULL.span("quiet"):
        pass


class _Recording(obs_counters.CounterRegistry):
    """A registry that also lists every emit, to count them."""

    def __init__(self):
        super().__init__()
        self.emits = []

    def add(self, name, value=1, **labels):
        self.emits.append(name)
        super().add(name, value, **labels)


def test_preprocessing_counters_once_per_call():
    t = random_sparse_tensor((30, 20, 40), 800, seed=1)
    tracer = Tracer()
    with obs_counters.use_registry(_Recording()) as reg, \
            use_tracer(tracer):
        for calls in (1, 2):
            ft = build_flycoo(t, num_workers=1)
            dist.prepare_runtime(ft, RANK)
            assert reg.emits.count("flycoo.build_s") == calls
            assert reg.emits.count("runtime.prepare_s") == calls
    assert reg.get("flycoo.build_s") > 0
    assert reg.get("runtime.prepare_s") > 0
    spans = [r.name for r in tracer.records]
    assert spans.count("flycoo.build") == 2
    assert spans.count("runtime.prepare") == 2
