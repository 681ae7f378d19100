"""Device time per phase from the program's named scopes (``phases``),
and the readers built on it."""
import gzip
import json
import os
import shutil

import jax
import jax.numpy as jnp
import pytest

import devtrace
import phases
from conftest import ROOT
from devtrace import Op, Trace



HLO = '''HloModule jit_inner, is_scheduled=true

%fused_computation.1 (param_0: s32[8]) -> s32[8] {
  %param_0 = s32[8]{0} parameter(0)
  ROOT %add.1 = s32[8]{0} add(%param_0, %param_0), metadata={op_name="jit(inner)/als_mode0/mttkrp/jit(build_block_layout)/layout/add" stack_frame_id=3}
}

%body.2 (p: (s32[], s32[8])) -> (s32[], s32[8]) {
  %gather.3 = s32[8]{0} gather(%x, %y), metadata={op_name="while/body/gather"}
  %fusion.10 = s32[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(inner)/als_mode2/remap/jit(searchsorted)/while/body/lt"}
}

ENTRY %main.9 (idx.1: s32[8]) -> s32[8] {
  %idx.1 = s32[8]{0} parameter(0), metadata={op_name="idx"}
  %while.4 = (s32[], s32[8]) while(%t), condition=%cond.5, body=%body.2, metadata={op_name="jit(inner)/als_mode0/mttkrp/jit(mttkrp_device_step)/jit(build_block_layout)/layout/jit(searchsorted)/while"}
  %fusion.6 = s32[8]{0} fusion(%idx.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(inner)/als_mode0/solve/mul"}
  %shard_map.1 = f32[] multiply(%a, %b), metadata={op_name="jit(inner)/fit/mul;jit(inner)/shard_map"}
  %copy-start.7 = s32[8]{0} copy-start(%idx.1)
  ROOT %all-to-all.8 = s32[8]{0} all-to-all(%fusion.6), metadata={op_name="jit(inner)/als_mode1/remap/all_to_all" source_file="/x/remap.py" source_line=3}
}
'''


def test_op_names_parses_every_computation():
    names = phases.op_names(HLO)
    assert names == {
        "add.1": "jit(inner)/als_mode0/mttkrp/jit(build_block_layout)/"
                 "layout/add",
        "gather.3": "while/body/gather",
        "fusion.10": "jit(inner)/als_mode2/remap/jit(searchsorted)/while/"
                     "body/lt",
        "idx.1": "idx",
        "while.4": "jit(inner)/als_mode0/mttkrp/jit(mttkrp_device_step)/"
                   "jit(build_block_layout)/layout/jit(searchsorted)/while",
        "fusion.6": "jit(inner)/als_mode0/solve/mul",
        "shard_map.1": "jit(inner)/fit/mul;jit(inner)/shard_map",
        "all-to-all.8": "jit(inner)/als_mode1/remap/all_to_all"}


@pytest.mark.parametrize("op_name,path", [
    ("jit(inner)/als_mode2/remap/sort", ("als_mode2", "remap", "sort")),
    ("jit(inner)/fit/mul;jit(inner)/shard_map", ("fit", "mul")),
    # the first part that names a phase wins
    ("jit(inner)/shard_map;jit(inner)/als_mode1/solve/dot",
     ("als_mode1", "solve", "dot")),
    ("while/body/gather", ()), ("", ()), ("jit(inner)/fitness/x", ())])
def test_phase_path(op_name, path):
    assert phases.phase_path(op_name) == path


def _phase_trace():
    # Device 0: a layout while loop 0..50 with its body ops inside it:
    # two with no phase of their own, one whose op_name names another
    # caller of the shared body (a remap); then a solve fusion 50..60, a
    # copy with no op_name 60..65, the fit 65..70. Device 1: a remap
    # all-to-all 0..30.
    def op(name, start, dur):
        return Op(f"%{name} = s32[8]{{0}} x()", start, dur)
    dev0 = [op("while.4", 0, 50), op("gather.3", 5, 10),
            op("gather.3", 20, 10), op("fusion.10", 35, 10),
            op("fusion.6", 50, 10),
            op("copy-start.7", 60, 5), op("shard_map.1", 65, 5)]
    dev1 = [op("all-to-all.8", 0, 30)]
    return Trace((0.0, 100.0), {0: dev0, 1: dev1},
                 [("bench.window", 0, 100)])


def test_body_ops_inherit_their_while_phase():
    rows = phases.op_phases(_phase_trace(), phases.op_names(HLO))[0]
    paths = {(phases.instruction(o), o.start): p for o, p in rows}
    loop = paths[("while.4", 0)]
    assert phases.LAYOUT in loop
    assert paths[("gather.3", 5)] == loop == paths[("gather.3", 20)]
    # A shared body's own op_name names another caller: the loop wins.
    assert paths[("fusion.10", 35)] == loop
    assert paths[("copy-start.7", 60)] == ()


@pytest.mark.parametrize("scope,want", [
    ("mttkrp", {0: 50e-9, 1: 0.0}), ("layout", {0: 50e-9, 1: 0.0}),
    ("solve", {0: 10e-9, 1: 0.0}), ("remap", {0: 0.0, 1: 30e-9}),
    ("fit", {0: 5e-9, 1: 0.0}), ("unscoped", {0: 5e-9, 1: 0.0}),
    ("als_mode0", {0: 60e-9, 1: 0.0}), ("als_mode1", {0: 0.0, 1: 30e-9})])
def test_phase_seconds(scope, want):
    got = phases.phase_seconds(_phase_trace(), phases.op_names(HLO),
                                 scope)
    assert got == pytest.approx(want)


def test_phase_summary_adds_up_to_busy():
    t = _phase_trace()
    s = phases.summary(t, phases.op_names(HLO), sweeps=1)
    total = sum(s[p] for p in phases.PHASES + (phases.UNSCOPED,))
    assert total == pytest.approx(devtrace.busy_s(t))
    assert s["modes"]["als_mode0"]["layout"] == pytest.approx(25e-9)
    assert s["top_unscoped"][0][1] == pytest.approx(2.5e-9)


@pytest.fixture(scope="module")
def scoped(tmp_path_factory):
    """A trace recorded on a TPU v5e of the program with its named scopes:
    one sweep of the tiny cell (``conftest.TINY_CONFIG``), traced by the
    harness with ``--trace 1``, and the op names of the compiled sweep's
    ``as_text()`` that the harness joined it with."""
    data = os.path.join(os.path.dirname(__file__), "data")
    path = tmp_path_factory.mktemp("scoped") / "tiny.xplane.pb"
    with gzip.open(os.path.join(data, "tiny_v5e_scoped.xplane.pb.gz")) as f, \
            open(path, "wb") as out:
        shutil.copyfileobj(f, out)
    with gzip.open(os.path.join(data, "tiny_v5e_scoped.hlo.txt.gz"),
                   "rt") as f:
        text = f.read()
    return devtrace.load(str(path)), text


def test_op_names_parses_recorded_hlo(scoped):
    trace, text = scoped
    names = phases.op_names(text)
    # Every computation, bodies included, and ';'-joined names.
    assert len(names) > 0.5 * text.count(" = ")
    assert any(";" in n for n in names.values())
    kernels = [o for o in trace.devices[0] if devtrace.is_pallas(o)]
    assert kernels
    for o in kernels:
        path = phases.phase_path(names[phases.instruction(o)])
        assert path[0].startswith("als_mode") and path[1] == "mttkrp"


def test_recorded_phases_add_up_to_busy(scoped):
    trace, text = scoped
    names = phases.op_names(text)
    s = phases.summary(trace, names, sweeps=1)
    busy = devtrace.busy_s(trace)
    total = sum(s[p] for p in phases.PHASES + (phases.UNSCOPED,))
    assert total == pytest.approx(busy, rel=0.01)
    assert s[phases.UNSCOPED] <= 0.02 * busy
    assert 0 < s[phases.LAYOUT] <= s["mttkrp"]
    assert set(s["modes"]) == {"als_mode0", "als_mode1", "als_mode2"}
    for rows in phases.op_phases(trace, names).values():
        for _, p in rows:
            if phases.LAYOUT in p:
                assert "mttkrp" in p


def test_recorded_loop_bodies_take_their_loop_phase(scoped):
    """The stream-sized searchsorted loops of the layout build and of the
    remap share one body computation; each loop's ops count for it."""
    trace, text = scoped
    names = phases.op_names(text)
    rows = phases.op_phases(trace, names)[0]
    loops = [(o, p) for o, p in rows if devtrace.is_loop(o)]
    assert {p[1] for _, p in loops} >= {"mttkrp", "remap"}
    for loop, path in loops:
        inside = [p for o, p in rows
                  if loop.start <= o.start and o.end <= loop.end]
        assert all(p == path for p in inside)


SCOPE_READERS = ("layout_s", "mttkrp_s", "solve_s", "remap_s")
COUNTER_READERS = ("flycoo_build_s", "runtime_prepare_s")


def _context(trace):
    import harness

    return harness.Context(trace, 1, 1e-7, 4000, (61, 47, 83), 16, 2,
                           "TPU v5 lite")


@pytest.fixture
def program(monkeypatch):
    """The op names and the counters a program with the scopes gives."""
    from repro.obs import counters

    monkeypatch.setattr(phases, "sweep_op_names",
                        lambda: phases.op_names(HLO))
    with counters.use_registry() as reg:
        reg.add("flycoo.build_s", 1.5)
        reg.add("runtime.prepare_s", 0.25)
        yield


def test_new_readers_read_scopes_and_counters(program):
    import harness

    ctx = _context(_phase_trace())
    got = {name: harness.load_reader(name)(ctx)
           for name in SCOPE_READERS + COUNTER_READERS}
    assert got == pytest.approx({
        "layout_s": 50e-9, "mttkrp_s": 50e-9, "solve_s": 10e-9,
        "remap_s": 30e-9, "flycoo_build_s": 1.5, "runtime_prepare_s": 0.25})


def test_new_readers_without_scopes_or_counters_are_an_error(monkeypatch,
                                                            capsys):
    """A program without the scopes or counters (the parent of the change
    that added them) gives nothing to read: each reader returns None and
    the harness says so, as for the opcode readers."""
    import harness
    from repro.obs import counters

    monkeypatch.setattr(phases, "sweep_op_names", lambda: {})
    ctx = _context(_phase_trace())
    spec = json.loads(open(os.path.join(ROOT, "BENCHMARK.json")).read())
    names = SCOPE_READERS + COUNTER_READERS
    cell = harness.Cell("x", 1, {}, {}, {}, [], [
        m for m in spec["per_layer"] if m["name"] in names])
    with counters.use_registry():
        for name in names:
            assert harness.load_reader(name)(ctx) is None, name
        assert harness.per_layer(cell, ctx) == {}
    err = capsys.readouterr().err
    for name in names:
        assert f"ERROR: per-layer metric {name} matched no operation" in err


def test_counter_readers_stay_silent_off_the_chip(program):
    import harness

    ctx = _context(Trace((0.0, 1.0), {}, []))
    for name in COUNTER_READERS:
        assert harness.load_reader(name)(ctx) is None


def test_sweep_op_names_finds_the_live_compiled_program():
    def f(x):
        with jax.named_scope("als_mode0"), jax.named_scope("solve"):
            return jnp.sin(x) * 2

    compiled = jax.jit(f).lower(jnp.ones(8)).compile()
    names = phases.sweep_op_names()
    assert any(phases.phase_path(n)[:2] == ("als_mode0", "solve")
               for n in names.values())
    del compiled


def test_readers_on_the_recorded_trace(scoped, monkeypatch):
    """The numbers the chip run printed for the recorded sweep."""
    import harness

    trace, text = scoped
    monkeypatch.setattr(phases, "sweep_op_names",
                        lambda: phases.op_names(text))
    ctx = _context(trace)
    got = {name: harness.load_reader(name)(ctx) for name in SCOPE_READERS}
    assert got == pytest.approx({
        "layout_s": 0.001156917, "mttkrp_s": 0.001537308,
        "solve_s": 3.3583e-05, "remap_s": 0.00156606})
