"""repro.obs.prof — timing harness, self-time attribution, roofline,
noise-aware timed gate (PR-10).

Coverage per the issue checklist:
  * robust stats: median/MAD arithmetic, modified-z outlier rejection
    (and its >=4-sample guard), deterministic fake-clock measurement;
  * self-time attribution: self times partition the wall clock exactly,
    top-down paths, bottom-up recursion guard under same-name nesting,
    collapsed-stack flamegraph format;
  * the same-name-nesting ``self_counters`` regression (the tracer fix
    this PR's roofline join relies on): aggregating self deltas by name
    never double-counts;
  * roofline join: ``planner.*`` bytes excluded, moved-bytes basis
    preference (pipelined+index_stream > model > sum), label folding,
    backend→rung defaulting, per-mode breakdown shares;
  * the timed gate — both directions, by arithmetic rather than luck:
    an injected 2x slowdown fails; seeded same-distribution jitter
    passes across many seeds; host-noise/fingerprint/sub-resolution/
    per-phase-noise all SKIP or soften instead of flaking;
  * ``run_profile`` with an injected fast collect, and every
    ``python -m repro.obs.prof`` CLI path (run/report/gate).
"""
import json
import random

import pytest

from repro.obs import counters as ocnt
from repro.obs import tracer as otr
from repro.obs.prof import gate as pgate
from repro.obs.prof import harness as ph
from repro.obs.prof import roofline as prf
from repro.obs.prof import selftime as pst
from repro.obs.prof import __main__ as prof_main


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _rec(sid, parent, name, t0, t1, *, args=None, counters=None,
         self_counters=None, depth=0):
    return otr.SpanRecord(sid=sid, parent=parent, depth=depth, name=name,
                          args=args or {}, t0=t0, t1=t1,
                          counters=counters or {},
                          self_counters=self_counters or {})


def _mk_prof(phases, *, noise=0.02, fingerprint=None):
    """A minimal schema-valid PROF artifact from {name: (median, mad_frac)}."""
    fp = fingerprint or ph.env_fingerprint()
    body = {}
    for name, (median, mad_frac) in phases.items():
        mad = mad_frac * median / ph.MAD_SIGMA
        body[name] = {"n": 3, "median_s": median, "mad_s": mad,
                      "mad_frac": mad_frac, "mean_s": median,
                      "min_s": median, "max_s": median, "rejected": 0,
                      "samples_s": [median] * 3}
    return {
        "meta": {"schema": pgate.PROF_SCHEMA, "fingerprint": fp,
                 "noise": {"mad_frac": noise}, "workload": {"tensor": "t"},
                 "repeats": 3, "warmup": 1},
        "phases": body,
        "selftime": {"top_down": [], "bottom_up": []},
        "roofline": [],
        "breakdown": [],
    }


# ---------------------------------------------------------------------------
# harness: robust stats + steady-state measurement
# ---------------------------------------------------------------------------

def test_robust_stats_median_mad():
    st = ph.robust_stats([1.0, 2.0, 3.0])
    assert st.median_s == 2.0
    assert st.mad_s == 1.0
    assert st.rejected == 0
    assert st.kept_s == (1.0, 2.0, 3.0)
    # even-length median is the midpoint
    assert ph.robust_stats([1.0, 2.0, 3.0, 4.0]).median_s == 2.5


def test_robust_stats_rejects_outlier_and_recomputes():
    samples = [1.0, 1.01, 0.99, 1.0, 1.02, 0.98, 100.0]
    st = ph.robust_stats(samples)
    assert st.rejected == 1
    assert 100.0 not in st.kept_s
    assert st.median_s == pytest.approx(1.0, abs=0.02)
    assert st.max_s < 2.0                       # summary is post-rejection
    assert st.samples_s == tuple(samples)       # raw samples preserved


def test_robust_stats_needs_four_samples_to_reject():
    # 3 samples: the 100.0 would dominate its own z-score; keep everything.
    st = ph.robust_stats([1.0, 1.0, 100.0])
    assert st.rejected == 0 and 100.0 in st.kept_s


def test_robust_stats_identical_samples_no_rejection():
    # MAD == 0 would divide by zero in the modified z-score; guard skips.
    st = ph.robust_stats([2.0] * 6)
    assert st.rejected == 0 and st.mad_s == 0.0 and st.mad_frac == 0.0


def test_robust_stats_empty_raises():
    with pytest.raises(ValueError):
        ph.robust_stats([])


def test_mad_frac_is_sigma_scaled():
    st = ph.robust_stats([1.0, 1.1, 0.9])
    assert st.mad_frac == pytest.approx(ph.MAD_SIGMA * st.mad_s / st.median_s)


def test_measure_steady_fake_clock_and_warmup():
    calls = []
    ticks = iter(range(1000))

    def clock():
        return float(next(ticks))

    def fn():
        calls.append(1)
        return len(calls)

    st = ph.measure_steady(fn, warmup=2, repeats=5, clock=clock, block=None)
    assert len(calls) == 7                      # 2 warmup + 5 timed
    assert len(st.samples_s) == 5
    # each sample brackets exactly one clock pair -> duration 1.0 tick
    assert st.median_s == 1.0 and st.mad_s == 0.0


def test_measure_steady_block_fences_every_call():
    fenced = []
    ph.measure_steady(lambda: "x", warmup=1, repeats=3,
                      block=lambda v: fenced.append(v))
    assert fenced == ["x"] * 4


def test_measure_steady_rejects_zero_repeats():
    with pytest.raises(ValueError):
        ph.measure_steady(lambda: None, repeats=0)


def test_fingerprint_strict_keys_only():
    fp = ph.env_fingerprint()
    assert ph.fingerprint_compatible(fp, dict(fp)) == []
    other = dict(fp)
    other["python"] = "0.0.0"                   # informational: ignored
    assert ph.fingerprint_compatible(fp, other) == []
    other["cpu_count"] = (fp.get("cpu_count") or 0) + 64
    mism = ph.fingerprint_compatible(fp, other)
    assert len(mism) == 1 and "cpu_count" in mism[0]


def test_noise_calibration_shape():
    noise = ph.noise_calibration(repeats=5, warmup=1)
    assert set(noise) >= {"workload", "median_s", "mad_frac", "samples_s"}
    assert noise["median_s"] > 0
    assert len(noise["samples_s"]) == 5


# ---------------------------------------------------------------------------
# self-time attribution
# ---------------------------------------------------------------------------

def _forest():
    # root[0,10] -> a[0,4] -> c[1,2]; root -> b[4,9]
    return [
        _rec(0, -1, "root", 0.0, 10.0),
        _rec(1, 0, "a", 0.0, 4.0, depth=1),
        _rec(2, 1, "c", 1.0, 2.0, depth=2),
        _rec(3, 0, "b", 4.0, 9.0, depth=1),
    ]


def test_self_times_partition_wall_clock():
    recs = _forest()
    selfs = pst.self_times_s(recs)
    assert selfs[0] == pytest.approx(1.0)       # 10 - (4 + 5)
    assert selfs[1] == pytest.approx(3.0)       # 4 - 1
    assert selfs[2] == pytest.approx(1.0)
    assert selfs[3] == pytest.approx(5.0)
    # the partition property: self times sum exactly to the root total
    assert sum(selfs.values()) == pytest.approx(10.0)


def test_self_time_clamped_at_zero():
    recs = [_rec(0, -1, "p", 0.0, 1.0),
            _rec(1, 0, "q", 0.0, 1.0 + 1e-12, depth=1)]
    assert pst.self_times_s(recs)[0] == 0.0


def test_topdown_paths_and_fractions():
    rows = pst.topdown_table(_forest())
    by_path = {r["path"]: r for r in rows}
    assert set(by_path) == {"root", "root;a", "root;a;c", "root;b"}
    assert by_path["root;b"]["self_s"] == pytest.approx(5.0)
    assert by_path["root;b"]["self_frac"] == pytest.approx(0.5)
    assert rows[0]["path"] == "root;b"          # sorted by self desc
    assert sum(r["self_frac"] for r in rows) == pytest.approx(1.0)


def test_bottomup_recursion_guard_same_name_nesting():
    # x[0,10] -> x[2,5]: inclusive total must count the outer span only.
    recs = [_rec(0, -1, "x", 0.0, 10.0),
            _rec(1, 0, "x", 2.0, 5.0, depth=1)]
    row = pst.bottomup_table(recs)[0]
    assert row["name"] == "x" and row["calls"] == 2
    assert row["total_s"] == pytest.approx(10.0)   # not 13
    assert row["self_s"] == pytest.approx(10.0)    # 7 outer + 3 inner


def test_flamegraph_collapsed_stack_format(tmp_path):
    lines = pst.flamegraph_lines(_forest())
    assert "root;a;c 1000000" in lines
    assert "root;b 5000000" in lines
    for ln in lines:
        path, _, val = ln.rpartition(" ")
        assert path and int(val) >= 0
    out = pst.write_flamegraph(_forest(), str(tmp_path / "f.folded"))
    text = open(out).read().strip().splitlines()
    assert sorted(text) == sorted(lines)
    # a second write without overwrite picks a fresh name
    out2 = pst.write_flamegraph(_forest(), str(tmp_path / "f.folded"))
    assert out2 != out


def test_span_paths_sanitize_names():
    recs = [_rec(0, -1, "bad name\nhere", 0.0, 1.0)]
    path = pst.span_paths(recs)[0]
    assert "\n" not in path
    assert path == otr.sanitize_span_name("bad name\nhere")


# ---------------------------------------------------------------------------
# self_counters under same-name nesting (the tracer regression this
# PR's roofline join depends on)
# ---------------------------------------------------------------------------

def test_self_counters_no_double_count_under_same_name_nesting():
    reg = ocnt.CounterRegistry()
    tracer = otr.Tracer()
    with ocnt.use_registry(reg):
        with tracer.span("oocore.mode_step"):
            reg.add("oocore.chunks", 5)
            with tracer.span("oocore.mode_step"):
                reg.add("oocore.chunks", 7)
            reg.add("oocore.chunks", 2)
    inner, outer = tracer.records          # inner closes first
    assert inner.name == outer.name == "oocore.mode_step"
    assert inner.self_counters == {"oocore.chunks": 7}
    assert outer.self_counters == {"oocore.chunks": 7}  # 5 + 2
    assert outer.counters == {"oocore.chunks": 14}      # inclusive
    # aggregate by name (what the roofline join does): no double count
    agg = {}
    for r in tracer.records:
        for k, v in r.self_counters.items():
            agg[k] = agg.get(k, 0) + v
    assert agg == {"oocore.chunks": 14}
    assert agg["oocore.chunks"] == reg.get("oocore.chunks")


# ---------------------------------------------------------------------------
# roofline join
# ---------------------------------------------------------------------------

def test_roofline_prefers_pipelined_plus_index_stream():
    recs = [_rec(0, -1, "oocore.mode_step", 0.0, 1.0,
                 args={"backend": "pallas_fused_gather_stream",
                       "rung": "stream", "ordering": "tile"},
                 self_counters={
                     "oocore.dma.pipelined_bytes": 1000,
                     "oocore.dma.index_stream_bytes": 24,
                     "oocore.dma.scheduled_bytes": 5000,
                     "oocore.dma.distinct_bytes": 800,
                 })]
    (row,) = prf.bandwidth_rows(recs)
    assert row["basis"] == "pipelined+index_stream"
    assert row["moved_bytes"] == 1024
    assert row["achieved_gbps"] == pytest.approx(1024 / 1e9)
    assert row["rung"] == "stream" and row["ordering"] == "tile"
    # the scheduled/distinct spread stays visible per counter
    assert row["per_counter_gbps"]["oocore.dma.scheduled_bytes"] == \
        pytest.approx(5000 / 1e9)


def test_roofline_model_basis_and_rung_default():
    recs = [_rec(0, -1, "ops.device_step", 0.0, 2.0,
                 args={"backend": "pallas_fused"},
                 self_counters={"ops.step.model_bytes{backend=pallas_fused}":
                                4096})]
    (row,) = prf.bandwidth_rows(recs)
    assert row["basis"] == "model"
    assert row["moved_bytes"] == 4096
    assert row["rung"] == prf.RUNG_BY_BACKEND["pallas_fused"]  # defaulted
    assert row["achieved_gbps"] == pytest.approx(4096 / 2.0 / 1e9)


def test_roofline_sum_fallback_and_label_folding():
    recs = [_rec(0, -1, "remap", 0.0, 1.0,
                 self_counters={"remap.a2a.exchanged_bytes{transition=0}": 60,
                                "remap.a2a.exchanged_bytes{transition=1}": 40})]
    (row,) = prf.bandwidth_rows(recs)
    assert row["basis"] == "sum"
    assert row["moved_bytes"] == 100
    assert row["counted_bytes"] == {"remap.a2a.exchanged_bytes": 100}


def test_roofline_excludes_planner_plan_bytes():
    # plan_bytes sizes a VMEM plan, not traffic — must never fabricate
    # a bandwidth row (the bug the baseline regeneration caught).
    recs = [_rec(0, -1, "mttkrp", 0.0, 1.0,
                 self_counters={"planner.vmem.plan_bytes{rung=whole}":
                                504832})]
    assert prf.bandwidth_rows(recs) == []
    recs2 = [_rec(0, -1, "mttkrp", 0.0, 1.0,
                  self_counters={"planner.vmem.plan_bytes": 504832,
                                 "ops.step.model_bytes": 100})]
    (row,) = prf.bandwidth_rows(recs2)
    assert row["moved_bytes"] == 100
    assert "planner.vmem.plan_bytes" not in row["counted_bytes"]


def test_roofline_groups_and_skips_byteless_spans():
    recs = [
        _rec(0, -1, "step", 0.0, 1.0, args={"backend": "ref"},
             self_counters={"ops.step.model_bytes": 100}),
        _rec(1, -1, "step", 1.0, 3.0, args={"backend": "ref"},
             self_counters={"ops.step.model_bytes": 300}),
        _rec(2, -1, "step", 3.0, 4.0, args={"backend": "pallas_fused"},
             self_counters={"ops.step.model_bytes": 100}),
        _rec(3, -1, "solve", 4.0, 5.0),        # no bytes: no row
    ]
    rows = prf.bandwidth_rows(recs)
    assert len(rows) == 2
    ref = next(r for r in rows if r["backend"] == "ref")
    assert ref["calls"] == 2 and ref["moved_bytes"] == 400
    assert ref["time_s"] == pytest.approx(3.0)
    assert not any(r["span"] == "solve" for r in rows)


def test_mode_breakdown_shares_and_child_split():
    recs = [
        _rec(0, -1, "sweep", 0.0, 10.0),
        _rec(1, 0, "mode", 0.0, 6.0, args={"mode": 0}, depth=1),
        _rec(2, 1, "mttkrp", 0.0, 3.0, depth=2),
        _rec(3, 1, "solve", 3.0, 4.0, depth=2),
        _rec(4, 1, "remap", 4.0, 5.5, depth=2),
        _rec(5, 0, "mode", 6.0, 10.0, args={"mode": 1}, depth=1),
        _rec(6, 5, "mttkrp", 6.0, 8.0, depth=2),
    ]
    rows = prf.mode_breakdown(recs)
    assert [r["mode"] for r in rows] == [0, 1]
    m0, m1 = rows
    assert m0["total_s"] == pytest.approx(6.0)
    assert m0["mttkrp_s"] == pytest.approx(3.0)
    assert m0["solve_s"] == pytest.approx(1.0)
    assert m0["remap_s"] == pytest.approx(1.5)
    assert m0["other_s"] == pytest.approx(0.5)
    assert m1["mttkrp_s"] == pytest.approx(2.0)
    assert m0["share_frac"] + m1["share_frac"] == pytest.approx(1.0)


# ---------------------------------------------------------------------------
# PROF schema validation
# ---------------------------------------------------------------------------

def test_validate_prof_accepts_synthetic_artifact():
    assert pgate.validate_prof(_mk_prof({"mttkrp": (1.0, 0.02)})) == []


@pytest.mark.parametrize("mutate,needle", [
    (lambda p: p.pop("meta"), "meta"),
    (lambda p: p["meta"].update(schema=99), "schema"),
    (lambda p: p["meta"].pop("noise"), "noise"),
    (lambda p: p["meta"]["noise"].pop("mad_frac"), "mad_frac"),
    (lambda p: p.update(phases={}), "phases"),
    (lambda p: p["phases"]["mttkrp"].pop("median_s"), "median_s"),
    (lambda p: p["phases"]["mttkrp"].update(samples_s=[]), "samples_s"),
    (lambda p: p.pop("selftime"), "selftime"),
    (lambda p: p.pop("roofline"), "roofline"),
    (lambda p: p.pop("breakdown"), "breakdown"),
])
def test_validate_prof_catches_each_break(mutate, needle):
    prof = _mk_prof({"mttkrp": (1.0, 0.02)})
    mutate(prof)
    errors = pgate.validate_prof(prof)
    assert errors and any(needle in e for e in errors)


def test_validate_prof_non_dict():
    assert pgate.validate_prof([1, 2]) == ["PROF artifact is not a dict"]


# ---------------------------------------------------------------------------
# the timed gate: both directions, by arithmetic not luck
# ---------------------------------------------------------------------------

def test_gate_catches_injected_2x_slowdown():
    base = _mk_prof({"mttkrp": (1.0, 0.02), "solve": (0.5, 0.02)})
    cur = _mk_prof({"mttkrp": (2.0, 0.02), "solve": (0.5, 0.02)})
    result = pgate.compare(cur, base)
    assert result.status == "fail" and result.exit_status == 1
    verdicts = {r["phase"]: r["verdict"] for r in result.phases}
    assert verdicts == {"mttkrp": "regressed", "solve": "ok"}
    # 2.0 > 1.5 + 3.0 * 0.04 = 1.62: the failure is threshold arithmetic
    row = next(r for r in result.phases if r["phase"] == "mttkrp")
    assert row["ratio"] == pytest.approx(2.0)
    assert row["threshold"] == pytest.approx(
        pgate.MAX_RATIO + pgate.TOLERANCE_Z * 0.04)
    assert any("re-baseline" in m for m in result.messages)


def test_gate_passes_on_seeded_same_distribution_jitter():
    # Noise tolerance proven by construction: for *any* seed, samples
    # drawn within ±8% of the same median stay under the noise-scaled
    # threshold, because the worst-case ratio 1.08/0.92 ≈ 1.17 < 1.5.
    for seed in range(20):
        rng = random.Random(seed)

        def draw():
            return ph.robust_stats(
                [1.0 * (1 + rng.uniform(-0.08, 0.08)) for _ in range(5)]
            ).to_json()

        base = _mk_prof({})
        cur = _mk_prof({})
        for prof in (base, cur):
            prof["phases"] = {"mttkrp": draw(), "sweep": draw()}
        result = pgate.compare(cur, base)
        assert result.status == "pass", (seed, result.messages)
        for row in result.phases:
            assert row["ratio"] < row["threshold"]
            assert row["threshold"] >= pgate.MAX_RATIO   # slack only widens


def test_gate_consecutive_runs_pass_against_same_baseline():
    # The acceptance shape: two fresh same-distribution runs, one
    # committed baseline, both gates green.
    rng = random.Random(1234)

    def fresh():
        return _mk_prof({}) | {"phases": {
            "mttkrp": ph.robust_stats(
                [0.8 + rng.uniform(-0.03, 0.03) for _ in range(5)]).to_json(),
            "run.total": ph.robust_stats(
                [2.0 + rng.uniform(-0.05, 0.05) for _ in range(5)]).to_json(),
        }}

    base = fresh()
    assert pgate.compare(fresh(), base).status == "pass"
    assert pgate.compare(fresh(), base).status == "pass"


def test_gate_skips_on_noisy_host():
    base = _mk_prof({"mttkrp": (1.0, 0.02)})
    cur = _mk_prof({"mttkrp": (5.0, 0.02)}, noise=0.5)  # 5x slower but...
    result = pgate.compare(cur, base)
    assert result.status == "skip" and result.exit_status == 0
    assert any("host-noise" in m for m in result.messages)
    # ...and symmetric: a noisy *baseline* also refuses to gate
    noisy_base = _mk_prof({"mttkrp": (1.0, 0.02)}, noise=0.5)
    assert pgate.compare(base, noisy_base).status == "skip"


def test_gate_skips_on_fingerprint_mismatch():
    fp = ph.env_fingerprint()
    other = dict(fp, cpu_count=(fp.get("cpu_count") or 0) + 64)
    base = _mk_prof({"mttkrp": (1.0, 0.02)}, fingerprint=other)
    cur = _mk_prof({"mttkrp": (9.0, 0.02)})
    result = pgate.compare(cur, base)
    assert result.status == "skip"
    assert any("fingerprint" in m for m in result.messages)


def test_gate_noisy_phase_reported_never_failed():
    base = _mk_prof({"mttkrp": (1.0, 0.02)})
    cur = _mk_prof({"mttkrp": (3.0, 0.40)})     # wildly noisy phase
    result = pgate.compare(cur, base)
    assert result.status == "pass"
    assert result.phases[0]["verdict"] == "noisy"


def test_gate_sub_resolution_phase_never_failed():
    base = _mk_prof({"tick": (1e-6, 0.0)})
    cur = _mk_prof({"tick": (5e-5, 0.0)})       # 50x but under 100µs
    result = pgate.compare(cur, base)
    assert result.status == "pass"
    assert result.phases[0]["verdict"] == "sub-resolution"


def test_gate_improvement_is_not_a_failure():
    base = _mk_prof({"mttkrp": (2.0, 0.02)})
    cur = _mk_prof({"mttkrp": (0.5, 0.02)})
    result = pgate.compare(cur, base)
    assert result.status == "pass"
    assert result.phases[0]["verdict"] == "improved"


def test_gate_notes_phase_set_drift():
    base = _mk_prof({"old": (1.0, 0.02), "both": (1.0, 0.02)})
    cur = _mk_prof({"new": (1.0, 0.02), "both": (1.0, 0.02)})
    result = pgate.compare(cur, base)
    assert result.status == "pass"
    assert any("'old' in baseline only" in m for m in result.messages)
    assert any("'new' is new" in m for m in result.messages)


def test_gate_no_common_phases_skips():
    result = pgate.compare(_mk_prof({"a": (1.0, 0.0)}),
                           _mk_prof({"b": (1.0, 0.0)}))
    assert result.status == "skip"


def test_gate_invalid_artifact_fails_loudly():
    good = _mk_prof({"mttkrp": (1.0, 0.02)})
    result = pgate.compare({"nope": 1}, good)
    assert result.status == "fail"
    assert any("current artifact invalid" in m for m in result.messages)


# ---------------------------------------------------------------------------
# run_profile with an injected fast collect + CLI paths
# ---------------------------------------------------------------------------

def _fake_collect_factory(extra_span_first_call=False):
    state = {"calls": 0}

    def collect(tracer=None):
        state["calls"] += 1
        with tracer.span("alpha", backend="ref"):
            with tracer.span("beta"):
                pass
        if extra_span_first_call and state["calls"] == 1:
            with tracer.span("flaky-once"):
                pass
        return {"counters": {"oocore.chunks": 3}}

    return collect, state


def test_run_profile_synthetic_collect_emits_valid_prof():
    collect, state = _fake_collect_factory()
    prof, records = prof_main.run_profile(repeats=3, warmup=1,
                                          collect=collect)
    assert pgate.validate_prof(prof) == []
    assert state["calls"] == 4                  # 1 warmup + 3 timed
    assert {"alpha", "beta", "run.total"} <= set(prof["phases"])
    for ph_row in prof["phases"].values():
        assert ph_row["n"] == 3                 # one sample per repeat
    assert prof["counters"] == {"oocore.chunks": 3}
    assert {r.name for r in records} == {"alpha", "beta"}


def test_run_profile_drops_phases_missing_from_some_repeat():
    collect, _ = _fake_collect_factory(extra_span_first_call=True)
    # warmup absorbs the first call, so the flaky span appears in zero
    # timed repeats here; flip warmup to 0 to land it in repeat 1 only.
    prof, _ = prof_main.run_profile(repeats=2, warmup=0, collect=collect)
    assert "flaky-once" not in prof["phases"]
    assert "alpha" in prof["phases"]


def test_run_profile_rejects_zero_repeats():
    collect, _ = _fake_collect_factory()
    with pytest.raises(ValueError):
        prof_main.run_profile(repeats=0, collect=collect)


@pytest.fixture()
def prof_tmp_paths(tmp_path, monkeypatch):
    """Point every prof CLI artifact at tmp so tests never touch the
    repo's committed experiments/obs/."""
    monkeypatch.setattr(prof_main, "RUN_PATH",
                        str(tmp_path / "PROF_run.json"))
    monkeypatch.setattr(prof_main, "BASELINE_PATH",
                        str(tmp_path / "PROF_baseline.json"))
    monkeypatch.setattr(prof_main, "FLAME_PATH",
                        str(tmp_path / "PROF_flame.folded"))
    monkeypatch.setattr(prof_main, "TRACE_PATH",
                        str(tmp_path / "PROF_trace.json"))
    return tmp_path


def test_cli_run_writes_artifacts(prof_tmp_paths, monkeypatch, capsys):
    from repro.obs import baseline as obaseline

    collect, _ = _fake_collect_factory()
    monkeypatch.setattr(obaseline, "collect", collect)
    rc = prof_main.main(["run", "--repeats", "2", "--warmup", "1"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "phases" in out
    prof = json.load(open(prof_tmp_paths / "PROF_run.json"))
    assert pgate.validate_prof(prof) == []
    assert (prof_tmp_paths / "PROF_flame.folded").exists()
    trace = json.load(open(prof_tmp_paths / "PROF_trace.json"))
    assert otr.validate_chrome_trace(trace, expect_names=["alpha", "beta"]) \
        == []


def test_cli_run_update_baseline_then_gate_passes(prof_tmp_paths,
                                                  monkeypatch, capsys):
    from repro.obs import baseline as obaseline

    collect, _ = _fake_collect_factory()
    monkeypatch.setattr(obaseline, "collect", collect)
    assert prof_main.main(["run", "--update-baseline"]) == 0
    assert (prof_tmp_paths / "PROF_baseline.json").exists()
    assert prof_main.main(["run"]) == 0
    capsys.readouterr()
    rc = prof_main.main(["gate",
                         "--current", str(prof_tmp_paths / "PROF_run.json"),
                         "--baseline",
                         str(prof_tmp_paths / "PROF_baseline.json")])
    out = capsys.readouterr().out
    # same host, same synthetic workload: pass — or skip iff this CI
    # runner's own measured noise exceeded the bar (printed either way)
    assert rc == 0
    assert ("timed gate passed" in out) or ("SKIP" in out)


def test_cli_gate_missing_baseline_skips(prof_tmp_paths, capsys):
    rc = prof_main.main(["gate",
                         "--current", str(prof_tmp_paths / "nope.json"),
                         "--baseline", str(prof_tmp_paths / "missing.json")])
    assert rc == 0
    assert "SKIP no timed baseline" in capsys.readouterr().out


def test_cli_gate_missing_current_fails(prof_tmp_paths, capsys):
    base = _mk_prof({"mttkrp": (1.0, 0.02)})
    bpath = prof_tmp_paths / "PROF_baseline.json"
    bpath.write_text(json.dumps(base))
    rc = prof_main.main(["gate", "--current",
                         str(prof_tmp_paths / "absent.json"),
                         "--baseline", str(bpath)])
    assert rc == 1
    assert "FAIL no current profile" in capsys.readouterr().out


def test_cli_gate_fails_on_2x_and_report_only_softens(prof_tmp_paths,
                                                      capsys):
    base = _mk_prof({"mttkrp": (1.0, 0.01)})
    cur = _mk_prof({"mttkrp": (2.0, 0.01)})
    bpath = prof_tmp_paths / "base.json"
    cpath = prof_tmp_paths / "cur.json"
    bpath.write_text(json.dumps(base))
    cpath.write_text(json.dumps(cur))
    argv = ["gate", "--current", str(cpath), "--baseline", str(bpath)]
    assert prof_main.main(argv) == 1
    assert "FAILED" in capsys.readouterr().out
    assert prof_main.main(argv + ["--report-only"]) == 0
    assert "exit forced to 0" in capsys.readouterr().out


def test_cli_report_renders_and_rejects_invalid(prof_tmp_paths, capsys):
    prof = _mk_prof({"mttkrp": (1.0, 0.02)})
    prof["selftime"]["top_down"] = [
        {"path": "sweep;mode", "calls": 2, "total_s": 1.0, "self_s": 0.5,
         "self_frac": 0.5, "self_counters": {}}]
    prof["selftime"]["bottom_up"] = [
        {"name": "mode", "calls": 2, "total_s": 1.0, "self_s": 0.5,
         "self_frac": 0.5, "self_counters": {}}]
    prof["roofline"] = [
        {"span": "oocore.mode_step", "backend": "s", "rung": "stream",
         "ordering": "tile", "calls": 3, "time_s": 1.0,
         "moved_bytes": 1024, "basis": "pipelined+index_stream",
         "achieved_gbps": 1.0e-6, "per_counter_gbps": {},
         "counted_bytes": {}}]
    prof["breakdown"] = [
        {"mode": 0, "calls": 1, "total_s": 1.0, "mttkrp_s": 0.5,
         "solve_s": 0.2, "remap_s": 0.2, "other_s": 0.1,
         "share_frac": 1.0}]
    path = prof_tmp_paths / "p.json"
    path.write_text(json.dumps(prof))
    assert prof_main.main(["report", str(path)]) == 0
    out = capsys.readouterr().out
    for needle in ("phases", "top-down", "bottom-up", "achieved bandwidth",
                   "per-mode breakdown", "sweep;mode", "GB/s"):
        assert needle in out, needle
    bad = prof_tmp_paths / "bad.json"
    bad.write_text(json.dumps({"meta": {}}))
    assert prof_main.main(["report", str(bad)]) == 1


def test_committed_prof_baseline_is_schema_valid():
    import os

    path = os.path.join(os.path.dirname(__file__), "..", "experiments",
                        "obs", "PROF_baseline.json")
    prof = json.load(open(path))
    assert pgate.validate_prof(prof) == []
    # the profiled workload covered the paper's phases and the roofline
    # join produced real rows
    assert {"mttkrp", "solve", "remap", "sweep", "run.total"} \
        <= set(prof["phases"])
    assert prof["roofline"], "committed baseline has no roofline rows"
    for row in prof["roofline"]:
        assert row["moved_bytes"] > 0 and row["achieved_gbps"] > 0
        assert not any(b.startswith("planner.")
                       for b in row["counted_bytes"])
