"""repro.resilience — fault injection, degradation policy, guarded solve,
checkpoint/resume (PR-9).

Coverage per the issue checklist:
  * seeded schedules are bit-reproducible and every spec validates
    against the closed site registry;
  * injector mechanics: index-matched firing, per-site counters advance
    on retry (a retried call gets a fresh index), pending() accounting,
    conflicting-spec rejection, nesting restores the outer injector;
  * the degradation ladder is a strict walk over real backend names
    (validated against ``ops.BACKENDS``);
  * ``RetryPolicy.run`` / ``dispatch`` walks with fake calls: bounded
    transient retry, compiled → interpret flip, recorded rung descent,
    corruption propagation, ``ResilienceExhausted`` at the floor —
    every decision visible in ``resilience.*`` counters;
  * ``guarded_solve`` is bit-identical to the plain solve on healthy
    input and escalates (ridge → lstsq) on non-finite/singular grams,
    eagerly and under jit;
  * checkpoint state round-trip + config-fingerprint validation, the
    chaos CP-ALS fit matches the fault-free run, and (slow) a
    SIGKILL-ed job resumes warm to the same decomposition / a save
    killed mid-write can never corrupt the newest complete checkpoint.
"""
import os
import signal
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.obs import counters as ocnt
from repro.resilience import (
    DEGRADATION_LADDER,
    CorruptionFault,
    FaultInjector,
    FaultSpec,
    GUARD_LEVELS,
    InjectedFault,
    ResilienceExhausted,
    ResourceFault,
    RetryPolicy,
    TransientFault,
    fault_site,
    guarded_solve,
    inject,
    next_rung,
    seeded_schedule,
)
from repro.resilience import checkpoint as rckpt
from repro.resilience.faults import FAULT_KINDS, SITES

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SRC = os.path.join(_REPO, "src")


# ---------------------------------------------------------------------------
# Seeded schedules + spec validation
# ---------------------------------------------------------------------------

def test_seeded_schedule_bit_reproducible():
    a = seeded_schedule(7, per_site=2, horizon=5)
    b = seeded_schedule(7, per_site=2, horizon=5)
    assert a == b
    assert a != seeded_schedule(8, per_site=2, horizon=5)
    assert len(a) == 2 * len(SITES)
    for s in a:
        assert 0 <= s.index < 5
        assert s.kind in FAULT_KINDS
    # per-site indices are distinct (drawn without replacement).
    for site in SITES:
        idxs = [s.index for s in a if s.site == site]
        assert len(set(idxs)) == len(idxs) == 2


def test_seeded_schedule_kind_override():
    specs = seeded_schedule(0, kinds={"ops.kernel": "transient"})
    kinds = {s.site: s.kind for s in specs}
    assert kinds["ops.kernel"] == "transient"
    assert kinds["tune.table_load"] == "corruption"


@pytest.mark.parametrize("bad", [
    dict(site="nope.site", index=0, kind="transient"),
    dict(site="ops.kernel", index=0, kind="nope"),
    dict(site="ops.kernel", index=-1, kind="transient"),
])
def test_fault_spec_validation(bad):
    with pytest.raises(ValueError):
        FaultSpec(**bad)


def test_fault_site_rejects_unregistered_name():
    with pytest.raises(ValueError, match="unknown fault site"):
        fault_site("not.a.site")


def test_fault_taxonomy():
    assert issubclass(TransientFault, InjectedFault)
    assert issubclass(ResourceFault, InjectedFault)
    assert issubclass(CorruptionFault, InjectedFault)
    e = TransientFault("ops.kernel", 3, note="dma hiccup")
    assert e.site == "ops.kernel" and e.index == 3
    assert "dma hiccup" in str(e)


# ---------------------------------------------------------------------------
# Injector mechanics
# ---------------------------------------------------------------------------

def test_injector_fires_on_index_match():
    with ocnt.use_registry() as reg:
        with inject([FaultSpec("ops.kernel", 1, "transient")]) as inj:
            fault_site("ops.kernel")                    # call 0: passes
            with pytest.raises(TransientFault):
                fault_site("ops.kernel")                # call 1: fires
            fault_site("ops.kernel")                    # call 2: passes
            assert inj.calls["ops.kernel"] == 3
            assert [s.index for s in inj.injected] == [1]
            assert inj.pending() == ()
        assert reg.get("resilience.injected",
                       site="ops.kernel", kind="transient") == 1
        assert reg.get("resilience.site_calls",
                       site="ops.kernel") == 3


def test_injector_pending_when_site_not_reached():
    with inject([FaultSpec("oocore.chunk", 4, "transient")]) as inj:
        fault_site("oocore.chunk")
    assert inj.pending() == (FaultSpec("oocore.chunk", 4, "transient"),)


def test_injector_rejects_conflicting_specs():
    with pytest.raises(ValueError, match="conflicting"):
        FaultInjector((FaultSpec("ops.kernel", 0, "transient"),
                       FaultSpec("ops.kernel", 0, "resource")))


def test_inject_nesting_restores_outer():
    from repro.resilience.faults import active_injector
    with inject([]) as outer:
        with inject([]) as inner:
            assert active_injector() is inner
        assert active_injector() is outer
    assert active_injector() is None


def test_fault_site_noop_without_injector():
    with ocnt.use_registry() as reg:
        fault_site("execution.resolve")
        assert reg.get("resilience.site_calls",
                       site="execution.resolve") == 1
        assert reg.total("resilience.injected") == 0


# ---------------------------------------------------------------------------
# Degradation ladder + retry policy
# ---------------------------------------------------------------------------

def test_ladder_is_real_backends_and_strictly_descending():
    from repro.kernels.mttkrp import ops
    for rung in DEGRADATION_LADDER:
        assert rung in ops.BACKENDS, rung
    assert len(set(DEGRADATION_LADDER)) == len(DEGRADATION_LADDER)
    walk = [DEGRADATION_LADDER[0]]
    while next_rung(walk[-1]) is not None:
        walk.append(next_rung(walk[-1]))
    assert tuple(walk) == DEGRADATION_LADDER
    assert next_rung("ref") is None
    assert next_rung("not_a_backend") is None


def test_retry_run_recovers_and_counts():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise TransientFault("oocore.chunk", calls["n"] - 1)
        return "ok"

    with ocnt.use_registry() as reg:
        pol = RetryPolicy(max_retries=3)
        assert pol.run("oocore.chunk", flaky) == "ok"
        assert reg.get("resilience.retries",
                       site="oocore.chunk") == 2


def test_retry_run_exhausts():
    def always():
        raise TransientFault("oocore.chunk", 0)

    with ocnt.use_registry():
        with pytest.raises(ResilienceExhausted):
            RetryPolicy(max_retries=2).run("oocore.chunk", always)


def test_retry_run_propagates_non_transient():
    def res():
        raise ResourceFault("oocore.chunk", 0)

    with ocnt.use_registry():
        with pytest.raises(ResourceFault):
            RetryPolicy().run("oocore.chunk", res)


def test_retry_backoff_schedule_is_exponential():
    slept = []
    pol = RetryPolicy(max_retries=3, backoff_base_s=0.5, backoff_factor=2.0,
                      sleep=slept.append)
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] <= 3:
            raise TransientFault("oocore.chunk", 0)
        return 1

    with ocnt.use_registry():
        pol.run("oocore.chunk", flaky)
    assert slept == [0.5, 1.0, 2.0]


def _scripted_call(script):
    """A fake ``call(backend, interpret)``: pops the next scripted action."""
    log = []

    def call(backend, interpret):
        log.append((backend, interpret))
        action = script.pop(0) if script else "ok"
        if action == "ok":
            return ("done", backend, interpret)
        raise action

    return call, log


def test_dispatch_transient_retries_same_rung():
    call, log = _scripted_call([TransientFault("ops.kernel", 0), "ok"])
    with ocnt.use_registry() as reg:
        out = RetryPolicy().dispatch(call, "pallas_fused", False)
    assert out == ("done", "pallas_fused", False)
    assert log == [("pallas_fused", False)] * 2
    assert reg.get("resilience.retries",
                   site="ops.kernel") == 1


def test_dispatch_resource_flips_compiled_to_interpret_first():
    call, log = _scripted_call([ResourceFault("ops.kernel", 0), "ok"])
    with ocnt.use_registry() as reg:
        out = RetryPolicy().dispatch(call, "pallas_fused", False)
    assert out == ("done", "pallas_fused", True)   # same rung, interpreted
    assert log == [("pallas_fused", False), ("pallas_fused", True)]
    assert reg.get("resilience.interpret_fallbacks",
                   backend="pallas_fused") == 1
    assert reg.total("resilience.degradations") == 0


def test_dispatch_resource_under_compiled_mode_raises():
    # execution_mode="compiled" promises compiled kernels or an error:
    # the policy must not flip to the interpreter behind it.
    from repro.runtime import execution
    call, log = _scripted_call([ResourceFault("ops.kernel", 0), "ok"])
    with execution.execution_mode("compiled"), ocnt.use_registry() as reg:
        with pytest.raises(ResourceFault):
            RetryPolicy().dispatch(call, "pallas_fused", False)
    assert log == [("pallas_fused", False)]
    assert reg.total("resilience.interpret_fallbacks") == 0


def test_dispatch_resource_under_interpret_steps_down():
    call, log = _scripted_call([ResourceFault("ops.kernel", 0), "ok"])
    with ocnt.use_registry() as reg:
        out = RetryPolicy().dispatch(call, "pallas_fused_gather", True)
    assert out == ("done", "pallas_fused_gather_tiled", True)
    assert reg.get("resilience.degradations",
                   **{"from": "pallas_fused_gather",
                      "to": "pallas_fused_gather_tiled"}) == 1


def test_dispatch_corruption_propagates_immediately():
    call, log = _scripted_call([CorruptionFault("ops.kernel", 0)])
    with ocnt.use_registry() as reg:
        with pytest.raises(CorruptionFault):
            RetryPolicy().dispatch(call, "pallas_fused", True)
    assert len(log) == 1
    assert reg.total("resilience.retries") == 0
    assert reg.total("resilience.degradations") == 0


def test_dispatch_exhausts_at_ladder_floor():
    call, log = _scripted_call(
        [ResourceFault("ops.kernel", i) for i in range(20)])
    with ocnt.use_registry() as reg:
        with pytest.raises(ResilienceExhausted):
            RetryPolicy().dispatch(call, "pallas", True)
    # pallas → ref → floor: two attempts, one recorded degradation.
    assert [b for b, _ in log] == ["pallas", "ref"]
    assert reg.get("resilience.degradations",
                   **{"from": "pallas", "to": "ref"}) == 1


def test_dispatch_execution_mode_error_flip_then_raise():
    from repro.runtime.execution import ExecutionModeError
    call, log = _scripted_call([ExecutionModeError("compiled gone"),
                                ExecutionModeError("still gone")])
    with ocnt.use_registry() as reg:
        with pytest.raises(ExecutionModeError):
            RetryPolicy().dispatch(call, "pallas_fused", None)
    # One flip (resolution said "compiled impossible"), then unrecoverable.
    assert [i for _, i in log] == [None, True]
    assert reg.get("resilience.interpret_fallbacks",
                   backend="pallas_fused") == 1


def test_use_policy_scoping():
    from repro.resilience import get_policy, use_policy
    assert get_policy() is None
    with use_policy() as pol:
        assert get_policy() is pol
        custom = RetryPolicy(max_retries=1)
        with use_policy(custom):
            assert get_policy() is custom
        assert get_policy() is pol
    assert get_policy() is None


# ---------------------------------------------------------------------------
# Guarded solve
# ---------------------------------------------------------------------------

def _healthy_vm(rng, r=6, rows=9):
    A = np.asarray(rng.standard_normal((r + 2, r)), np.float32)
    V = (A.T @ A + np.eye(r, dtype=np.float32)).astype(np.float32)
    M = np.asarray(rng.standard_normal((rows, r)), np.float32)
    return V, M


def test_guarded_solve_healthy_is_bit_identical(rng):
    import jax.numpy as jnp
    V, M = _healthy_vm(rng)
    X, level = guarded_solve(jnp.asarray(V), jnp.asarray(M))
    assert int(level) == 0 and GUARD_LEVELS[int(level)] == "clean"
    plain = jnp.linalg.solve(
        jnp.asarray(V) + 1e-9 * jnp.eye(V.shape[0]), jnp.asarray(M).T).T
    np.testing.assert_array_equal(np.asarray(X), np.asarray(plain))


def test_guarded_solve_nonfinite_escalates_to_finite(rng):
    import jax.numpy as jnp
    V, M = _healthy_vm(rng)
    M = M.copy()
    M[0, 0] = np.nan
    X, level = guarded_solve(jnp.asarray(V), jnp.asarray(M))
    assert int(level) >= 1
    assert np.isfinite(np.asarray(X)).all()


def test_guarded_solve_collapsed_column_escalates(rng):
    import jax.numpy as jnp
    V, M = _healthy_vm(rng)
    V = V.copy()
    V[2, :] = 0.0
    V[:, 2] = 0.0          # collapsed factor column → zero gram diagonal
    X, level = guarded_solve(jnp.asarray(V), jnp.asarray(M))
    assert int(level) >= 1
    assert np.isfinite(np.asarray(X)).all()


def test_guarded_solve_all_zero_hits_lstsq(rng):
    # Zero gram + huge M: the escalated ridge solve (V + 1e-6·I)⁻¹ M
    # overflows fp32 → the SVD pinv floor must produce a finite answer.
    import jax.numpy as jnp
    r = 5
    V = jnp.zeros((r, r), jnp.float32)
    M = jnp.full((7, r), 1e38, jnp.float32)
    X, level = guarded_solve(V, M, ridge=0.0)
    assert GUARD_LEVELS[int(level)] == "lstsq"
    assert np.isfinite(np.asarray(X)).all()


def test_guarded_solve_same_under_jit(rng):
    import jax
    import jax.numpy as jnp
    V, M = _healthy_vm(rng)
    jitted = jax.jit(guarded_solve)
    Xe, le = guarded_solve(jnp.asarray(V), jnp.asarray(M))
    Xj, lj = jitted(jnp.asarray(V), jnp.asarray(M))
    assert int(le) == int(lj) == 0
    np.testing.assert_allclose(np.asarray(Xe), np.asarray(Xj), rtol=1e-6)


# ---------------------------------------------------------------------------
# Checkpoint state adapter + manager hardening
# ---------------------------------------------------------------------------

def _tiny_state(rng, sweep=0, rank=4, **kw):
    factors = [np.asarray(rng.standard_normal((d, rank)), np.float32)
               for d in (6, 5)]
    lam = np.ones(rank, np.float32)
    return rckpt.make_state(factors, lam, [0.5], sweep=sweep, rank=rank,
                            **kw)


def test_checkpoint_state_round_trip(tmp_path, rng):
    mgr = rckpt.make_manager(str(tmp_path))
    state = _tiny_state(rng, sweep=2, backend="jax")
    with ocnt.use_registry() as reg:
        rckpt.save_state(mgr, state)
        got, sweep = rckpt.restore_state(
            mgr, _tiny_state(rng, sweep=0, backend="jax"))
        assert reg.get("resilience.checkpoint.saves") == 1
        assert reg.get("resilience.checkpoint.restores") == 1
    assert sweep == 2
    for a, b in zip(got["factors"], state["factors"]):
        np.testing.assert_array_equal(np.asarray(a), b)
    np.testing.assert_array_equal(np.asarray(got["lam"]), state["lam"])


def test_checkpoint_restore_empty_dir_is_fresh_start(tmp_path, rng):
    mgr = rckpt.make_manager(str(tmp_path))
    state, sweep = rckpt.restore_state(mgr, _tiny_state(rng))
    assert state is None and sweep is None
    assert rckpt.make_manager(None) is None


@pytest.mark.parametrize("mutate, match", [
    (dict(rank=5), "rank"),
    (dict(backend="pallas"), "backend"),
    (dict(ordering="morton"), "ordering"),
])
def test_checkpoint_restore_rejects_config_mismatch(tmp_path, rng, mutate,
                                                    match):
    mgr = rckpt.make_manager(str(tmp_path))
    with ocnt.use_registry():
        rckpt.save_state(mgr, _tiny_state(rng, backend="jax",
                                          ordering="none"))
        template = _tiny_state(rng, **{**dict(backend="jax",
                                              ordering="none"), **mutate})
        with pytest.raises(ValueError, match=match):
            rckpt.restore_state(mgr, template)


def test_checkpoint_restore_rejects_shape_mismatch(tmp_path, rng):
    mgr = rckpt.make_manager(str(tmp_path))
    with ocnt.use_registry():
        rckpt.save_state(mgr, _tiny_state(rng))
        template = _tiny_state(rng)
        template["factors"][0] = template["factors"][0][:-1]
        with pytest.raises(ValueError, match="shape"):
            rckpt.restore_state(mgr, template)


def test_manager_sweeps_stale_tmp_dirs(tmp_path):
    from repro.checkpoint import CheckpointManager
    stale = tmp_path / "tmp.7"
    stale.mkdir()
    (stale / "half_written.npy").write_bytes(b"\x00" * 16)
    CheckpointManager(str(tmp_path))
    assert not stale.exists()


# ---------------------------------------------------------------------------
# End-to-end: chaos CP-ALS + (slow) kill/resume and crash atomicity
# ---------------------------------------------------------------------------

def test_cp_als_checkpoint_resume_matches_uninterrupted(tmp_path, rng):
    """Single-device driver: stop at sweep 2, resume to 4 == straight 4."""
    from repro.core.cpals import cp_als
    from repro.core.tensors import random_sparse_tensor
    t = random_sparse_tensor((12, 10, 8), 120, seed=0)
    d1, d2 = str(tmp_path / "a"), str(tmp_path / "b")
    with ocnt.use_registry() as reg:
        cp_als(t, 4, iters=2, seed=0, tol=0.0, checkpoint_dir=d1)
        resumed = cp_als(t, 4, iters=4, seed=0, tol=0.0, checkpoint_dir=d1)
        full = cp_als(t, 4, iters=4, seed=0, tol=0.0, checkpoint_dir=d2)
        assert reg.get("resilience.checkpoint.restores") == 1
    assert len(resumed.fits) == len(full.fits) == 4
    np.testing.assert_allclose(resumed.fits, full.fits, rtol=0, atol=0)
    for a, b in zip(resumed.factors, full.factors):
        np.testing.assert_array_equal(a, b)


def test_chaos_cp_als_fit_matches_fault_free(rng):
    """Faults at the kernel/remap boundaries; fit allclose, all counted."""
    import jax
    from jax.sharding import Mesh

    from repro.core import distributed as dist
    from repro.core.cpals import cp_als_distributed
    from repro.core.flycoo import build_flycoo
    from repro.core.tensors import random_sparse_tensor
    if jax.device_count() < 1:
        pytest.skip("needs a jax device")
    t = random_sparse_tensor((14, 12, 10), 150, seed=1)
    ft = build_flycoo(t, 1, m_bounds=(2, 8), g_bounds=(8, 64))
    mesh = Mesh(np.array(jax.devices()[:1]), (dist.AXIS,))

    def run(specs):
        jax.clear_caches()
        with ocnt.use_registry() as reg:
            if specs is None:
                res = cp_als_distributed(ft, 4, mesh, iters=2, seed=0,
                                         tol=0.0, backend="auto",
                                         resilience=RetryPolicy())
                return res, reg.snapshot(), None
            with inject(specs) as inj:
                res = cp_als_distributed(ft, 4, mesh, iters=2, seed=0,
                                         tol=0.0, backend="auto",
                                         resilience=RetryPolicy())
            return res, reg.snapshot(), inj

    ref, _, _ = run(None)
    specs = [FaultSpec("ops.kernel", 1, "transient"),
             FaultSpec("distributed.remap", 0, "transient")]
    chaos, snap, inj = run(specs)
    assert inj.pending() == ()
    np.testing.assert_allclose(chaos.fits, ref.fits, rtol=1e-4, atol=1e-5)
    handled = sum(v for k, v in snap.items()
                  if k.startswith(("resilience.retries",
                                   "resilience.degradations",
                                   "resilience.interpret_fallbacks")))
    assert handled >= len(specs)


@pytest.mark.slow
def test_cp_als_sigkill_resume(tmp_path):
    """A job SIGKILLed mid-run resumes warm and converges identically."""
    from repro.core.cpals import cp_als
    from repro.core.tensors import random_sparse_tensor
    d = str(tmp_path / "ck")
    child = textwrap.dedent("""
        import os, signal
        import repro.resilience.checkpoint as rc
        orig = rc.save_state
        def dying(mgr, state, _n=[0]):
            path = orig(mgr, state)
            _n[0] += 1
            if _n[0] >= 2:
                os.kill(os.getpid(), signal.SIGKILL)   # die after sweep 1
            return path
        rc.save_state = dying
        import repro.core.cpals as cp
        cp._ckpt.save_state = dying
        from repro.core.tensors import random_sparse_tensor
        t = random_sparse_tensor((12, 10, 8), 120, seed=0)
        cp.cp_als(t, 4, iters=5, seed=0, tol=0.0,
                  checkpoint_dir={d!r})
        raise SystemExit("unreachable: SIGKILL expected")
    """).format(d=d)
    env = dict(os.environ,
               PYTHONPATH=_SRC + os.pathsep + os.environ.get("PYTHONPATH",
                                                             ""))
    proc = subprocess.run([sys.executable, "-c", child], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == -signal.SIGKILL, proc.stderr

    from repro.checkpoint import CheckpointManager
    assert CheckpointManager(d).latest_step() == 1   # sweeps 0,1 persisted

    from repro.core.tensors import random_sparse_tensor
    t = random_sparse_tensor((12, 10, 8), 120, seed=0)
    with ocnt.use_registry() as reg:
        resumed = cp_als(t, 4, iters=5, seed=0, tol=0.0, checkpoint_dir=d)
        assert reg.get("resilience.checkpoint.restores") == 1
    full = cp_als(t, 4, iters=5, seed=0, tol=0.0)
    assert len(resumed.fits) == len(full.fits) == 5
    np.testing.assert_allclose(resumed.fits, full.fits, rtol=0, atol=0)


@pytest.mark.slow
def test_checkpoint_crash_atomicity(tmp_path):
    """SIGKILL mid-save never corrupts the newest complete checkpoint."""
    d = str(tmp_path / "ck")
    child = textwrap.dedent("""
        import os, signal
        import numpy as np
        import repro.checkpoint.manager as m
        mgr = m.CheckpointManager({d!r})
        state = dict(x=np.arange(64, dtype=np.float32),
                     y=np.ones((8, 8), np.float32))
        mgr.save(1, state)                      # complete checkpoint
        orig = m._fsync_file
        def dying(path, _n=[0]):
            _n[0] += 1
            if _n[0] >= 2:                      # mid-way through save #2
                os.kill(os.getpid(), signal.SIGKILL)
            orig(path)
        m._fsync_file = dying
        state2 = dict(x=np.full(64, 9.0, np.float32),
                      y=np.zeros((8, 8), np.float32))
        mgr.save(2, state2)
        raise SystemExit("unreachable: SIGKILL expected")
    """).format(d=d)
    env = dict(os.environ,
               PYTHONPATH=_SRC + os.pathsep + os.environ.get("PYTHONPATH",
                                                             ""))
    proc = subprocess.run([sys.executable, "-c", child], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == -signal.SIGKILL, proc.stderr

    from repro.checkpoint import CheckpointManager
    half = [n for n in os.listdir(d) if n.startswith("tmp.")]
    assert half == ["tmp.2"]                 # the crash left its debris...
    mgr = CheckpointManager(d)               # ...which init sweeps
    assert [n for n in os.listdir(d) if n.startswith("tmp.")] == []
    assert mgr.all_steps() == [1]            # step 2 never became visible
    template = dict(x=np.zeros(64, np.float32),
                    y=np.zeros((8, 8), np.float32))
    restored, step = mgr.restore(template)
    assert step == 1
    np.testing.assert_array_equal(np.asarray(restored["x"]),
                                  np.arange(64, dtype=np.float32))
