"""Imprecise real-time scheduler (paper §5): priority functions, simulator
invariants, and the paper's qualitative claims on synthetic workloads."""
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import energy
from repro.core.scheduler import (
    CHRTClock,
    Job,
    JobProfile,
    SimConfig,
    TaskSpec,
    simulate,
    zeta,
    zeta_intermittent,
)

PERSISTENT = energy.Harvester("battery", 1.0, 0.0, 10.0)


def profile(n_units=4, exit_at=None, correct_from=0):
    margins = np.linspace(0.05, 0.5, n_units)
    passes = np.zeros(n_units, bool)
    if exit_at is not None:
        passes[exit_at:] = True
    correct = np.zeros(n_units, bool)
    correct[correct_from:] = True
    return JobProfile(margins, passes, correct)


def make_task(tid=0, n_jobs=20, period=1.0, deadline=2.0, unit_t=0.1,
              unit_e=1e-3, n_units=4, exit_at=1):
    return TaskSpec(
        task_id=tid,
        period=period,
        deadline=deadline,
        unit_time=np.full(n_units, unit_t),
        unit_energy=np.full(n_units, unit_e),
        profiles=[profile(n_units, exit_at) for _ in range(n_jobs)],
    )


# --------------------------------------------------------------------------- #
# Priority functions (Eqs. 6-7).
# --------------------------------------------------------------------------- #


def _job(deadline=2.0, utility=0.3, mandatory=True):
    p = profile(4, exit_at=None if mandatory else 0)
    j = Job(make_task(), 0, 0.0, deadline, p)
    if not mandatory:
        j.exited_at = 0
        j.last_pred_unit = 0
        j.unit = 1
    return j


def test_zeta_matches_eq6():
    j = _job(deadline=2.0, mandatory=True)
    alpha, beta = 0.5, 1.0
    got = zeta(j, t_now=1.0, alpha=alpha, beta=beta)
    want = (1 - 0.5 * (2.0 - 1.0)) + (1 - 1.0 * j.utility) + 1.0
    assert got == pytest.approx(want)


def test_zeta_orderings():
    """Tighter deadline, lower utility, mandatory status all raise priority."""
    t = 0.0
    tight = _job(deadline=1.0)
    loose = _job(deadline=3.0)
    assert zeta(tight, t, 0.25, 1.0) > zeta(loose, t, 0.25, 1.0)
    mand = _job(mandatory=True)
    opt = _job(mandatory=False)
    assert zeta(mand, t, 0.25, 1.0) > zeta(opt, t, 0.25, 1.0)


def test_zeta_intermittent_gates_optional():
    """Eq. 7: below the eta-weighted energy threshold, optional units get
    zero priority while mandatory units keep the base priority."""
    mand = _job(mandatory=True)
    opt = _job(mandatory=False)
    lo = zeta_intermittent(opt, 0.0, 0.25, 1.0, eta=0.3, e_curr=0.2,
                           e_opt=0.5)
    assert lo == 0.0
    hi = zeta_intermittent(opt, 0.0, 0.25, 1.0, eta=0.9, e_curr=0.9,
                           e_opt=0.5)
    assert hi > 0.0
    m = zeta_intermittent(mand, 0.0, 0.25, 1.0, eta=0.3, e_curr=0.2,
                          e_opt=0.5)
    assert m > 0.0


# --------------------------------------------------------------------------- #
# Simulator invariants.
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("policy", ["edf", "edf-m", "rr", "zygarde"])
def test_persistent_underload_schedules_everything(policy):
    task = make_task(n_jobs=20, period=1.0, deadline=2.0, unit_t=0.05)
    res = simulate([task], PERSISTENT, eta=1.0,
                   sim=SimConfig(policy=policy, horizon=40.0))
    assert res.released == 20
    assert res.scheduled == 20
    assert res.deadline_misses == 0
    assert res.reboots == 0


@pytest.mark.parametrize("policy", ["edf", "edf-m", "zygarde"])
def test_scheduled_bounded_by_released(policy):
    task = make_task(n_jobs=30, period=0.5, deadline=1.0, unit_t=0.2)
    harv = energy.Harvester("h", 0.9, 0.9, 0.05)
    res = simulate([task], harv, eta=0.7,
                   sim=SimConfig(policy=policy, horizon=30.0))
    assert 0 <= res.correct <= res.scheduled <= res.released
    assert res.scheduled + res.deadline_misses <= res.released + 1


def test_early_exit_reduces_units():
    """Early exit (EDF-M) executes fewer units than full EDF."""
    t_full = make_task(n_jobs=15, exit_at=None)  # never exits early
    t_exit = make_task(n_jobs=15, exit_at=0)     # exits after unit 1
    full = simulate([t_full], PERSISTENT, 1.0,
                    sim=SimConfig(policy="edf", horizon=30.0))
    part = simulate([t_exit], PERSISTENT, 1.0,
                    sim=SimConfig(policy="edf-m", horizon=30.0))
    assert part.units_executed < full.units_executed


def test_zygarde_runs_optional_units_when_energy_rich():
    task = make_task(n_jobs=10, period=2.0, deadline=4.0, unit_t=0.05,
                     exit_at=0)
    res = simulate([task], PERSISTENT, eta=1.0,
                   sim=SimConfig(policy="zygarde", horizon=30.0))
    assert res.optional_units > 0
    edfm = simulate([task], PERSISTENT, eta=1.0,
                    sim=SimConfig(policy="edf-m", horizon=30.0))
    assert edfm.optional_units == 0


def test_overload_zygarde_and_edfm_beat_edf():
    """Paper Figs. 17-20: with U > 1, imprecise policies schedule more jobs
    than EDF (which must run every unit)."""
    task = make_task(n_jobs=30, period=0.5, deadline=1.0, unit_t=0.2,
                     exit_at=0)  # mandatory = 1 unit of 4
    edf = simulate([task], PERSISTENT, 1.0,
                   sim=SimConfig(policy="edf", horizon=30.0))
    edfm = simulate([task], PERSISTENT, 1.0,
                    sim=SimConfig(policy="edf-m", horizon=30.0))
    zyg = simulate([task], PERSISTENT, 1.0,
                   sim=SimConfig(policy="zygarde", horizon=30.0))
    assert edfm.scheduled > edf.scheduled
    assert zyg.scheduled > edf.scheduled


def test_intermittent_power_causes_misses_and_reboots():
    task = make_task(n_jobs=20, period=1.0, deadline=2.0, unit_t=0.1,
                     unit_e=5e-2)
    weak = energy.Harvester("weak", 0.8, 0.8, 0.02)
    res = simulate([task], weak, eta=0.5,
                   sim=SimConfig(policy="zygarde", horizon=40.0, seed=3))
    assert res.idle_no_energy > 0
    assert res.scheduled < res.released


def test_queue_overflow_drops_jobs():
    task = make_task(n_jobs=40, period=0.05, deadline=0.2, unit_t=0.5)
    res = simulate([task], PERSISTENT, 1.0,
                   sim=SimConfig(policy="edf", horizon=10.0, queue_size=2))
    assert res.deadline_misses > 0


@given(st.integers(0, 10_000), st.sampled_from(["edf", "edf-m", "zygarde"]))
@settings(max_examples=12, deadline=None)
def test_simulator_accounting_property(seed, policy):
    """released == scheduled-or-missed under any seed/policy."""
    rng = np.random.default_rng(seed)
    task = make_task(
        n_jobs=int(rng.integers(5, 25)),
        period=float(rng.uniform(0.3, 2.0)),
        deadline=float(rng.uniform(0.5, 3.0)),
        unit_t=float(rng.uniform(0.02, 0.3)),
        exit_at=int(rng.integers(0, 4)),
    )
    harv = energy.Harvester("h", 0.9, 0.9, float(rng.uniform(0.01, 1.0)))
    res = simulate([task], harv, eta=0.6,
                   sim=SimConfig(policy=policy, horizon=20.0, seed=seed))
    assert res.scheduled + res.deadline_misses == res.released
    assert res.correct <= res.scheduled
    assert res.busy_time <= res.sim_time + 1e-6


def test_chrt_clock_error_distribution():
    clock = CHRTClock()
    rng = np.random.default_rng(0)
    errs = np.array([clock.read(100.0, rng) - 100.0 for _ in range(5000)])
    assert (errs == 0).mean() == pytest.approx(0.80, abs=0.03)
    assert (errs < 0).mean() < 0.04  # negative error < 3% (paper §8.7)


def test_chrt_slightly_degrades_schedule():
    task = make_task(n_jobs=25, period=1.0, deadline=2.0, unit_t=0.1)
    harv = energy.Harvester("h", 0.95, 0.95, 0.08)
    rtc = simulate([task], harv, 0.7,
                   sim=SimConfig(policy="zygarde", horizon=40.0, seed=1))
    chrt = simulate([task], harv, 0.7,
                    sim=SimConfig(policy="zygarde", horizon=40.0, seed=1,
                                  clock=CHRTClock()))
    # CHRT may cost a few jobs but not collapse (paper: < 0.1% loss at scale)
    assert chrt.scheduled >= rtc.scheduled - 3
