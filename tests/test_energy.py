"""Energy model (paper §3, §5.3): h(N), KW distance, eta-factor, harvesters,
capacitor, schedulability — unit + hypothesis property tests."""
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import energy


# --------------------------------------------------------------------------- #
# h(N) — conditional energy events (Eq. 1).
# --------------------------------------------------------------------------- #


def test_h_curve_alternating():
    """A strictly alternating trace: after 1 event the next never occurs."""
    trace = np.tile([1, 0], 500)
    assert energy.conditional_energy_event(trace, 1) == pytest.approx(0.0)
    assert energy.conditional_energy_event(trace, -1) == pytest.approx(1.0)
    # runs of length 2 never happen
    assert np.isnan(energy.conditional_energy_event(trace, 2))


def test_h_curve_constant_on():
    trace = np.ones(1000, dtype=np.int8)
    for n in (1, 5, 19):
        assert energy.conditional_energy_event(trace, n) == pytest.approx(1.0)
        assert np.isnan(energy.conditional_energy_event(trace, -n))


def test_h_curve_iid():
    rng = np.random.default_rng(0)
    trace = (rng.random(200_000) < 0.5).astype(np.int8)
    h = energy.conditional_energy_event
    assert h(trace, 1) == pytest.approx(0.5, abs=0.02)
    assert h(trace, -3) == pytest.approx(0.5, abs=0.02)


# --------------------------------------------------------------------------- #
# eta-factor (Eqs. 2-3).
# --------------------------------------------------------------------------- #


def test_eta_persistent_is_one():
    h = energy.Harvester("p", 1.0, 0.0, 1.0)
    tr = h.sample_events(np.random.default_rng(0), 5000, init=1)
    assert energy.eta_factor(tr) == pytest.approx(1.0, abs=1e-6)


def test_eta_random_is_near_zero():
    h = energy.Harvester("r", 0.5, 0.5, 1.0)
    tr = h.sample_events(np.random.default_rng(0), 50_000)
    assert energy.eta_factor(tr) < 0.1


def test_eta_monotone_in_burstiness():
    """More bursty (higher stay-probability) => higher eta (paper Fig. 25)."""
    etas = []
    for p in (0.55, 0.7, 0.85, 0.95, 0.99):
        h = energy.Harvester("h", p, p, 1.0)
        tr = h.sample_events(np.random.default_rng(3), 60_000)
        etas.append(energy.eta_factor(tr))
    assert all(b > a - 0.02 for a, b in zip(etas, etas[1:]))
    assert etas[-1] > etas[0] + 0.3


@given(st.floats(0.05, 0.95))
@settings(max_examples=15, deadline=None)
def test_eta_bounds(p_stay):
    h = energy.Harvester("h", p_stay, p_stay, 1.0)
    tr = h.sample_events(np.random.default_rng(1), 5000)
    eta = energy.eta_factor(tr)
    assert 0.0 <= eta <= 1.0


def test_calibrate_harvester_hits_target():
    for target in (0.38, 0.51, 0.71):
        h = energy.calibrate_harvester(target, 0.6)
        tr = h.sample_events(np.random.default_rng(42), 40_000)
        assert energy.eta_factor(tr) == pytest.approx(target, abs=0.08)


def test_kw_distance_properties():
    a = energy.ideal_h_curve()
    r = energy.random_h_curve()
    assert energy.kw_distance(a, a) == pytest.approx(0.0)
    assert energy.kw_distance(a, r) > 0
    assert energy.kw_distance(a, r) == pytest.approx(
        energy.kw_distance(r, a)
    )


# --------------------------------------------------------------------------- #
# Capacitor.
# --------------------------------------------------------------------------- #


def test_capacitor_capacity_50mF():
    cap = energy.Capacitor()  # paper default: 50 mF, 1.8-3.3 V
    expected = 0.5 * 0.05 * (3.3 ** 2 - 1.8 ** 2)
    assert cap.capacity_j == pytest.approx(expected)


@given(
    st.lists(st.tuples(st.booleans(), st.floats(0, 0.2)), min_size=1,
             max_size=60)
)
@settings(max_examples=50, deadline=None)
def test_capacitor_invariants(ops):
    cap = energy.Capacitor(capacitance_f=0.01)
    for is_charge, amount in ops:
        if is_charge:
            stored = cap.charge(amount)
            assert 0.0 <= stored <= amount + 1e-12
        else:
            ok = cap.discharge(amount)
            if not ok:
                assert cap.energy_j < amount
        assert -1e-12 <= cap.energy_j <= cap.capacity_j + 1e-12


def test_optimal_capacitance_formula():
    # C = sqrt(2 P dT / V^2), paper §8.6
    c = energy.optimal_capacitance(0.5, 2.0, v=3.3)
    assert c == pytest.approx(np.sqrt(2 * 0.5 * 2.0 / 3.3 ** 2))


# --------------------------------------------------------------------------- #
# Schedulability (paper §5.3).
# --------------------------------------------------------------------------- #


def test_expected_outage_geometric():
    assert energy.expected_outage_slots(0.5) == pytest.approx(1.0)
    assert energy.expected_outage_slots(0.9) == pytest.approx(9.0)
    assert energy.expected_outage_slots(0.0) == pytest.approx(0.0)


def test_min_energy_task_period():
    # T_E >= (eta/(1-eta)) / (1 - U)
    t = energy.min_energy_task_period(0.5, 0.5)
    assert t == pytest.approx(2.0)
    assert energy.min_energy_task_period(0.5, 1.0) == float("inf")


@given(st.floats(0.0, 0.95), st.floats(0.01, 0.99), st.floats(0.1, 100.0))
@settings(max_examples=60, deadline=None)
def test_schedulability_consistent(eta, util, period):
    ok = energy.is_schedulable([util], eta, period)
    # schedulable iff the N+1-task utilisation test holds
    expected = util + energy.expected_outage_slots(eta) / period <= 1.0
    assert ok == expected
