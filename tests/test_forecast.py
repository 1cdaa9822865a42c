"""Harvest-pattern forecasting (`repro.adapt.forecast`).

Four layers:

* kernel-dispatch parity: the fleet-shaped ``(D, W, F)`` classify/update
  entry points (:func:`repro.core.kmeans.classify_batch` /
  :func:`repro.core.kmeans.online_update`, backed by the padded Pallas
  wrappers in :mod:`repro.kernels.ops`) match a numpy oracle and run
  under ``jax.jit``;
* hypothesis property tests for the forecaster — the spawned cluster
  count never exceeds ``n_clusters`` (and member counts are monotone),
  predictions never leave the envelope of the (eta, supply) values fed in
  (they are convex combinations of observed per-window statistics), and
  the whole pipeline is deterministic: two forecasters fed the same
  stream agree exactly;
* integration: both controller compositions (feedback and forecast) run
  per-device over ``fleet.run_segments`` on a multi-device fleet spanning
  a CHRT ``clock_drift`` axis, producing per-device histories;
* the seeded nonstationary regression: on the solar -> RF -> occluded
  trace of ``examples/online_adapt.py``, the forecast-aware controller
  must beat the PR-4 feedback-only controller — anticipation dominates
  reaction once the regime cycle has been seen.
"""
from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hypothesis import given, settings
from hypothesis import strategies as st
from repro import adapt, fleet
from repro.core import energy, kmeans
from repro.core.scheduler import JobProfile, TaskSpec
from repro.fleet import grid as fgrid


# --------------------------------------------------------------------------- #
# Fleet-shaped kernel dispatch.
# --------------------------------------------------------------------------- #


def test_classify_batch_matches_numpy_oracle():
    rng = np.random.default_rng(0)
    x = rng.random((5, 3, 6)).astype(np.float32)      # (D, W, F)
    c = rng.random((4, 6)).astype(np.float32)
    idx, d1, d2, margin = kmeans.classify_batch(jnp.asarray(c),
                                                jnp.asarray(x))
    ref = np.abs(x[:, :, None, :] - c[None, None]).sum(-1)   # (D, W, k)
    assert idx.shape == (5, 3)
    np.testing.assert_array_equal(np.asarray(idx), ref.argmin(-1))
    np.testing.assert_allclose(np.asarray(d1), ref.min(-1), rtol=1e-5)
    part = np.partition(ref, 1, axis=-1)
    np.testing.assert_allclose(np.asarray(d2), part[..., 1], rtol=1e-5)
    assert np.all(np.asarray(margin) >= 0.0)
    # 2-D batches work too (the per-segment online path)
    idx2, *_ = kmeans.classify_batch(jnp.asarray(c), jnp.asarray(x[:, 0]))
    np.testing.assert_array_equal(np.asarray(idx2), np.asarray(idx)[:, 0])


def test_online_update_matches_weighted_mean_and_ignores_negatives():
    rng = np.random.default_rng(1)
    x = rng.random((7, 6)).astype(np.float32)
    c = rng.random((3, 6)).astype(np.float32)
    assign = np.array([0, 0, 1, -1, 1, 2, 0], np.int32)
    w = 4.0
    new_c, new_n = kmeans.online_update(
        jnp.asarray(c), jnp.zeros(3), jnp.asarray(x), jnp.asarray(assign), w)
    for j in range(3):
        members = x[assign == j]
        want = (w * c[j] + members.sum(0)) / (w + len(members))
        np.testing.assert_allclose(np.asarray(new_c)[j], want, rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(new_n), [3, 2, 1])


def test_batched_entry_points_are_jit_safe():
    @jax.jit
    def step(c, n, x):
        idx, *_ = kmeans.classify_batch(c, x)
        return kmeans.online_update(c, n, x, idx, 8.0)

    rng = np.random.default_rng(2)
    c, n = step(jnp.asarray(rng.random((4, 6), ), jnp.float32),
                jnp.zeros(4),
                jnp.asarray(rng.random((3, 5, 6)), jnp.float32))
    assert c.shape == (4, 6) and float(jnp.sum(n)) == 15.0


# --------------------------------------------------------------------------- #
# Forecaster properties.
# --------------------------------------------------------------------------- #


def _feed(fc: adapt.HarvestForecaster, stream: np.ndarray) -> None:
    """Feed an (n_steps, D, F) feature stream window by window."""
    for feats in stream:
        fc.observe(feats.astype(np.float32), feats[:, 0], feats[:, 2])


def _stream(draws, n_steps: int, n_dev: int) -> np.ndarray:
    vals = np.asarray(draws, np.float64).reshape(n_steps, n_dev, 1)
    # six O(1) feature columns derived deterministically from one draw
    cols = [vals, 1.0 - vals, vals ** 2, 0.5 * vals, vals ** 3, 1.0 - vals ** 2]
    return np.concatenate(cols, axis=-1)


@settings(max_examples=25, deadline=None)
@given(
    st.lists(st.floats(min_value=0.0, max_value=1.0), min_size=2,
             max_size=24),
    st.integers(min_value=1, max_value=6),
)
def test_cluster_count_bounded_and_counts_monotone(draws, n_clusters):
    fc = adapt.HarvestForecaster(n_clusters=n_clusters, spawn_radius=0.4)
    stream = _stream(draws, len(draws), 1)
    prev_counts = np.zeros(n_clusters)
    for feats in stream:
        fc.observe(feats.astype(np.float32), feats[:, 0], feats[:, 2])
        assert 1 <= fc.n_born <= n_clusters
        assert fc.centroids.shape == (n_clusters, feats.shape[-1])
        counts = np.asarray(fc.counts, np.float64)
        assert np.all(counts >= prev_counts - 1e-6)
        prev_counts = counts
    assert fc.stats_n.sum() == pytest.approx(len(draws))


@settings(max_examples=25, deadline=None)
@given(
    st.lists(st.floats(min_value=0.0, max_value=1.0), min_size=2,
             max_size=24),
    st.floats(min_value=0.5, max_value=8.0),
)
def test_prediction_bounded_by_observed_range(draws, horizon):
    """Predicted (eta, supply) are convex combinations of the per-window
    statistics fed to observe(), so they stay in the observed envelope."""
    fc = adapt.HarvestForecaster(n_clusters=3, spawn_radius=0.4)
    stream = _stream(draws, len(draws), 1)
    _feed(fc, stream)
    pred = fc.predict(horizon)
    etas, supplies = stream[:, :, 0], stream[:, :, 2]
    assert etas.min() - 1e-9 <= pred["eta"][0] <= etas.max() + 1e-9
    assert supplies.min() - 1e-9 <= pred["supply"][0] <= supplies.max() + 1e-9
    assert 0.0 <= pred["confidence"][0] <= 1.0
    assert 0.0 <= pred["w_stay"][0] <= 1.0


@settings(max_examples=15, deadline=None)
@given(st.integers(min_value=0, max_value=2 ** 31 - 1))
def test_forecaster_deterministic_under_fixed_seed(seed):
    """Two forecasters fed the bit-identical stream agree exactly — the
    whole pipeline (featurize, Pallas classify/update, host bookkeeping)
    has no hidden randomness."""
    rng = np.random.default_rng(seed)
    stream = rng.random((10, 2, 6))
    fc1 = adapt.HarvestForecaster(n_clusters=3)
    fc2 = adapt.HarvestForecaster(n_clusters=3)
    _feed(fc1, stream)
    _feed(fc2, stream)
    np.testing.assert_array_equal(fc1.centroids, fc2.centroids)
    np.testing.assert_array_equal(fc1.trans, fc2.trans)
    p1, p2 = fc1.predict(2.0), fc2.predict(2.0)
    for key in p1:
        np.testing.assert_array_equal(p1[key], p2[key])


def test_forecaster_validation_and_empty_predict():
    with pytest.raises(ValueError, match="n_clusters"):
        adapt.HarvestForecaster(n_clusters=0)
    fc = adapt.HarvestForecaster()
    pred = fc.predict()
    assert pred["eta"].size == 0 and pred["confidence"].size == 0


def test_window_features_shapes_and_prior():
    harv = energy.Harvester("h", 0.9, 0.9, 0.05)
    ev = np.stack([harv.sample_events(np.random.default_rng(s), 60, init=1)
                   for s in range(3)]).astype(np.float32)
    f = adapt.window_features(ev, t_end=40.0, slot_s=1.0, window_s=10.0,
                              n_windows=3)
    assert f.shape == (3, 3, len(adapt.FEATURES))
    assert np.all(f >= 0.0) and np.all(np.isfinite(f))
    # nothing observed yet: the all-zero patternless prior
    f0 = adapt.window_features(ev, t_end=0.0, slot_s=1.0, window_s=10.0)
    assert np.all(f0 == 0.0)
    # windows ending before the trace starts are empty too — a negative
    # slice end must not wrap around and leak future slots into features
    ev_future = np.zeros((1, 60), np.float32)
    ev_future[:, 10:] = 1.0          # all the energy arrives after t_end
    f_early = adapt.window_features(ev_future, t_end=5.0, slot_s=1.0,
                                    window_s=10.0, n_windows=3)
    assert np.all(f_early[:, :2] == 0.0)      # the two pre-trace windows
    assert f_early[0, 2, adapt.FEATURES.index("amp")] == 0.0


def test_duration_model_anticipates_regime_switch():
    """On a deterministic alternating regime the forecaster learns the stay
    duration and shifts its supply prediction toward the successor before
    the switch happens."""
    fc = adapt.HarvestForecaster(n_clusters=2)
    rich = np.array([[0.9, 0.9, 0.9, 0.5, 0.1, 0.1]], np.float32)
    lean = np.array([[0.1, 0.1, 0.1, 0.05, 0.6, 0.4]], np.float32)
    preds = []
    for t in range(40):
        feats = rich if (t // 10) % 2 == 0 else lean
        fc.observe(feats, feats[:, 0], feats[:, 2] * 0.06)
        preds.append(fc.predict(horizon=2.0))
    assert fc.n_born == 2
    # learned stay duration: exactly 10 observations
    assert fc.dur_sum[:2] / np.maximum(fc.dur_n[:2], 1) == pytest.approx(
        [10.0, 10.0])
    # mid-stay (t=24, rich regime): predict the rich supply
    assert preds[24]["supply"][0] == pytest.approx(0.9 * 0.06, rel=0.05)
    # end of stay (t=29): prediction has moved toward the lean successor
    assert preds[29]["supply"][0] < 0.5 * preds[24]["supply"][0]


# --------------------------------------------------------------------------- #
# Integration: controller compositions over run_segments (with drift axis).
# --------------------------------------------------------------------------- #


def _drift_fleet(horizon: float = 60.0):
    """A 3-device fleet sharing one bursty harvester but spanning a CHRT
    clock-drift axis."""
    n_units = 4
    prof = JobProfile(np.linspace(0.1, 0.5, n_units),
                      np.array([False, True, True, True]),
                      np.ones(n_units, bool))
    task = TaskSpec(task_id=0, period=1.0, deadline=2.0,
                    unit_time=np.full(n_units, 0.1),
                    unit_energy=np.full(n_units, 5e-3),
                    profiles=[prof] * (int(horizon) + 2))
    harv = energy.Harvester("h", 0.9, 0.9, 0.05)
    devices = [
        fgrid.device_config(task, harv, 0.5, energy.Capacitor(),
                            policy="zygarde", horizon=horizon,
                            events=fgrid.sample_events(harv, horizon, s),
                            clock_drift=drift)
        for s, drift in enumerate((0.0, 0.01, -0.01))
    ]
    statics = fleet.FleetStatics(dt=0.025, horizon=horizon, slot_s=1.0)
    return fgrid.stack_configs(devices), statics


@pytest.mark.parametrize("arm", ["feedback", "forecast"])
def test_controllers_run_per_device_under_clock_drift(arm):
    cfg, statics = _drift_fleet()
    if arm == "feedback":
        adapter = adapt.OnlineAdapter(statics, cfg, window_s=15.0)
    else:
        adapter = adapt.OnlineAdapter(statics, cfg, controllers=[
            adapt.EtaController(window_s=15.0),
            adapt.ForecastController(window_s=8.0, horizon_s=10.0),
        ])
    res, _ = fleet.run_segments(cfg, statics, 12, hook=adapter.hook)
    assert len(adapter.history) == 12
    last = adapter.history[-1]
    d = cfg.n_devices
    assert last["eta_hat"].shape == (d,)
    assert last["e_opt_frac"].shape == (d,)
    assert adapter.eta_hat.shape == (d,)
    assert np.all(np.asarray(res.released) > 0)
    assert np.all(np.isfinite(np.asarray(res.correct, np.float64)))
    if arm == "forecast":
        assert last["cluster"].shape == (d,)
        assert np.all((last["confidence"] >= 0) & (last["confidence"] <= 1))
        # the tunable exit-threshold substrate was actually engaged
        assert any(h["depth"] is not None for h in adapter.history
                   if "depth" in h)


def test_controller_list_reuse_resets_state_between_adapters():
    """Constructing a second adapter over the same controller list starts
    fresh trajectories: the eta estimator and the forecaster are rebuilt by
    reset(), not carried over (an injected forecaster IS carried — that's
    the warm-start path)."""
    cfg, statics = _drift_fleet()
    controllers = [adapt.EtaController(), adapt.ForecastController()]
    adapter = adapt.OnlineAdapter(statics, cfg, controllers=controllers)
    fleet.run_segments(cfg, statics, 2, hook=adapter.hook)
    assert adapter.eta_hat is not None
    assert controllers[1].forecaster.n_obs > 0
    adapter2 = adapt.OnlineAdapter(statics, cfg, controllers=controllers)
    assert adapter2.eta_hat is None
    assert controllers[1].forecaster.n_obs == 0
    # explicit injection keeps the learned statistics across trajectories
    warm = adapt.HarvestForecaster()
    fc = adapt.ForecastController(forecaster=warm)
    adapt.OnlineAdapter(statics, cfg, controllers=[fc])
    assert fc.forecaster is warm


def test_forecast_controller_falls_back_to_feedback_before_confidence():
    """With an unconfident forecaster (first segments), the forecast
    controller's E_opt must equal the feedback controller's exactly — the
    blend degrades to the PR-4 law, so the anticipatory arm can never be
    worse during warmup."""
    cfg, statics = _drift_fleet()
    fb = adapt.OnlineAdapter(statics, cfg)
    fc = adapt.OnlineAdapter(statics, cfg, controllers=[
        adapt.EtaController(),
        adapt.ForecastController(conf_min=2.0),   # exit_thr never engages
    ])
    # run one segment each on identical inputs
    fleet.run_segments(cfg, statics, 2, hook=fb.hook)
    fleet.run_segments(cfg, statics, 2, hook=fc.hook)
    f0, c0 = fb.history[0], fc.history[0]
    # first segment: no transition statistics -> confidence 0 -> same E_opt
    assert c0["confidence"] == pytest.approx(np.zeros(cfg.n_devices))
    np.testing.assert_allclose(c0["e_opt_frac"], f0["e_opt_frac"], rtol=1e-9)
    np.testing.assert_allclose(c0["supply_hat"], f0["supply_hat"], rtol=1e-9)


# --------------------------------------------------------------------------- #
# The nonstationary regression: forecast beats feedback.
# --------------------------------------------------------------------------- #


def test_forecast_beats_feedback_on_nonstationary_trace(online_adapt_demo):
    """Pins the example's seeded win: once the solar -> RF -> occluded
    cycle has been observed, anticipating the next regime (banking the
    reserve and shrinking the mandatory prefix *before* the blackout)
    beats reacting to the current one.  Fully deterministic."""
    _, out = online_adapt_demo
    assert out["forecast"]["score"] >= out["online"]["score"] + 0.02
    # the anticipation mechanism actually engaged: confident clusters and
    # a moving mandatory/optional boundary
    conf = np.array([h["confidence"][0] for h in out["forecast_history"]])
    depth = np.array([h["depth"][0] for h in out["forecast_history"]])
    assert conf.max() > 0.8
    assert depth.max() > 0.3 and depth.min() < 0.05
    # fewer blackout misses than the reactive arm
    assert out["forecast"]["misses"] < out["online"]["misses"]
