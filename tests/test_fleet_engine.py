"""Scalar <-> fleet live-serving parity (the fleet_engine harness).

The vectorized live path (:class:`repro.serve.fleet_engine.FleetServeEngine`)
claims *bit-exactness* against the event-driven scalar
:class:`repro.serve.ServeEngine` on workloads where the two clocks
coincide: persistent power, charged start, unit times commensurate with
the fixed step.  These tests pin that contract — same units executed,
same exit units, same predictions, same margins (bitwise), same
scheduled/miss sets — across policies, adaptation on/off, device counts
and segmented scans, plus the row-classifier's bit-equality with the
scalar k-means/Pallas path.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import energy, kmeans as km
from repro.serve import FleetServeEngine, Request, ServeConfig, ServeEngine


def _persistent():
    return energy.Harvester("battery", 1.0, 0.0, 1.0)


def _fresh_model(trained_cnn, threshold=None):
    """A private AgileCNN (adaptation mutates ``bank`` in place); an
    optional uniform threshold override forces (or forbids) early exit."""
    from repro.core.agile import AgileCNN

    bank = [uc if threshold is None
            else uc._replace(threshold=jnp.float32(threshold))
            for uc in trained_cnn.bank]
    return AgileCNN(trained_cnn.cfg, trained_cnn.params, bank)


def _requests(ds, n, period):
    return [Request(ds.x_test[i], int(ds.y_test[i]), release=i * period)
            for i in range(n)]


def _cfg(policy, n, adapt, period=2.0, deadline=1.5):
    """The clock-commensurate parity recipe: dt=0.05 divides the 0.2s
    units, releases and deadlines; charged persistent power removes the
    energy gate's dependence on harvest-sample timing."""
    return ServeConfig(policy=policy, period=period, deadline=deadline,
                       horizon=n * period + 2.0, adapt=adapt,
                       start_charged=True, sim_dt=0.05)


def _scalar_run(trained_cnn, cfg, reqs, threshold):
    eng = ServeEngine([_fresh_model(trained_cnn, threshold)], _persistent(),
                      eta=1.0, config=cfg)
    res = eng.run([reqs])
    jobs = eng.jobs_
    units = np.array([j.unit for j in jobs])
    sched = np.array([0 <= j.mandatory_done_time <= j.deadline
                      for j in jobs])
    profs = eng.profiles_[0]
    pred = np.array([p._preds[u - 1] if u > 0 else -1
                     for p, u in zip(profs, units)])
    margin = np.array([p._margins[u - 1] if u > 0 else 0.0
                       for p, u in zip(profs, units)], np.float32)
    return res, units, sched, pred, margin


def _fleet_run(trained_cnn, cfg, reqs, threshold, n_devices=1, **kw):
    eng = FleetServeEngine([_fresh_model(trained_cnn, threshold)],
                           _persistent(), eta=1.0, config=cfg,
                           feature_batch=1)
    return eng, eng.run([reqs], n_devices=n_devices, **kw)


def test_row_classifier_matches_kmeans_classify(trained_cnn, mnist_tiny):
    """classify_unit (plain-jnp row math on the padded stacked bank) is
    bitwise the scalar path: km.classify -> Pallas l1_topk2 (interpret)."""
    from repro.serve.fleet_engine import ServeTables, classify_unit

    model = _fresh_model(trained_cnn)
    eng = FleetServeEngine([model], _persistent(), eta=1.0,
                           config=_cfg("zygarde", 4, False))
    _, _, tables, _, _ = eng.build([_requests(mnist_tiny, 4, 2.0)],
                                   n_devices=1)
    tables = ServeTables(*(jax.tree.map(np.asarray, tables)))
    feats = model.unit_features([r.x for r in _requests(mnist_tiny, 4, 2.0)])
    for u, uc in enumerate(model.bank):
        pred_s, _, _, idx_s, margin_s = km.classify(uc, jnp.asarray(feats[u]))
        for j in range(4):
            m, ci, p = classify_unit(eng.bank0, tables, jnp.int32(0),
                                     jnp.int32(u), jnp.int32(j))
            assert int(ci) == int(idx_s[j])
            assert int(p) == int(pred_s[j])
            assert np.float32(m) == np.float32(margin_s[j])


@pytest.mark.parametrize("policy", ["zygarde", "edf"])
@pytest.mark.parametrize("adapt", [False, True])
def test_live_parity_scalar_vs_fleet(trained_cnn, mnist_tiny, policy, adapt):
    """One device, live fleet == scalar engine bit-for-bit: units, exits,
    schedule, predictions and margins.  ``adapt=True`` lowers the bank
    thresholds so every job exits early and adapts the centroids — the
    hardest case (classification at step t depends on every earlier
    adaptation); under EDF adaptation still fires at the first bank pass
    (the q_apass latch) even though EDF never exits early."""
    n = 6
    thr = 0.02 if adapt else None
    cfg = _cfg(policy, n, adapt)
    reqs = _requests(mnist_tiny, n, cfg.period)
    res, units, sched, pred, margin = _scalar_run(trained_cnn, cfg, reqs,
                                                  thr)
    _, fres = _fleet_run(trained_cnn, cfg, reqs, thr)
    assert np.array_equal(units, fres.units[0, 0, :n])
    assert np.array_equal(sched, fres.sched[0, 0, :n])
    assert np.array_equal(pred, fres.pred[0, 0, :n])
    assert np.array_equal(margin, fres.margin[0, 0, :n])
    f = fres.fleet
    assert int(res.scheduled) == int(f.scheduled[0])
    assert int(res.correct) == int(f.correct[0])
    assert int(res.deadline_misses) == int(f.deadline_misses[0])
    assert int(res.units_executed) == int(f.units_executed[0])
    if adapt:
        assert (fres.exit_unit[0, 0, :n] >= 0).all()


def test_live_parity_many_devices(trained_cnn, mnist_tiny):
    """D=4 devices on the same stream: every device reproduces the scalar
    run (per-device banks adapt independently from the same start)."""
    n = 5
    cfg = _cfg("zygarde", n, True)
    reqs = _requests(mnist_tiny, n, cfg.period)
    _, units, sched, pred, margin = _scalar_run(trained_cnn, cfg, reqs, 0.02)
    _, fres = _fleet_run(trained_cnn, cfg, reqs, 0.02, n_devices=4)
    for d in range(4):
        assert np.array_equal(units, fres.units[d, 0, :n])
        assert np.array_equal(sched, fres.sched[d, 0, :n])
        assert np.array_equal(pred, fres.pred[d, 0, :n])
        assert np.array_equal(margin, fres.margin[d, 0, :n])


def test_segmented_scan_bit_identity(trained_cnn, mnist_tiny):
    """n_segments=3 (carry materialised at boundaries) is bit-identical to
    the monolithic scan — the checkpoint/resume contract."""
    n = 5
    cfg = _cfg("zygarde", n, True)
    reqs = _requests(mnist_tiny, n, cfg.period)
    _, f1 = _fleet_run(trained_cnn, cfg, reqs, 0.02, n_segments=1)
    _, f3 = _fleet_run(trained_cnn, cfg, reqs, 0.02, n_segments=3)
    for a, b in [(f1.units, f3.units), (f1.pred, f3.pred),
                 (f1.margin, f3.margin), (f1.sched, f3.sched),
                 (f1.exit_unit, f3.exit_unit)]:
        assert np.array_equal(a, b)
    for la, lb in zip(jax.tree.leaves(f1.carry), jax.tree.leaves(f3.carry)):
        assert np.array_equal(np.asarray(la), np.asarray(lb))


@pytest.mark.parametrize("threshold", [None, 10.0])
def test_miss_sets_match_under_overload(trained_cnn, mnist_tiny, threshold):
    """Deadline tighter than full execution (0.7s vs 0.8s of units): the
    scalar and fleet paths agree on exactly *which* jobs miss.  (Unit
    counts are outside the parity domain here — at expiry the event loop
    lets the in-flight unit run to its boundary while the fixed-step path
    drops the job at the deadline tick — but the miss *set* must match.)
    With the utility test disabled (threshold=10) nothing can exit early,
    so every released job must miss on both sides."""
    n = 5
    cfg = _cfg("zygarde", n, False, period=1.0, deadline=0.7)
    reqs = _requests(mnist_tiny, n, cfg.period)
    res, _, sched, _, _ = _scalar_run(trained_cnn, cfg, reqs, threshold)
    _, fres = _fleet_run(trained_cnn, cfg, reqs, threshold)
    assert np.array_equal(sched, fres.sched[0, 0, :n])
    assert int(res.deadline_misses) == int(fres.fleet.deadline_misses[0])
    if threshold == 10.0:
        assert not sched.any()
        assert int(res.deadline_misses) == n


def test_shared_bank_collaborative_adaptation(trained_cnn, mnist_tiny):
    """bank_mode='shared': one global bank absorbs every device's exits
    (collaborative semantics — documented as distinct from the sequential
    scalar updates, so aggregates, not bitwise parity)."""
    n = 4
    cfg = _cfg("zygarde", n, True)
    reqs = _requests(mnist_tiny, n, cfg.period)
    eng = FleetServeEngine([_fresh_model(trained_cnn, 0.02)], _persistent(),
                           eta=1.0, config=cfg, bank_mode="shared",
                           feature_batch=1)
    fres = eng.run([reqs], n_devices=3)
    assert int(np.asarray(fres.fleet.released).sum()) == 3 * n
    assert (fres.exit_unit[:, 0, :n] >= 0).all()
    # the single shared bank gained mass (counts only ever grow)
    assert (np.asarray(fres.carry.bank.counts).sum()
            > float(np.asarray(eng.bank0.counts).sum()))


@pytest.mark.parametrize("passing", ["none", "one", "same_cluster"])
@pytest.mark.parametrize("bank_mode", ["per-device", "shared"])
def test_refreshed_view_equals_a_fresh_select(trained_cnn, bank_mode,
                                              passing):
    """The adapting scan carries the bank's selected columns and refreshes
    only the rows an adaptation moved: after one adaptation of a random
    bank, the refreshed view is ``select_centroids`` of the updated bank,
    bit for bit — with no device passing, one, and several on the same
    (task, unit) and cluster."""
    from repro.serve.fleet_engine import refresh_cent_rows, select_centroids

    D = 4
    eng = FleetServeEngine([_fresh_model(trained_cnn) for _ in range(2)],
                           _persistent(), eta=1.0,
                           config=_cfg("zygarde", 2, True),
                           bank_mode=bank_mode)
    K, U, C, F = eng.bank0.centroids.shape
    Sn = 7
    shared = bank_mode == "shared"
    lead = () if shared else (D,)
    rng = np.random.default_rng(["none", "one", "same_cluster"].index(
        passing) + 10 * shared)
    bank = eng.bank0._replace(
        centroids=jnp.asarray(rng.normal(size=lead + (K, U, C, F)),
                              jnp.float32),
        counts=jnp.asarray(rng.integers(1, 40, lead + (K, U, C)),
                           jnp.float32))
    # each classifier's columns lie inside its unit's feature width
    fidx = jnp.asarray([[rng.integers(0, fu, Sn) for fu in dims]
                        for dims in eng.meta.feat_dim], jnp.int32)
    x_full = jnp.asarray(rng.normal(size=(D, F)), jnp.float32)
    tk = rng.integers(0, K, D)
    u = rng.integers(0, U, D)
    ci = rng.integers(0, C, D)
    first_pass = np.zeros(D, bool)
    if passing == "one":
        # a pass at the first unit propagates to every deeper unit's row
        first_pass[1], u[1] = True, 0
    elif passing == "same_cluster":
        first_pass[:3] = True
        tk[:3], u[:3], ci[:3] = tk[0], u[0], ci[0]
    args = [jnp.asarray(a, jnp.int32) for a in (tk, u, ci)]
    fp = jnp.asarray(first_pass)

    @jax.jit
    def adapt_and_refresh(bank):
        view = select_centroids(bank.centroids, fidx)
        view = view.reshape(view.shape[:-4] + (-1, Sn))
        if shared:
            new = eng._adapt_shared(bank, x_full, *args, fp)
        else:
            new = jax.vmap(eng._adapt_per_device)(bank, x_full, *args, fp)
        fresh = select_centroids(new.centroids, fidx).reshape(view.shape)
        return view, refresh_cent_rows(view, new.centroids, fidx, args[0],
                                       args[2], fp), fresh

    view, refreshed, fresh = map(np.asarray, adapt_and_refresh(bank))
    assert np.array_equal(refreshed, fresh)
    # the adaptation moved the view exactly where some device passed
    assert np.array_equal(view, fresh) == (passing == "none")
