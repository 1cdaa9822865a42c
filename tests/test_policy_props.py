"""Hypothesis property tests for the shared priority math in
:mod:`repro.core.policy` — the single source of truth behind the scalar
simulator, the vmapped fleet path and the Pallas kernel.

Properties:

* priority monotonicity — a closer deadline strictly raises the EDF key;
  under a fixed laxity, *lower* utility (less classifier confidence) raises
  zeta (Eq. 6 spends ``1 - beta * psi``: confident jobs can afford to
  wait), and the mandatory flag adds exactly gamma = 1;
* NEG-sentinel dominance — inactive slots and EDF-M optional work sit at
  the NEG floor, strictly below any bounded active/mandatory score and
  below the idle thresholds;
* ``exit_test`` strict-inequality consistency with
  :func:`repro.core.utility.calibrate_threshold` (a margin exactly at the
  threshold does NOT exit, matching the calibration curve's ``margin > t``);
* float-vs-jnp-vs-``(D, Q)``-array agreement — the same expressions give
  the same numbers for python scalars, jnp scalars and batched arrays.
"""
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

import jax.numpy as jnp

from repro.core import policy as P
from repro.core.kmeans import UnitClassifier, classify
from repro.core.utility import calibrate_threshold

# bounded, finite operating ranges (scores must stay far above NEG)
finite = dict(allow_nan=False, allow_infinity=False)
laxities = st.floats(-5.0, 50.0, **finite)
deadlines = st.floats(0.0, 1e3, **finite)
releases = st.floats(0.0, 1e3, **finite)
utilities = st.floats(0.0, 1.0, **finite)
alphas = st.floats(1e-3, 2.0, **finite)
betas = st.floats(0.0, 2.0, **finite)
etas = st.floats(0.0, 1.0, **finite)
energies = st.floats(0.0, 1.0, **finite)


# --------------------------------------------------------------------------- #
# Monotonicity.
# --------------------------------------------------------------------------- #


@given(deadlines, deadlines, releases)
@settings(max_examples=50, deadline=None)
def test_edf_key_closer_deadline_wins(d1, d2, release):
    """Strictly earlier deadline => strictly higher EDF key (the release
    tie-break perturbation must never overturn a genuine deadline gap)."""
    lo, hi = sorted((d1, d2))
    if hi - lo < 1e-3:   # below the documented _TIE * release resolution
        hi = lo + 1e-3
    assert P.edf_key(lo, release) > P.edf_key(hi, release)


@given(deadlines, releases, releases)
@settings(max_examples=50, deadline=None)
def test_edf_key_deadline_tie_breaks_by_release(deadline, r1, r2):
    lo, hi = sorted((r1, r2))
    if hi - lo < 1e-3:
        hi = lo + 1e-3
    assert P.edf_key(deadline, lo) > P.edf_key(deadline, hi)


@given(laxities, utilities, utilities, alphas, betas)
@settings(max_examples=50, deadline=None)
def test_zeta_lower_utility_higher_priority(laxity, u1, u2, alpha, beta):
    """Eq. 6 spends (1 - beta * psi): under a fixed laxity the LESS
    confident job ranks at least as high, strictly when beta > 0."""
    lo, hi = sorted((u1, u2))
    z_confident = P.zeta_priority(laxity, hi, True, alpha, beta)
    z_unsure = P.zeta_priority(laxity, lo, True, alpha, beta)
    assert z_unsure >= z_confident
    if beta * (hi - lo) > 1e-9:
        assert z_unsure > z_confident


@given(laxities, laxities, utilities, alphas, betas)
@settings(max_examples=50, deadline=None)
def test_zeta_smaller_laxity_higher_priority(l1, l2, util, alpha, beta):
    lo, hi = sorted((l1, l2))
    if hi - lo < 1e-6:
        hi = lo + 1e-6
    assert (P.zeta_priority(lo, util, True, alpha, beta)
            > P.zeta_priority(hi, util, True, alpha, beta))


@given(laxities, utilities, alphas, betas)
@settings(max_examples=50, deadline=None)
def test_zeta_mandatory_adds_exactly_gamma(laxity, util, alpha, beta):
    m = P.zeta_priority(laxity, util, True, alpha, beta)
    o = P.zeta_priority(laxity, util, False, alpha, beta)
    assert m - o == pytest.approx(1.0)


@given(laxities, utilities, alphas, betas, etas, energies)
@settings(max_examples=50, deadline=None)
def test_zeta_intermittent_gate(laxity, util, alpha, beta, eta, energy):
    """Eq. 7: with the energy gate closed, optional work scores exactly 0
    and mandatory work keeps the gamma-less Eq. 6 base; with it open, both
    recover Eq. 6 (minus gamma for optional units)."""
    e_opt = 0.5
    z6 = P.zeta_priority(laxity, util, True, alpha, beta)   # base + gamma
    z7m = P.zeta_intermittent_priority(laxity, util, True, alpha, beta,
                                       eta, energy, e_opt)
    z7o = P.zeta_intermittent_priority(laxity, util, False, alpha, beta,
                                       eta, energy, e_opt)
    if eta * energy >= e_opt:
        assert z7m == pytest.approx(z6)
        assert z7o == pytest.approx(z6 - 1.0)
    else:
        assert z7o == 0.0
        assert z7m == pytest.approx(z6 - 1.0)


# --------------------------------------------------------------------------- #
# NEG-sentinel dominance.
# --------------------------------------------------------------------------- #


@given(deadlines, releases, utilities, st.integers(0, 3))
@settings(max_examples=50, deadline=None)
def test_neg_sentinel_dominance(deadline, release, util, policy_id):
    """Inactive slots are pinned to NEG and can never outrank an active
    slot with bounded inputs, under every policy; the idle threshold sits
    strictly above NEG so an all-inactive queue never gets picked."""
    active = jnp.array([1.0, 0.0])
    args = dict(
        policy_id=jnp.int32(policy_id),
        active=active,
        laxity=jnp.array([deadline, deadline]),
        release=jnp.array([release, release]),
        utility=jnp.array([util, util]),
        mandatory=jnp.array([1.0, 1.0]),
        alpha=jnp.float32(0.5), beta=jnp.float32(1.0),
        eta=jnp.float32(0.8), energy=jnp.float32(0.9),
        e_opt=jnp.float32(0.5), persistent=jnp.float32(0.0),
    )
    scores, thr = P.policy_scores(**args, task_rank=jnp.array([0.0, 0.0]))
    # the sentinel survives the f32 round-trip (compare in f32 terms)
    assert float(scores[1]) == pytest.approx(P.NEG, rel=1e-6)
    assert float(scores[0]) > 0.5 * P.NEG
    if policy_id != 0:   # deadline-keyed policies idle only on empty queues
        assert float(thr) < 0.4 * P.NEG
        assert float(scores[0]) > float(thr)
    # all-inactive queue: nothing clears the threshold
    scores0, thr0 = P.policy_scores(
        **{**args, "active": jnp.zeros(2)}, task_rank=jnp.zeros(2))
    assert float(jnp.max(scores0)) <= float(thr0)


@given(deadlines, releases, deadlines, releases)
@settings(max_examples=50, deadline=None)
def test_edfm_optional_work_never_schedulable(d_opt, r_opt, d_mand, r_mand):
    """EDF-M pins optional (post-exit) work at NEG: any mandatory slot with
    bounded deadline/release dominates it."""
    opt = P.edfm_key(d_opt, r_opt, False)
    mand = P.edfm_key(d_mand, r_mand, True)
    assert opt == P.NEG
    assert mand > opt


def test_rr_key_task_rotation_dominates_release():
    """The task-rotation rank outweighs any in-horizon release gap, and
    rank 0 degenerates to the plain FIFO key bit-for-bit."""
    assert P.rr_key(123.25, 0.0) == -123.25
    # a task one rotation step closer wins despite a much older release
    assert P.rr_key(999.0, 0.0) > P.rr_key(0.0, 1.0)
    # within a task (same rank), FIFO by release
    assert P.rr_key(1.0, 2.0) > P.rr_key(5.0, 2.0)


# --------------------------------------------------------------------------- #
# exit_test ↔ calibrate_threshold strict-inequality consistency.
# --------------------------------------------------------------------------- #


@given(st.integers(0, 10_000))
@settings(max_examples=10, deadline=None)
def test_exit_test_matches_calibration_curve(seed):
    """calibrate_threshold's trade-off curve is computed with the strict
    ``margin > t`` rule; re-evaluating exit_test on the same margins must
    reproduce every curve point's exit fraction — including thresholds that
    sit exactly on a margin value (quantiles of the margins themselves),
    where a >= rule would disagree."""
    rng = np.random.default_rng(seed)
    n, d, k = 64, 4, 3
    uc = UnitClassifier(
        centroids=jnp.asarray(rng.normal(size=(k, d)), jnp.float32),
        labels=jnp.arange(k, dtype=jnp.int32) % 2,
        feature_idx=jnp.arange(d, dtype=jnp.int32),
        counts=jnp.ones((k,), jnp.float32),
        threshold=jnp.float32(0.1),
    )
    feats = rng.normal(size=(n, d)).astype(np.float32)
    labels = rng.integers(0, 2, size=n)
    thr, curve = calibrate_threshold(uc, feats, labels, grid=12)
    margin = np.asarray(classify(uc, jnp.asarray(feats))[4])
    for t, frac, _acc in curve:
        assert np.mean(np.asarray(P.exit_test(margin, t))) == (
            pytest.approx(frac))
    # the chosen threshold comes from the curve and obeys the same rule
    assert float(thr) in [t for t, _, _ in curve]
    # strictness at the boundary: a margin exactly at the threshold stays
    assert not bool(P.exit_test(float(thr), float(thr)))


# --------------------------------------------------------------------------- #
# float vs jnp vs (D, Q) array agreement.
# --------------------------------------------------------------------------- #


@given(st.integers(0, 10_000))
@settings(max_examples=10, deadline=None)
def test_policy_scores_scalar_vs_batched_agreement(seed):
    """One (D, Q) policy_scores call must agree elementwise with D*Q python
    float evaluations of the underlying priority functions — the guarantee
    that lets the scalar simulator, the vmapped fleet and the Pallas kernel
    share one implementation."""
    rng = np.random.default_rng(seed)
    D, Q = 4, 3
    laxity = rng.uniform(-2, 20, (D, Q))
    release = rng.uniform(0, 30, (D, Q))
    util = rng.uniform(0, 1, (D, Q))
    mand = rng.integers(0, 2, (D, Q)).astype(float)
    rank = rng.integers(0, 4, (D, Q)).astype(float)
    policy_id = rng.integers(0, 4, (D,))
    eta = rng.uniform(0.1, 1.0, (D,))
    energy = rng.uniform(0, 1, (D,))
    e_opt = rng.uniform(0.1, 0.9, (D,))
    persistent = rng.integers(0, 2, (D,)).astype(float)
    alpha, beta = 0.5, 1.0

    scores, _ = P.policy_scores(
        jnp.asarray(policy_id)[:, None], jnp.ones((D, Q)),
        jnp.asarray(laxity), jnp.asarray(release), jnp.asarray(util),
        jnp.asarray(mand), alpha, beta, jnp.asarray(eta)[:, None],
        jnp.asarray(energy)[:, None], jnp.asarray(e_opt)[:, None],
        jnp.asarray(persistent)[:, None], jnp.asarray(rank))
    scores = np.asarray(scores)

    for i in range(D):
        for q in range(Q):
            if policy_id[i] == 0:
                if persistent[i]:
                    want = P.zeta_priority(
                        laxity[i, q], util[i, q], mand[i, q], alpha, beta)
                else:
                    want = P.zeta_intermittent_priority(
                        laxity[i, q], util[i, q], mand[i, q], alpha, beta,
                        eta[i], energy[i], e_opt[i])
            elif policy_id[i] == 1:
                want = P.edf_key(laxity[i, q], release[i, q])
            elif policy_id[i] == 2:
                want = P.edfm_key(laxity[i, q], release[i, q], mand[i, q])
            else:
                want = P.rr_key(release[i, q], rank[i, q])
            # python-float and jnp-scalar evaluations agree with the batch
            assert scores[i, q] == pytest.approx(float(want), rel=1e-5)


@given(laxities, utilities, alphas, betas)
@settings(max_examples=25, deadline=None)
def test_priority_fns_float_jnp_agree(laxity, util, alpha, beta):
    """The pure functions accept python floats, numpy and jnp scalars
    interchangeably (the polymorphism the three call sites rely on)."""
    as_float = P.zeta_priority(laxity, util, True, alpha, beta)
    as_np = P.zeta_priority(np.float64(laxity), np.float64(util), True,
                            np.float64(alpha), np.float64(beta))
    as_jnp = P.zeta_priority(jnp.float32(laxity), jnp.float32(util), True,
                             jnp.float32(alpha), jnp.float32(beta))
    assert as_float == pytest.approx(float(as_np), rel=1e-6)
    assert as_float == pytest.approx(float(as_jnp), rel=1e-4, abs=1e-4)
    e = P.edf_key(laxity, util)
    assert float(P.edf_key(jnp.float32(laxity), jnp.float32(util))) == (
        pytest.approx(e, rel=1e-4, abs=1e-4))


@given(st.lists(st.integers(-3, 3), min_size=1, max_size=17),
       st.sampled_from([-np.inf, 0.0]))
@settings(max_examples=50, deadline=None)
def test_index_reductions_match_jnp(vals, fill):
    """The step core's Mosaic-safe index reductions give ``jnp.argmax`` /
    ``jnp.argmin``'s answer, first index on ties (small integer values
    force ties), including all-False masks and ``-inf`` rows."""
    from repro.core import step as S

    x = jnp.asarray(np.array(vals, np.float32))
    x = jnp.where(x < -2, fill, x)
    assert int(S.argmax_first(x)) == int(jnp.argmax(x))
    assert int(S.argmin_first(x)) == int(jnp.argmin(x))
    for mask in (x > 0, x > 99):
        assert int(S.first_true(mask)) == int(jnp.argmax(mask))
