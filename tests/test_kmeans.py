"""Semi-supervised k-means classifier bank (paper §4.3)."""
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

import jax
import jax.numpy as jnp

from repro.core import kmeans as km


def blobs(n=120, d=20, k=4, sep=6.0, seed=0):
    rng = np.random.default_rng(seed)
    protos = rng.normal(size=(k, d)) * sep
    y = rng.integers(0, k, n)
    x = protos[y] + rng.normal(size=(n, d))
    return x.astype(np.float32), y.astype(np.int64)


def test_fit_and_classify_separable():
    x, y = blobs()
    uc = km.fit_unit_classifier(x, y, n_sel=20)
    pred, d1, d2, idx, margin = km.classify(uc, jnp.asarray(x))
    acc = (np.asarray(pred) == y).mean()
    assert acc > 0.95
    assert (np.asarray(d2) >= np.asarray(d1)).all()
    assert (np.asarray(margin) >= 0).all()


def test_select_k_best_finds_informative_dims():
    rng = np.random.default_rng(1)
    n = 400
    y = rng.integers(0, 2, n)
    x = rng.normal(size=(n, 10)).astype(np.float32)
    x[:, 3] += 5.0 * y  # only dim 3 carries signal
    idx = km.select_k_best(x, y, 1)
    assert list(idx) == [3]


def test_utility_test_threshold():
    x, y = blobs(sep=8.0)
    uc = km.fit_unit_classifier(x, y, n_sel=20, threshold=0.05)
    _, _, _, _, margin = km.classify(uc, jnp.asarray(x))
    passed = km.utility_test(uc, margin)
    assert float(jnp.mean(passed)) > 0.8  # well-separated data exits


def test_adapt_moves_centroid_toward_new_points():
    x, y = blobs(seed=2)
    uc = km.fit_unit_classifier(x, y, n_sel=20)
    shift = jnp.asarray(x[:8] + 10.0)  # distribution shift
    _, _, _, idx, _ = km.classify(uc, shift)
    new = km.adapt(uc, shift, idx, weight=4.0)
    moved = np.asarray(new.centroids) - np.asarray(uc.centroids)
    touched = np.unique(np.asarray(idx))
    assert np.abs(moved[touched]).max() > 0.1
    untouched = [j for j in range(uc.centroids.shape[0])
                 if j not in touched]
    if untouched:
        np.testing.assert_allclose(moved[untouched], 0.0, atol=1e-6)
    # counts grew only for touched clusters
    dc = np.asarray(new.counts) - np.asarray(uc.counts)
    assert dc.sum() == 8


@given(st.floats(1.0, 256.0))
@settings(max_examples=20, deadline=None)
def test_adapt_weight_bounds_motion(weight):
    """Weighted average: new centroid lies between old centroid and batch
    mean, closer to the old one for larger weight (paper §11.3)."""
    x, y = blobs(seed=3)
    uc = km.fit_unit_classifier(x, y, n_sel=20)
    pts = jnp.asarray(x[:6])
    idx = jnp.zeros((6,), jnp.int32)
    new = km.adapt(uc, pts, idx, weight=weight)
    old_c = np.asarray(uc.centroids[0])
    mean = np.asarray(pts.mean(0))
    got = np.asarray(new.centroids[0])
    lam = weight / (weight + 6.0)
    np.testing.assert_allclose(
        got, lam * old_c + (1 - lam) * mean, rtol=1e-4, atol=1e-4
    )


def test_propagate_matches_formula():
    """c^{i+1} = (1/r) sigma(W^{i+1} (r c^i)) for the touched clusters."""
    x, y = blobs(d=16, seed=4)
    uc0 = km.fit_unit_classifier(x, y, n_sel=16)
    rng = np.random.default_rng(5)
    W = jnp.asarray(rng.normal(size=(16, 16)).astype(np.float32))

    def unit_apply(f):
        return f @ W

    feats1 = np.maximum(x @ np.asarray(W), 0.0)
    uc1 = km.fit_unit_classifier(feats1, y, n_sel=16)
    touched = jnp.asarray([0, 2])
    out = km.propagate(uc0, uc1, unit_apply, touched)
    r = np.asarray(uc0.counts)[:, None]
    want = np.maximum((r * np.asarray(uc0.centroids)) @ np.asarray(W), 0) / r
    got = np.asarray(out.centroids)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(got[2], want[2], rtol=1e-4, atol=1e-4)
    # untouched clusters keep the target bank's centroids
    np.testing.assert_allclose(got[1], np.asarray(uc1.centroids)[1],
                               atol=1e-6)


def test_fit_bank_and_accuracy_monotone_layers(mnist_tiny, trained_cnn):
    """Deeper units should classify at least as well as the first unit on
    the training distribution (the layer-aware loss enforces this)."""
    from repro.models.cnn import cnn_forward_all

    feats = [
        np.asarray(f) for f in cnn_forward_all(
            trained_cnn.cfg, trained_cnn.params,
            jnp.asarray(mnist_tiny.x_train),
        )
    ]
    accs = km.bank_accuracy(trained_cnn.bank, feats, mnist_tiny.y_train)
    assert len(accs) == trained_cnn.cfg.n_units
    assert max(accs[1:]) >= accs[0] - 0.05
    assert accs[-1] > 1.5 / mnist_tiny.n_classes  # far above chance
