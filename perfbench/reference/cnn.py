"""Plain agile CNN of the paper's Table 3, and the benchmark's own model.

Every layer is one Zygarde unit: a 5x5 SAME convolution with bias, ReLU
and 2x2 max-pooling, or a fully connected layer with bias and ReLU.  A
unit's feature is its flattened (NHWC) output.  No import from the
program.

The weights (He-normal, then trained by :mod:`lib.train`) and the
per-unit k-means bank are made by the benchmark from the seeds the
configuration states, and handed both to the program and to the
reference: class-mean centroids over the most class-separating features
of a calibration set, and thresholds set for an exit accuracy on a
held-out set.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax


def unit_shapes(model: dict):
    """``[(kind, in_shape, out_shape, fan_in, fan_out)]`` per unit; conv
    shapes are ``(H, W, C)``, FC shapes ``(D,)``."""
    h, w, c = model["input_shape"]
    out = []
    for ch, k, pool in model["convs"]:
        ho, wo = (h // 2, w // 2) if pool else (h, w)
        out.append(("conv", (h, w, c), (ho, wo, ch), k * k * c, ch))
        h, w, c = ho, wo, ch
    d = h * w * c
    for f in model["fcs"]:
        out.append(("fc", (d,), (f,), d, f))
        d = f
    return out


def feature_dims(model: dict) -> list[int]:
    return [int(np.prod(s[2])) for s in unit_shapes(model)]


def init_params(model: dict, key):
    """He-normal weights, zero biases, in float32."""
    params = []
    keys = jax.random.split(key, len(unit_shapes(model)))
    for kk, (kind, shp_in, _, fan, out) in zip(keys, unit_shapes(model)):
        if kind == "conv":
            k = model["convs"][len(params)][1]
            wshape = (k, k, shp_in[2], out)
        else:
            wshape = (fan, out)
        params.append({"w": jax.random.normal(kk, wshape, jnp.float32)
                       * (2.0 / fan) ** 0.5,
                       "b": jnp.zeros((out,), jnp.float32)})
    return params


#: a ``precision`` for :func:`unit_apply`: float32 products as a TPU
#: computes them at ``lax.Precision.HIGH``, on any backend
BF16_3X = "bf16_3x"


def _bf16_parts(a):
    """A float32 array's bfloat16 high and low parts, kept in float32.
    ``reduce_precision`` and not a round trip through bfloat16: XLA may
    fold a float32 -> bfloat16 -> float32 pair away (the TPU compiler
    does), which would leave the high part unrounded."""
    hi = lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)
    return hi, lax.reduce_precision(a - hi, exponent_bits=8, mantissa_bits=7)


def _product(op, a, b, precision):
    """``op(a, b)``; at :data:`BF16_3X` each float32 operand is split into
    a bfloat16 high and low part and the three products hi*hi + hi*lo +
    lo*hi are summed in float32, the low*low term dropped (the three-pass
    algorithm of ``Precision.HIGH``).  Each product of bfloat16 values is
    exact in float32, so it is computed at ``HIGHEST``."""
    if precision != BF16_3X:
        return op(a, b, precision)
    (ah, al), (bh, bl) = _bf16_parts(a), _bf16_parts(b)
    hp = lax.Precision.HIGHEST
    return op(ah, bh, hp) + op(ah, bl, hp) + op(al, bh, hp)


def unit_apply(model: dict, params, u: int, x, precision, dtype=jnp.float32):
    """Unit ``u`` on a batch of its inputs; returns ``(B, F_u)``."""
    kind, shp_in, _, _, _ = unit_shapes(model)[u]
    p = params[u]
    w, b = p["w"].astype(dtype), p["b"].astype(dtype)
    if kind == "conv":
        x = x.reshape((-1,) + tuple(shp_in)).astype(dtype)
        y = _product(lambda a, k, pr: lax.conv_general_dilated(
            a, k, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=pr, preferred_element_type=dtype), x, w, precision) + b
        y = jnp.maximum(y, 0)
        if model["convs"][u][2]:
            y = lax.reduce_window(y, np.array(-np.inf, dtype), lax.max,
                                  (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
        return y.reshape(y.shape[0], -1)
    x = x.reshape(x.shape[0], -1).astype(dtype)
    y = _product(lambda a, k, pr: jnp.dot(a, k, precision=pr,
                                          preferred_element_type=dtype),
                 x, w, precision) + b
    return jnp.maximum(y, 0)


def features(model: dict, params, frames, precision, dtype=jnp.float32):
    """Every unit's feature for a batch of frames ``(B, H, W, C)``."""
    out, h = [], frames
    for u in range(len(unit_shapes(model))):
        h = unit_apply(model, params, u, h, precision, dtype)
        out.append(h)
    return out


# --------------------------------------------------------------------------- #
# The classifier bank.
# --------------------------------------------------------------------------- #


def margins(fsel, csel):
    """L1 top-2 margin ``(d2 - d1) / (d1 + d2)`` and nearest cluster of
    each row of ``fsel`` ``(B, S)`` against ``csel`` ``(C, S)``."""
    d = np.abs(fsel[:, None, :] - csel[None]).sum(-1)
    order = np.argsort(d, axis=1, kind="stable")
    d1 = np.take_along_axis(d, order[:, :1], 1)[:, 0]
    d2 = np.take_along_axis(d, order[:, 1:2], 1)[:, 0]
    return (d2 - d1) / np.maximum(d1 + d2, 1e-9), order[:, 0]


def exit_threshold(margin, correct, min_accuracy: float,
                   grid: int = 50) -> float:
    """The smallest margin threshold, on a grid of quantiles of held-out
    margins, above which the exiting frames are at least ``min_accuracy``
    correct; the grid's top where none is."""
    for t in np.quantile(margin, np.linspace(0.0, 0.98, grid)):
        out = margin > t
        if not out.any() or correct[out].mean() >= min_accuracy:
            return float(t)
    return float(np.quantile(margin, 0.98))


def build_bank(feats, labels, held_feats, held_labels, n_classes: int,
               n_sel: int, min_accuracy: float):
    """One classifier per unit: the ``n_sel`` features with the highest
    between-class over within-class variance, one centroid per class at
    the class mean (full width, for propagation), the class sizes as
    member counts, and the utility threshold from held-out frames
    (:func:`exit_threshold`)."""
    bank = []
    for f, h in zip(feats, held_feats):
        f = np.asarray(f, np.float64)
        mean = f.mean(0)
        between = np.zeros(f.shape[1])
        within = np.zeros(f.shape[1])
        cents, counts = [], []
        for c in range(n_classes):
            sub = f[labels == c]
            between += len(sub) * (sub.mean(0) - mean) ** 2
            within += ((sub - sub.mean(0)) ** 2).sum(0)
            cents.append(sub.mean(0))
            counts.append(len(sub))
        score = between / (within + 1e-9)
        idx = np.sort(np.argsort(-score, kind="stable")[:n_sel]).astype(np.int32)
        cents = np.asarray(cents, np.float32)
        m, near = margins(np.asarray(h, np.float32)[:, idx], cents[:, idx])
        bank.append(dict(centroids=cents, labels=np.arange(n_classes,
                                                           dtype=np.int32),
                         feature_idx=idx,
                         counts=np.asarray(counts, np.float32),
                         threshold=np.float32(exit_threshold(
                             m, near == held_labels, min_accuracy))))
    return bank
