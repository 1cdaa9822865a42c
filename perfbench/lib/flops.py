"""Operations an agile CNN job needs, from the configuration's shapes.

A unit's operations are its convolution or matrix product, two per
multiply-accumulate at the published shapes: a 5x5 SAME convolution
produces every input pixel before the 2x2 pooling, and a tap that falls
on the zero padding at the border is no work.  Its classifier's are
the L1 distances of the selected features to every cluster: a subtract,
an absolute value and an add per feature and cluster.  Biases, ReLU,
pooling and the adaptation of the bank are not counted.  Nothing here
reads the program's arrays, so a change to how the program computes a
unit leaves the count alone.
"""
from __future__ import annotations

import numpy as np


def _taps(n: int, k: int) -> int:
    """Kernel taps inside an axis of ``n`` pixels, summed over the ``n``
    outputs of a SAME convolution of odd width ``k``."""
    r = k // 2
    return sum(min(i + r, n - 1) - max(i - r, 0) + 1 for i in range(n))


def unit_flops(model: dict) -> list[int]:
    """Operations of each unit's layer for one frame."""
    h, w, c = model["input_shape"]
    out = []
    for ch, k, pool in model["convs"]:
        out.append(2 * _taps(h, k) * _taps(w, k) * ch * c)
        if pool:
            h, w = h // 2, w // 2
        c = ch
    d = h * w * c
    for f in model["fcs"]:
        out.append(2 * d * f)
        d = f
    return out


def classifier_flops(model: dict, n_sel: int) -> int:
    """Operations of one unit's L1 classification against one centroid
    per class."""
    return 3 * n_sel * model["n_classes"]


def job_flops(model: dict, n_sel: int) -> np.ndarray:
    """``out[n]``: operations of a job that executed ``n`` units."""
    per = [f + classifier_flops(model, n_sel) for f in unit_flops(model)]
    return np.concatenate([[0], np.cumsum(per)]).astype(np.float64)


def executed_flops(models, n_sel: int, units) -> float:
    """Operations of every executed unit in a log of executed-unit counts
    ``units`` ``(..., K, J)``."""
    units = np.asarray(units)
    total = 0.0
    for k, m in enumerate(models):
        total += float(job_flops(m, n_sel)[units[..., k, :]].sum())
    return total
