"""The operation counter against hand counts and XLA's cost analysis."""
import json

import pytest

import jax
import jax.numpy as jnp
from jax import lax

from conftest import BENCH
from lib import flops
from reference import cnn

CONFIG = json.loads(
    (BENCH / "configs" / "agile-cnn-cifar100-vww.json").read_text())
MODELS = {m["name"]: m for m in CONFIG["models"]}

# taps of a 5x5 SAME convolution that land inside an axis of n pixels,
# summed over the n outputs: 3 + 4 + 5 (n - 4) + 4 + 3 for n >= 4
TAPS = {32: 154, 16: 74, 8: 34, 4: 14}

# per unit: 2 x in-image taps x out channels x in channels; FC 2 x in x out
HAND = {
    "cifar100": [2 * 154 * 154 * 32 * 3, 2 * 74 * 74 * 64 * 32,
                 2 * 8 * 8 * 64 * 384, 2 * 384 * 192],
    "vww": [2 * 154 * 154 * 16 * 3, 2 * 74 * 74 * 32 * 16,
            2 * 34 * 34 * 64 * 32, 2 * 14 * 14 * 64 * 64,
            2 * 2 * 2 * 64 * 192],
}


@pytest.mark.parametrize("name", sorted(HAND))
def test_unit_flops_match_hand_counts(name):
    assert flops.unit_flops(MODELS[name]) == HAND[name]


def test_taps():
    assert {n: flops._taps(n, 5) for n in TAPS} == TAPS


def test_full_depth_jobs():
    assert flops.job_flops(MODELS["cifar100"], 150)[-1] == pytest.approx(
        30.3e6, rel=0.01)
    assert flops.job_flops(MODELS["vww"], 150)[-1] == pytest.approx(
        14.3e6, rel=0.01)


@pytest.mark.parametrize("name,unit", [("cifar100", 0), ("cifar100", 1),
                                       ("cifar100", 2), ("vww", 3),
                                       ("vww", 4)])
def test_unit_flops_match_xla_cost_analysis(name, unit):
    m = MODELS[name]
    kind, shp_in, _, fan, out = cnn.unit_shapes(m)[unit]
    if kind == "conv":
        k = m["convs"][unit][1]
        w = jax.ShapeDtypeStruct((k, k, shp_in[2], out), jnp.float32)
        x = jax.ShapeDtypeStruct((1,) + tuple(shp_in), jnp.float32)
        f = lambda x, w: lax.conv_general_dilated(  # noqa: E731
            x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))
    else:
        w = jax.ShapeDtypeStruct((fan, out), jnp.float32)
        x = jax.ShapeDtypeStruct((1, fan), jnp.float32)
        f = jnp.dot
    cost = jax.jit(f).lower(x, w).compile().cost_analysis()
    assert cost["flops"] == flops.unit_flops(m)[unit]


def test_executed_flops_counts_only_executed_units():
    import numpy as np

    models = [MODELS["cifar100"], MODELS["vww"]]
    units = np.array([[[0, 1, 4], [5, 2, 0]]])          # (1, K=2, J=3)
    per = [flops.job_flops(m, 150) for m in models]
    want = per[0][1] + per[0][4] + per[1][5] + per[1][2]
    assert flops.executed_flops(models, 150, units) == want
