"""The benchmark's own trainer for its agile CNNs.

Every unit of a network gets a linear softmax head on its feature, and the
network is trained on the sum of the units' cross-entropies, so that each
unit's feature separates the classes on its own (the property the paper's
layer-aware loss asks of an agile CNN, section 6.1).  The heads are
dropped afterwards: units are classified by their k-means bank.

Training runs on the device in one jitted call: frames are drawn from the
world's prototypes inside a ``lax.scan`` over steps, Adam updates the
weights, and the result depends only on the seed and the settings.  No
import from the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from lib import frames
from reference import cnn

ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8
#: the backend's default matmul precision (one bfloat16 pass on a TPU):
#: training only has to be deterministic, and set-up stays short
PRECISION = lax.Precision.DEFAULT


def _heads(model: dict):
    return [{"w": jnp.zeros((f, model["n_classes"]), jnp.float32),
             "b": jnp.zeros((model["n_classes"],), jnp.float32)}
            for f in cnn.feature_dims(model)]


def _loss(model, params, heads, x, y):
    feats = cnn.features(model, params, x, PRECISION)
    total = 0.0
    for f, h in zip(feats, heads):
        logits = jnp.dot(f, h["w"], precision=PRECISION) + h["b"]
        logp = jax.nn.log_softmax(logits)
        total = total - jnp.take_along_axis(logp, y[:, None], 1).mean()
    return total


def train(model: dict, params, protos, envs, key, settings: dict,
          separability: float, steps: int):
    """``params`` after ``steps`` Adam steps of ``settings["batch"]``
    fresh frames each, without drift; also the loss of every step."""
    lr = float(settings["lr"])
    batch = int(settings["batch"])
    state = (params, _heads(model))
    zeros = jax.tree.map(jnp.zeros_like, state)

    def step(carry, k):
        st, m, v, t = carry
        x, y = frames.make_frames(k, protos, envs, batch, separability, 0.0)
        loss, g = jax.value_and_grad(
            lambda s: _loss(model, s[0], s[1], x, y))(st)
        t = t + 1
        m = jax.tree.map(lambda a, b: ADAM_B1 * a + (1 - ADAM_B1) * b, m, g)
        v = jax.tree.map(lambda a, b: ADAM_B2 * a + (1 - ADAM_B2) * b * b,
                         v, g)
        c1, c2 = 1 - ADAM_B1 ** t, 1 - ADAM_B2 ** t
        st = jax.tree.map(
            lambda p, a, b: p - lr * (a / c1) / (jnp.sqrt(b / c2) + ADAM_EPS),
            st, m, v)
        return (st, m, v, t), loss

    (st, _, _, _), losses = lax.scan(
        step, (state, zeros, zeros, jnp.float32(0.0)),
        jax.random.split(key, steps))
    return st[0], losses
