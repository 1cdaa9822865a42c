"""Synthetic camera frames of a CIFAR-100-like and a VWW-like task.

Each class of a model has a smooth prototype image, fixed by the
configuration's seed.  A frame is its class's prototype at a random
amplitude, plus a random share of another class's prototype (a confuser),
an environment offset (the drift a deployed camera sees between scenes)
and pixel noise.  Frames are made on the device in one jitted call.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def prototypes(key, n: int, shape):
    """``n`` smooth images: white noise box-blurred over 5x5 and scaled
    to unit variance."""
    x = jax.random.normal(key, (n,) + tuple(shape), jnp.float32)
    k = jnp.ones((5, 5, 1, 1), jnp.float32) / 25.0
    c = shape[2]
    x = jax.lax.conv_general_dilated(
        x, jnp.tile(k, (1, 1, 1, c)), (1, 1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), feature_group_count=c,
        precision=jax.lax.Precision.HIGHEST)
    return x / x.std(axis=(1, 2, 3), keepdims=True)


def make_frames(key, protos, envs, n: int, separability: float,
                drift: float):
    """``n`` frames and labels: class ``y``, amplitude in [0.6, 1.3],
    confuser share in [0, 0.7], one of ``envs`` scaled by ``drift``,
    unit pixel noise."""
    k = jax.random.split(key, 6)
    n_cls = protos.shape[0]
    y = jax.random.randint(k[0], (n,), 0, n_cls)
    other = (y + 1 + jax.random.randint(k[1], (n,), 0, max(n_cls - 1, 1))) \
        % n_cls
    amp = jax.random.uniform(k[2], (n, 1, 1, 1), minval=0.6, maxval=1.3)
    conf = jax.random.uniform(k[3], (n, 1, 1, 1), minval=0.0, maxval=0.7)
    env = jax.random.randint(k[4], (n,), 0, envs.shape[0])
    x = separability * (amp * protos[y] + conf * protos[other]) \
        + drift * envs[env] + jax.random.normal(k[5], (n,) + protos.shape[1:])
    return x, y
