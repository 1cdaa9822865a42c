"""Pallas TPU kernel: batched L1 distance to k centroids + top-2 margins.

This is Zygarde's inner loop: every unit boundary runs the k-means classify +
utility test, which needs, for each feature vector, the two smallest L1
distances to the k cluster centroids (Delta_1, Delta_2) and the argmin.

TPU adaptation (vs the MCU's add-only rationale): the computation is
bandwidth-bound (centroids re-read per feature tile), so the kernel tiles the
feature batch into VMEM-resident blocks of ``block_b`` rows while keeping the
full (k, d) centroid table resident in VMEM across the batch grid — one HBM
read of the centroids per call instead of per row.  The lane dimension d is
padded to a multiple of 128 by the wrapper (ops.py) so VREG lanes are full.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._tiling import choose_block, pad_axis, pairwise_sum

POS = 1e30  # python scalar: jnp constants would be captured consts in pallas


def _l1_topk2_kernel(x_ref, c_ref, d1_ref, d2_ref, idx_ref):
    """x: (bB, d) VMEM; c: (k, d) VMEM; outputs (1, bB) each."""
    x = x_ref[...]  # (bB, d)
    c = c_ref[...]  # (k, d)
    # distances: (bB, k) — elementwise |x - c| reduced over d in the fixed
    # order the live-serving classify shares (bit-exact scalar vs fleet)
    d = pairwise_sum(jnp.abs(x[:, None, :] - c[None, :, :]))
    d1 = jnp.min(d, axis=1)
    idx = jnp.argmin(d, axis=1).astype(jnp.int32)
    k = d.shape[1]
    masked = jnp.where(
        jax.nn.one_hot(idx, k, dtype=jnp.bool_), POS, d
    )
    d2 = jnp.min(masked, axis=1)
    d1_ref[...] = d1.reshape(1, -1)
    d2_ref[...] = d2.reshape(1, -1)
    idx_ref[...] = idx.reshape(1, -1)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def l1_topk2(
    x: jax.Array,
    centroids: jax.Array,
    *,
    block_b: int = 256,
    interpret: bool = False,
):
    """x: (B, d) f32, centroids: (k, d) f32 -> (d1 (B,), d2 (B,), idx (B,))."""
    B, d = x.shape
    k = centroids.shape[0]
    # pad the row axis to a block multiple instead of shrinking the block
    # (halving collapses odd/prime B to 1-row tiles); padded rows compute
    # garbage distances that are sliced off below
    block_b, Bp = choose_block(B, block_b)
    if Bp != B:
        x = pad_axis(x, 0, block_b)
    grid = (Bp // block_b,)
    d1, d2, idx = pl.pallas_call(
        _l1_topk2_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, d), lambda i: (i, 0)),
            pl.BlockSpec((k, d), lambda i: (0, 0)),  # centroids resident
        ],
        # each block's outputs are one lane-dense row: a 1-D (block_b,)
        # block would not match XLA's tiling of the (Bp,) result
        out_specs=[pl.BlockSpec((None, 1, block_b), lambda i: (i, 0, 0))] * 3,
        out_shape=[
            jax.ShapeDtypeStruct((grid[0], 1, block_b), jnp.float32),
            jax.ShapeDtypeStruct((grid[0], 1, block_b), jnp.float32),
            jax.ShapeDtypeStruct((grid[0], 1, block_b), jnp.int32),
        ],
        interpret=interpret,
    )(x, centroids)
    return d1.reshape(Bp)[:B], d2.reshape(Bp)[:B], idx.reshape(Bp)[:B]
