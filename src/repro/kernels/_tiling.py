"""Shared tile-size / padding helpers for the Pallas kernel wrappers, and
the one fixed-order sum that kernels and their XLA twins share.

Every kernel in this package tiles one or more axes into VMEM-resident
blocks.  When an axis size is not a multiple of the block, the kernels used
to *shrink* the block (halve until divisible) — which silently collapses to
1-row tiles for odd/prime sizes (D=999 -> 999 single-row grid steps, a
catastrophic slowdown).  The fix is the same pad-and-slice idiom the fleet
k-means wrappers in :mod:`repro.kernels.ops` already use: keep the block,
pad the axis up to the next block multiple with values that cannot leak
into real rows (zeros / identity gates / invalid sentinels, chosen per
kernel), and slice the outputs back.

Lives in its own leaf module so the kernel implementations can import it
without pulling in :mod:`repro.kernels.ops` (which imports the kernels —
the other direction would be circular).
"""
from __future__ import annotations

import jax.numpy as jnp


def pad_axis(a, axis: int, multiple: int, value=0.0):
    """Constant-pad ``a`` along ``axis`` up to the next ``multiple``."""
    size = a.shape[axis]
    rem = (-size) % multiple
    if rem == 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, rem)
    return jnp.pad(a, widths, constant_values=value)


def pad_tree(nt, multiple: int, axis: int = 0, value=0.0):
    """:func:`pad_axis` applied to every leaf of a NamedTuple pytree.

    The whole-segment kernels (:mod:`repro.kernels.fleet_step`) tile the
    leading device axis of several pytrees at once (params, carry, bank,
    log) — all of them pad with the same block multiple, and padded rows
    are inert by construction (``n_releases == 0`` configs) and sliced
    back off the outputs.
    """
    return type(nt)(*[pad_axis(l, axis, multiple, value) for l in nt])


def choose_block(size: int, block: int) -> tuple[int, int]:
    """Tile size and padded axis length for tiling ``size`` rows in blocks
    of (at most) ``block``.

    Returns ``(bd, padded)`` with ``padded % bd == 0`` and
    ``padded - size < bd``: callers pad the axis to ``padded``
    (:func:`pad_axis`) and slice kernel outputs back to ``size``.  When
    ``size`` is already a block multiple this is the identity
    (``padded == size``), so divisible shapes keep their exact program.
    """
    bd = min(block, size)
    padded = -(-size // bd) * bd
    return bd, padded


def pairwise_sum(x):
    """Sum over the trailing axis in one fixed order: zero-pad the axis to
    a power of two, then halve it pairwise (``x[:h] + x[h:]``) until one
    element is left.

    ``jnp.sum`` leaves the order of the additions to the compiler, and XLA
    and Mosaic choose different ones on a TPU, so the same f32 reduction
    rounds differently in a fused kernel and in its XLA twin.  This order
    is spelled out as elementwise adds, which every backend computes
    alike.  Zero padding to any larger power of two gives the same result
    bit for bit (its first halvings add zeros), so operands padded to
    different widths agree.
    """
    n = x.shape[-1]
    width = 1 << (n - 1).bit_length()
    if width != n:
        x = jnp.concatenate(
            [x, jnp.zeros(x.shape[:-1] + (width - n,), x.dtype)], axis=-1)
    while x.shape[-1] > 1:
        h = x.shape[-1] // 2
        x = x[..., :h] + x[..., h:]
    return x[..., 0]
