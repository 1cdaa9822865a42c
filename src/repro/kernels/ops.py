"""Public jit'd entry points for the Pallas kernels.

On the CPU backend the kernels execute in ``interpret=True`` mode — the
kernel body runs as traced JAX ops — which validates tiling/indexing logic
against the pure-jnp oracles in :mod:`repro.kernels.ref`.  On a TPU the
same calls compile to Mosaic; nothing selects interpret mode there.

The ``fleet_*`` wrappers add *fleet-shaped* dispatch for the k-means
kernels: the online harvest-pattern forecaster (:mod:`repro.adapt.forecast`)
classifies and adapts over ``(D, W, F)`` window batches — ``D`` devices ×
``W`` trailing windows × ``F`` features — so the wrappers flatten the
leading batch axes, pad the feature (lane) dimension to a multiple of 128
and the row (sublane) dimension to a tile multiple, run the 2-D kernel
once over the whole fleet, and restore the batch shape.  L1 distances are
invariant to zero-padded feature columns (both operands gain the same
zeros), and padded rows carry assignment ``-1`` whose one-hot is all-zero,
so the padding never leaks into results.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ._tiling import pad_axis as _pad_axis  # noqa: F401  (public via ops)
from .centroid_update import centroid_update as _centroid_update
from .decode_gqa import decode_gqa as _decode_gqa
from .flash_attn import flash_attention as _flash_attention
from .fleet_priority import fleet_priority as _fleet_priority
from .fleet_step import fleet_fused_steps as _fleet_fused_steps
from .fleet_step import serve_fused_steps as _serve_fused_steps
from .l1_topk2 import l1_topk2 as _l1_topk2
from .pairwise_l1 import pairwise_l1 as _pairwise_l1
from .rglru_scan import rglru_scan as _rglru_scan


@functools.lru_cache(maxsize=1)
def _interpret() -> bool:
    """Pallas runs in interpret mode on the CPU backend, and only there.

    Cached — the backend cannot change within a process."""
    return jax.default_backend() == "cpu"


def l1_topk2(x, centroids, **kw):
    kw.setdefault("interpret", _interpret())
    return _l1_topk2(x, centroids, **kw)


def pairwise_l1(x, y, **kw):
    kw.setdefault("interpret", _interpret())
    return _pairwise_l1(x, y, **kw)


def centroid_update(centroids, x, assign, weight, **kw):
    kw.setdefault("interpret", _interpret())
    return _centroid_update(centroids, x, assign, weight, **kw)


def rglru_scan(a, b, h0, **kw):
    kw.setdefault("interpret", _interpret())
    return _rglru_scan(a, b, h0, **kw)


def decode_gqa(q, k_cache, v_cache, slot_pos, my_pos, **kw):
    kw.setdefault("interpret", _interpret())
    return _decode_gqa(q, k_cache, v_cache, slot_pos, my_pos, **kw)


def flash_attention(q, k, v, **kw):
    kw.setdefault("interpret", _interpret())
    return _flash_attention(q, k, v, **kw)


def fleet_l1_topk2(x, centroids, *, block_b: int = 256, lane: int = 128,
                   **kw):
    """:func:`l1_topk2` over fleet-batched windows.

    ``x``: ``(..., F)`` feature windows with any leading batch shape (the
    forecaster passes ``(D, W, F)`` or ``(D, F)``); ``centroids``: ``(k, F)``.
    Returns ``(d1, d2, idx)`` each shaped like the batch ``(...,)``.  Rows
    are flattened and tile-padded, features are zero-padded to a lane
    multiple — L1 distances are unchanged because both operands gain the
    same zero columns.
    """
    x = jnp.asarray(x, jnp.float32)
    centroids = jnp.asarray(centroids, jnp.float32)
    batch = x.shape[:-1]
    flat = x.reshape((-1, x.shape[-1]))
    n_rows = flat.shape[0]
    flat = _pad_axis(_pad_axis(flat, 1, lane), 0, min(block_b, 8))
    cents = _pad_axis(centroids, 1, lane)
    d1, d2, idx = l1_topk2(flat, cents, block_b=block_b, **kw)
    return (d1[:n_rows].reshape(batch), d2[:n_rows].reshape(batch),
            idx[:n_rows].reshape(batch))


def fleet_centroid_update(centroids, x, assign, weight, *, lane: int = 128,
                          **kw):
    """:func:`centroid_update` over fleet-batched windows.

    ``x``: ``(..., F)``, ``assign``: ``(...,)`` int32 cluster ids (rows with
    ``assign < 0`` are ignored — their one-hot is all-zero), ``centroids``:
    ``(k, F)``.  Flattens the batch, pads rows with ``assign = -1`` and
    features with zeros, and slices the padded columns back off the
    ``(k, F)`` result.
    """
    centroids = jnp.asarray(centroids, jnp.float32)
    k, f = centroids.shape
    flat = jnp.asarray(x, jnp.float32).reshape((-1, f))
    aflat = jnp.asarray(assign, jnp.int32).reshape((-1,))
    flat = _pad_axis(_pad_axis(flat, 1, lane), 0, 8)
    aflat = _pad_axis(aflat, 0, 8, value=-1)
    new_c = centroid_update(_pad_axis(centroids, 1, lane), flat, aflat,
                            weight, **kw)
    return new_c[:, :f]


def fleet_priority(policy, active, laxity, release, utility, mandatory,
                   alpha, beta, eta, persistent, energy, e_opt, charge,
                   capacity, gate_e, drain, forced, task, rr_cursor, *,
                   n_tasks=1, **kw):
    """Batched scheduler pick + capacitor update over a task-set workload;
    returns jnp-typed flags (``sel`` int32, ``picked``/``run`` bool,
    ``e_new`` f32).  ``task``/``rr_cursor`` feed the in-kernel round-robin
    task rotation (``n_tasks`` is static)."""
    kw.setdefault("interpret", _interpret())
    sel, picked, run, e_new = _fleet_priority(
        policy, active, laxity, release, utility, mandatory, alpha, beta,
        eta, persistent, energy, e_opt, charge, capacity, gate_e, drain,
        forced, task, rr_cursor, n_tasks=n_tasks, **kw)
    return sel, picked.astype(bool), run.astype(bool), e_new


def fleet_fused_steps(cfg, carry, i0, *, statics, n_steps, **kw):
    """Whole-segment fused device-step: advance every device ``n_steps``
    timesteps in ONE ``pallas_call`` with the carry tile VMEM-resident
    (:mod:`repro.kernels.fleet_step`).  Bit-exact vs the vmap scan —
    the kernel body IS :func:`repro.core.step.device_step`."""
    kw.setdefault("interpret", _interpret())
    return _fleet_fused_steps(cfg, carry, i0, statics=statics,
                              n_steps=n_steps, **kw)


def serve_fused_steps(cfg, carry, look, i0, job0, *, statics, n_steps,
                      **kw):
    """Whole-segment fused LIVE serving: advance every device ``n_steps``
    timesteps in ONE ``pallas_call`` with the L1-top-2 classify +
    live-register update in-tile and the centroid bank VMEM-resident
    (:mod:`repro.kernels.fleet_step`).  Bit-exact vs the serve scan —
    the kernel body IS :func:`repro.serve.fleet_engine.serve_step`."""
    kw.setdefault("interpret", _interpret())
    return _serve_fused_steps(cfg, carry, look, i0, job0,
                              statics=statics, n_steps=n_steps, **kw)
