"""Pallas TPU kernel: the ENTIRE fleet time loop fused into one kernel.

The previous "kernel mode" (:mod:`repro.kernels.fleet_priority`) only ran
the pick stage in-tile: every timestep still dispatched one ``pallas_call``
from inside the scan, bouncing the whole carry through HBM between the
admit/expire/apply stages — a measured 5-7x *slowdown* over plain ``vmap``.
This kernel inverts the loop structure: a ``block_d``-row tile of the full
:class:`repro.core.step.DeviceCarry` (queue slots, energy, rr cursor, live
registers, metric accumulators) is held in VMEM while a ``lax.fori_loop``
runs ``n_steps`` timesteps per tile, evaluating the *entire*
admit -> expire -> pick -> apply transition per step — ONE ``pallas_call``
per segment instead of one per step, with zero HBM round-trips inside the
horizon chunk.

The transition body is :func:`repro.core.step.device_step` itself — the
step core is written batch-polymorphic and gather-free (one-hot iota
contractions instead of dynamic indexing, trailing-axis reductions), so the
kernel and the ``vmap`` frontend share literally one implementation and the
results are bit-exact against each other (asserted across the full parity
matrix in ``tests/test_parity.py``).

Dtype packing: Mosaic refs carry ``f32``/``i32``; boolean params/carry
leaves ride as ``i32`` 0/1 masks and are re-materialized as bools in-tile
(``!= 0``) and on the way out (:func:`pack_tree`/:func:`unpack_tree`, also
exposed as ``repro.fleet.state.pack_carry``/``unpack_carry`` for
checkpointing).  The device axis is padded to a block multiple
(:mod:`repro.kernels._tiling`); padded devices have ``n_releases == 0`` so
they never release work, and their rows are sliced off the outputs.

On the CPU backend the kernel executes in interpret mode — it validates
the fused semantics (and the one-call-per-segment dispatch shape) rather
than racing the vmap path; on a TPU the same call compiles to Mosaic with
the carry VMEM-resident across the whole segment, and ``chip_smoke.py``
checks it bit-exact against ``vmap`` there.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.step import (DeviceCarry, StepParams, StepStatics, device_step,
                         onehot_lowering)
from ..fleet.state import ServeCarry, ServeLog
from ._tiling import choose_block, pad_axis, pad_tree

#: StepParams / DeviceCarry leaves that are booleans in the pytree but ride
#: through Pallas refs as int32 0/1 masks (TPU-friendly dtypes).
BOOL_PARAM_FIELDS = ("imprecise", "is_edfm", "persistent", "use_exit_thr",
                     "passes", "correct")
BOOL_CARRY_FIELDS = ("was_off", "q_active", "q_correct", "q_apass")
#: ServeLog leaves that are booleans (packed the same way for the fused
#: serve kernel).
BOOL_LOG_FIELDS = ("correct", "sched")


def pack_tree(nt, bool_fields):
    """Cast the named boolean leaves of a NamedTuple pytree to int32."""
    return type(nt)(*[
        v.astype(jnp.int32) if f in bool_fields else v
        for f, v in zip(nt._fields, nt)
    ])


def unpack_tree(nt, bool_fields):
    """Re-materialize the named int32 0/1 leaves as booleans."""
    return type(nt)(*[
        (v != 0) if f in bool_fields else v
        for f, v in zip(nt._fields, nt)
    ])


def to_tiles(leaf, bd: int):
    """The array a ``(D, *dims)`` leaf travels as: ``(D // bd, 1, bd)``
    for a per-device scalar (``D`` a multiple of ``bd``), ``(D, prod(dims))``
    otherwise.

    Mosaic tiles the two trailing axes of a block as (sublane, lane).  A
    ``(D,)`` leaf in ``bd``-row blocks would not match XLA's 1-D tiling,
    and a ``(block_d, K, J, U)`` table would pad ``U`` to 128 lanes for
    every ``(device, task, job)`` — tens of times its size in VMEM — while
    an in-kernel reshape that merges those axes is refused.  Flat, the
    tables reshape to their logical shape in-kernel (:func:`from_tile`) and
    straight back inside the step core's flat-index lookups
    (:func:`repro.core.step._take`), a pair the compiler folds away."""
    if leaf.ndim == 1:
        return leaf.reshape(-1, 1, bd)
    return leaf.reshape(leaf.shape[0], -1)


def tile_spec(leaf, bd: int):
    """BlockSpec of grid step ``i`` over ``to_tiles(leaf, bd)``."""
    if leaf.ndim == 1:
        return pl.BlockSpec((None, 1, bd), lambda i: (i, 0, 0))
    return pl.BlockSpec((bd, math.prod(leaf.shape[1:])), lambda i: (i, 0))


def from_tile(v, like, bd: int):
    """In-kernel: a block of :func:`to_tiles` -> its logical
    ``(bd, *dims)`` shape (``like`` is the unblocked leaf)."""
    return v.reshape((bd,) + like.shape[1:])


def to_tile(v, bd: int):
    """In-kernel inverse of :func:`from_tile`, for output blocks."""
    return v.reshape(1, bd) if v.ndim == 1 else v.reshape(bd, -1)


def _shapes(nt):
    return jax.tree.map(lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), nt)


#: the segment's first step index rides in scalar memory
_I0_SPEC = pl.BlockSpec(memory_space=pltpu.SMEM)

#: the fused kernels hold whole per-device tables of a ``block_d`` tile in
#: VMEM (double-buffered), beyond the default scoped limit; v5e has 128 MiB
_COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=100 * 2**20)


_N_PARAMS = len(StepParams._fields)
_N_CARRY = len(DeviceCarry._fields)


def _fleet_step_kernel(*refs, statics: StepStatics, n_steps: int,
                       p_like: StepParams, c_like: DeviceCarry, bd: int):
    """One device tile: reconstruct the pytrees from the packed refs, run
    the whole segment's time loop in VMEM, write the carry back."""
    i0_ref = refs[0]
    p_refs = refs[1:1 + _N_PARAMS]
    c_refs = refs[1 + _N_PARAMS:1 + _N_PARAMS + _N_CARRY]
    o_refs = refs[1 + _N_PARAMS + _N_CARRY:]

    # unpack before the reshape, so that the step core's flat lookups meet
    # the reshape they fold against
    params = StepParams(*[
        from_tile(v, l, bd) for v, l in zip(
            unpack_tree(StepParams(*[r[...] for r in p_refs]),
                        BOOL_PARAM_FIELDS), p_like)])
    # the loop carries the packed (int32) leaves: Mosaic cannot carry bool
    # vectors through a loop
    packed = DeviceCarry(*[from_tile(r[...], l, bd)
                           for r, l in zip(c_refs, c_like)])
    i0 = i0_ref[0]

    def body(s, packed):
        # the shared clock: t = step_index * dt and t_end = (index+1) * dt,
        # the same expressions as the vmap path's scan.  Both are single
        # multiplies — always correctly rounded — so every frontend
        # produces identical bits.  (A ``t + dt`` form would invite the
        # backend to contract the mul+add into a single-rounding FMA in
        # one program but not another, a 1-ulp drift that breaks parity.)
        t = (i0 + s).astype(jnp.float32) * statics.dt
        t_end = (i0 + s + 1).astype(jnp.float32) * statics.dt
        st = device_step(params, unpack_tree(packed, BOOL_CARRY_FIELDS), t,
                         statics, t_end=t_end)
        return pack_tree(st, BOOL_CARRY_FIELDS)

    # Mosaic has no gather: trace the whole in-tile loop with table lookups
    # lowered as one-hot iota contractions instead of ``take_along_axis``.
    with onehot_lowering():
        packed = lax.fori_loop(0, n_steps, body, packed)
    for ref, v in zip(o_refs, packed):
        ref[...] = to_tile(v, bd)


@functools.partial(
    jax.jit, static_argnames=("statics", "n_steps", "block_d", "interpret"))
def fleet_fused_steps(
    cfg: StepParams,        # every leaf (D, ...)
    carry: DeviceCarry,     # every leaf (D, ...)
    i0,                     # i32 scalar: first step index of this segment
    *,
    statics: StepStatics,
    n_steps: int,
    block_d: int = 128,
    interpret: bool = False,
) -> DeviceCarry:
    """Advance the whole fleet ``n_steps`` timesteps in ONE ``pallas_call``.

    Drop-in replacement for the vmap path's ``scan`` over
    :func:`repro.core.step.device_step` — same carry in, same carry out,
    bit-exact.  ``n_steps`` is static (a segment length); ``i0`` is traced,
    so equal-length segments share one compilation.
    """
    D = cfg.policy.shape[0]
    bd, Dp = choose_block(D, block_d)
    p = pack_tree(cfg, BOOL_PARAM_FIELDS)
    c = pack_tree(carry, BOOL_CARRY_FIELDS)
    if Dp != D:
        # padded devices are all-zero configs: n_releases == 0 means they
        # never admit work and their garbage metrics are sliced off below
        p = StepParams(*[pad_axis(l, 0, bd) for l in p])
        c = DeviceCarry(*[pad_axis(l, 0, bd) for l in c])

    p_like, c_like = _shapes(p), _shapes(c)
    tiles_c = [to_tiles(l, bd) for l in c]
    outs = pl.pallas_call(
        functools.partial(_fleet_step_kernel, statics=statics,
                          n_steps=n_steps, p_like=p_like, c_like=c_like,
                          bd=bd),
        grid=(Dp // bd,),
        in_specs=([_I0_SPEC] + [tile_spec(l, bd) for l in p]
                  + [tile_spec(l, bd) for l in c]),
        out_specs=[tile_spec(l, bd) for l in c],
        out_shape=[jax.ShapeDtypeStruct(t.shape, t.dtype) for t in tiles_c],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name="fleet_fused_steps",
    )(jnp.asarray(i0, jnp.int32).reshape(1),
      *[to_tiles(l, bd) for l in p], *tiles_c)
    outs = [o.reshape(l.shape) for o, l in zip(outs, c)]
    new = unpack_tree(DeviceCarry(*outs), BOOL_CARRY_FIELDS)
    if Dp != D:
        new = jax.tree.map(lambda l: l[:D], new)
    return new


# --------------------------------------------------------------------- #
# Fused live serving: classify + live-register update in-tile.
# --------------------------------------------------------------------- #

_N_LOG = len(ServeLog._fields)
_N_LOOK = 5   # ServeLookup: feat_rows, cent_rows, labels, clabels, thr


def _serve_step_kernel(*refs, statics: StepStatics, n_steps: int,
                       p_like: StepParams, c_like: DeviceCarry, bd: int):
    """One device tile of live serving: rebuild the pytrees from the packed
    refs, run the whole segment's serve loop in VMEM — the per-step body IS
    :func:`repro.serve.fleet_engine.serve_step`, the exact trace the XLA
    scan path runs, lowered with one-hot gathers — and write the device
    carry + outcome log back.  The bank is read-only here (adaptation is
    fleet-level and compiled out in fused mode)."""
    # lazy: the serve engine imports this package's public wrappers
    from ..serve.fleet_engine import ServeLookup, serve_step

    i0 = refs[0][0]
    off = 2
    p_refs = refs[off:off + _N_PARAMS]
    off += _N_PARAMS
    c_refs = refs[off:off + _N_CARRY]
    off += _N_CARRY
    l_refs = refs[off:off + _N_LOG]
    off += _N_LOG
    look = ServeLookup(*[r[...] for r in refs[off:off + _N_LOOK]])
    o_refs = refs[off + _N_LOOK:]

    params = StepParams(*[
        from_tile(v, l, bd) for v, l in zip(
            unpack_tree(StepParams(*[r[...] for r in p_refs]),
                        BOOL_PARAM_FIELDS), p_like)])
    # packed (int32) loop carry: Mosaic cannot carry bool vectors
    packed = (DeviceCarry(*[from_tile(r[...], l, bd)
                            for r, l in zip(c_refs, c_like)]),
              ServeLog(*[r[...] for r in l_refs]))
    job0 = refs[1][...]

    def body(s, packed):
        d, lg = packed
        t = (i0 + s).astype(jnp.float32) * statics.dt
        d, lg, _ = serve_step(params, look,
                              unpack_tree(d, BOOL_CARRY_FIELDS),
                              unpack_tree(lg, BOOL_LOG_FIELDS), t, job0,
                              statics=statics)
        return (pack_tree(d, BOOL_CARRY_FIELDS),
                pack_tree(lg, BOOL_LOG_FIELDS))

    with onehot_lowering():
        dev, log = lax.fori_loop(0, n_steps, body, packed)
    outs = [to_tile(v, bd) for v in dev] + list(log)
    for ref, v in zip(o_refs, outs):
        ref[...] = v


def _row_spec(leaf, bd: int):
    """Block ``bd`` rows of the leading (device) axis, whole otherwise."""
    nz = leaf.ndim - 1
    return pl.BlockSpec((bd,) + leaf.shape[1:],
                        lambda i, _nz=nz: (i,) + (0,) * _nz)


def _whole_spec(leaf):
    """The whole array in every grid step (a leaf shared by all devices)."""
    nz = leaf.ndim
    return pl.BlockSpec(leaf.shape, lambda i, _nz=nz: (0,) * _nz)


@functools.partial(
    jax.jit, static_argnames=("statics", "n_steps", "block_d", "interpret",
                              "shared_bank", "per_dev_tables"))
def serve_fused_steps(
    cfg: StepParams,         # every leaf (D, ...)
    carry: ServeCarry,       # dev/log leaves (D, ...); bank per mode
    look,                    # ServeLookup; feat_rows/labels (D, ...) if
                             # per_dev_tables, cent_rows if not shared_bank
    i0,                      # i32 scalar: first step index of this segment
    job0,                    # (K,) i32: global job id of window row 0
    *,
    statics: StepStatics,
    n_steps: int,
    block_d: int = 128,
    interpret: bool = False,
    shared_bank: bool = False,
    per_dev_tables: bool = False,
) -> ServeCarry:
    """Advance live serving ``n_steps`` timesteps in ONE ``pallas_call``.

    The L1-top-2 classify + live-register update run in-tile with the
    bank's selected columns VMEM-resident: a ``block_d``-row tile of the
    device carry, outcome log, bank rows (unless ``shared_bank``) and
    feature rows (if ``per_dev_tables``) is held while a ``fori_loop``
    evaluates the full admit → expire → pick → classify → apply transition
    per step.  Bit-exact vs :meth:`FleetServeEngine._scan_steps` — the
    kernel body is the same :func:`serve_step` trace.  Requires
    ``adapt=False`` (bank adaptation is fleet-level); the bank passes
    through unchanged.
    """
    from ..serve.fleet_engine import flat_log

    D = cfg.policy.shape[0]
    bd, Dp = choose_block(D, block_d)
    p = pack_tree(cfg, BOOL_PARAM_FIELDS)
    c = pack_tree(carry.dev, BOOL_CARRY_FIELDS)
    lg = pack_tree(flat_log(carry.log), BOOL_LOG_FIELDS)
    per_dev = {"feat_rows": per_dev_tables, "labels": per_dev_tables,
               "cent_rows": not shared_bank}
    look = type(look)(*[
        pad_axis(l, 0, bd) if per_dev.get(f) and Dp != D else l
        for f, l in zip(look._fields, look)])
    if Dp != D:
        p, c, lg = pad_tree(p, bd), pad_tree(c, bd), pad_tree(lg, bd)

    job0 = jnp.asarray(job0, jnp.int32)
    p_like, c_like = _shapes(p), _shapes(c)
    tiles_c = [to_tiles(l, bd) for l in c]
    out_tmpl = tiles_c + list(lg)
    outs = pl.pallas_call(
        functools.partial(_serve_step_kernel, statics=statics,
                          n_steps=n_steps, p_like=p_like, c_like=c_like,
                          bd=bd),
        grid=(Dp // bd,),
        in_specs=([_I0_SPEC, _whole_spec(job0)]
                  + [tile_spec(l, bd) for l in p]
                  + [tile_spec(l, bd) for l in c]
                  + [_row_spec(l, bd) for l in lg]
                  + [_row_spec(l, bd) if per_dev.get(f) else _whole_spec(l)
                     for f, l in zip(look._fields, look)]),
        out_specs=([tile_spec(l, bd) for l in c]
                   + [_row_spec(l, bd) for l in lg]),
        out_shape=[jax.ShapeDtypeStruct(l.shape, l.dtype)
                   for l in out_tmpl],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name="serve_fused_steps",
    )(jnp.asarray(i0, jnp.int32).reshape(1), job0,
      *[to_tiles(l, bd) for l in p], *tiles_c, *lg, *look)
    new_dev = unpack_tree(
        DeviceCarry(*[o.reshape(l.shape)
                      for o, l in zip(outs[:_N_CARRY], c)]),
        BOOL_CARRY_FIELDS)
    new_log = unpack_tree(ServeLog(*outs[_N_CARRY:]), BOOL_LOG_FIELDS)
    new_log = ServeLog(*[f[:D].reshape(l.shape)
                         for f, l in zip(new_log, carry.log)])
    return ServeCarry(dev=jax.tree.map(lambda l: l[:D], new_dev),
                      bank=carry.bank, log=new_log)
