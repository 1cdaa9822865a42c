"""The unified per-device step core: ONE implementation of the scheduler
transition shared by every simulation frontend.

Everything that happens to a single intermittently-powered device in one
fixed timestep — release/admit, expiry, priority pick via
:mod:`repro.core.policy`, fragment execution, capacitor charge/discharge,
metric accumulation — lives here as pure functions over two pytrees:

* :class:`StepParams` — immutable per-device configuration (task tables,
  harvester event stream, scheduler scalars).  No device axis; batching is
  the caller's job.
* :class:`DeviceCarry` — the mutable simulation state threaded through
  ``(params, carry, t) -> carry`` transitions: capacitor energy, the
  fixed-size job queue as parallel arrays, metric accumulators.

Three frontends consume the same functions:

* :func:`repro.core.scheduler.simulate_stepped` — the scalar discretized
  frontend: one device, one ``lax.scan``, no ``vmap``.
* :mod:`repro.fleet.simulator` — ``jax.vmap`` adds the device axis and
  ``lax.scan`` the time axis (optionally chunked into segments with a host
  hook between chunks, the substrate for in-trajectory online adaptation).
* :mod:`repro.kernels.fleet_priority` — the Pallas kernel evaluates the
  pick stage on VMEM tiles; its post-score selection semantics are
  :func:`select_and_charge`, imported from here so the in-tile math can
  never drift from the reference.

Because the fleet path is literally ``vmap`` of these functions, the
scalar-stepped and fleet paths are *bit-exact* on the shared clock — the
parity harness in ``tests/test_parity.py`` asserts exact equality, not
calibrated tolerances.

Shapes use ``K`` tasks per device, ``Q`` queue slots, ``U`` units per job,
``J`` jobs per task, ``S`` harvester slots.  Static (python) dimensions and
step sizes live in the hashable :class:`StepStatics` (a ``jax.jit`` static
argument).
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from . import policy as P

_F32 = jnp.float32


# --------------------------------------------------------------------------- #
# Batched indexing helpers with a frontend-selected lowering.
#
# The stages below run in three very different execution contexts: scalar
# (one device, no batch axes), under ``vmap`` (fleet axis stripped), and
# *inside a Pallas kernel tile* with a ``(block_d,)`` device axis attached to
# every leaf (repro.kernels.fleet_step).  Mosaic supports neither gathers nor
# 1-D iota, so inside the kernel every table lookup is phrased as a one-hot
# iota contraction over the trailing axis; on the XLA frontends the same
# lookup lowers to ``take_along_axis`` (a cheap batched gather — the one-hot
# form is ~10x slower there: three passes over Q*N per lookup vs Q reads).
# The two lowerings are bit-exact against each other: exactly one lane is
# hot and ``x + 0.0 == x`` / ``x | False == x``, and every call site with a
# possibly-invalid index (-1 sentinels) masks the looked-up value
# downstream.  The transition logic itself is written ONCE; only this
# helper switches, under :func:`onehot_lowering` (entered by the fused
# kernel body around its time loop).  All reductions and index reads use
# trailing-axis (-1/-2) conventions so arbitrary leading batch axes ride
# along untouched.
# --------------------------------------------------------------------------- #

_ONEHOT_ONLY = False


@contextlib.contextmanager
def onehot_lowering():
    """Trace-time switch: lower table lookups as one-hot iota contractions
    (Mosaic kernels — no gather support) instead of ``take_along_axis``."""
    global _ONEHOT_ONLY
    prev = _ONEHOT_ONLY
    _ONEHOT_ONLY = True
    try:
        yield
    finally:
        _ONEHOT_ONLY = prev


def _oh_eq(idx, n: int):
    """One-hot of ``idx`` over a new trailing axis of size ``n`` (bool)."""
    iota = lax.broadcasted_iota(jnp.int32, idx.shape + (n,), idx.ndim)
    return iota == idx[..., None]


def _col(mask):
    """``mask[..., None]`` for a bool ``mask``, expanded as int32: Mosaic
    refuses shape casts of bool vectors."""
    return mask.astype(jnp.int32)[..., None] != 0


def select_bool(cond, a, b):
    """``jnp.where(cond, a, b)`` for bool ``a``/``b`` as mask algebra:
    Mosaic lowers no select of bool vectors."""
    return (cond & a) | (~cond & b)


def _flat(table, nd: int):
    """Collapse the ``nd`` trailing axes of ``table`` into one."""
    return table.reshape(table.shape[:table.ndim - nd] + (-1,))


def _onehot_sum(oh, t, axis: int = -1):
    """Contract the one-hot mask ``oh`` against ``t`` over ``axis`` (exact:
    one hot lane, ``x + 0 == x``).  Bool tables are contracted as int32,
    since Mosaic lowers neither bool reductions nor bool shape casts."""
    if t.dtype == jnp.bool_:
        return _onehot_sum(oh, t.astype(jnp.int32), axis) != 0
    return jnp.sum(jnp.where(oh, t, jnp.zeros((), t.dtype)), axis=axis)


def _take(table, idx, nd: int = 1):
    """``table[..., idx]`` with ``idx`` a row-major flat index over the
    ``nd`` trailing axes of ``table``.

    ``table``: ``(..., *dims)``; ``idx``: int ``(..., Q)`` with the same
    leading axes -> ``(..., Q)`` in ``table.dtype``.  Exact: one hot lane
    (one-hot lowering) / clamped gather (XLA lowering); call sites mask any
    slot whose index can be out of range.
    """
    table = _flat(table, nd)
    if not _ONEHOT_ONLY:
        lead = jnp.broadcast_shapes(table.shape[:-1], idx.shape[:-1])
        return jnp.take_along_axis(
            jnp.broadcast_to(table, lead + table.shape[-1:]),
            jnp.broadcast_to(idx, lead + idx.shape[-1:]),
            axis=-1, mode="clip")
    oh = _oh_eq(idx, table.shape[-1])          # (..., Q, N)
    if table.dtype == jnp.bool_:               # expand as int32, see above
        return _onehot_sum(oh, table.astype(jnp.int32)[..., None, :]) != 0
    return _onehot_sum(oh, table[..., None, :])


def first_true(mask):
    """Index of the first ``True`` along the trailing axis (0 when none) —
    ``jnp.argmax`` of a bool array, written as a min over an iota because
    Mosaic lowers index reductions of ``f32`` operands only."""
    n = mask.shape[-1]
    iota = lax.broadcasted_iota(jnp.int32, mask.shape, mask.ndim - 1)
    idx = jnp.min(jnp.where(mask, iota, n), axis=-1)
    return jnp.where(idx == n, 0, idx)


def argmax_first(x):
    """``jnp.argmax`` over the trailing axis (first maximum) for NaN-free
    ``x``, in the lowering :func:`first_true` needs."""
    return first_true(x == jnp.max(x, axis=-1, keepdims=True))


def argmin_first(x):
    """``jnp.argmin`` over the trailing axis (first minimum), NaN-free."""
    return first_true(x == jnp.min(x, axis=-1, keepdims=True))


def _take1(table, idx, nd: int = 1):
    """``table[..., idx]`` for a single per-device index."""
    if not _ONEHOT_ONLY:
        return _take(table, idx[..., None], nd)[..., 0]
    table = _flat(table, nd)
    return _onehot_sum(_oh_eq(idx, table.shape[-1]), table)


def take_rows(table, idx, nd: int = 1):
    """``table[..., idx, :]`` — one row per index, ``idx`` a row-major flat
    index over the ``nd`` axes before the last.

    ``table``: ``(..., *rows, M)``; ``idx``: int ``(...,)`` with leading
    axes broadcastable against the table's -> ``(..., M)`` in
    ``table.dtype``.  The live-serving transition uses this to pull one
    device's feature / centroid row out of the ``(K, J, U, ...)`` tables.
    Same lowering contract as :func:`_take`: ``take_along_axis`` (clamped)
    on the XLA frontends, a one-hot iota contraction over the row axis
    inside Mosaic kernels — bit-exact against each other (one hot lane,
    ``x + 0 == x``).  A 2-D table with batched indices lowers as a plain
    ``jnp.take`` so the operand is gathered directly instead of being
    broadcast across the batch.
    """
    table = table.reshape(table.shape[:table.ndim - nd - 1] + (-1,)
                          + table.shape[-1:])
    if not _ONEHOT_ONLY:
        n = table.shape[-2]
        if table.ndim == 2:
            return jnp.take(table, jnp.clip(idx, 0, n - 1), axis=0)
        lead = jnp.broadcast_shapes(table.shape[:-2], idx.shape)
        t = jnp.broadcast_to(table, lead + table.shape[-2:])
        ix = jnp.broadcast_to(idx[..., None, None],
                              lead + (1,) + table.shape[-1:])
        return jnp.take_along_axis(t, ix, axis=-2, mode="clip")[..., 0, :]
    oh = _col(_oh_eq(idx, table.shape[-2]))            # (..., N, 1)
    return _onehot_sum(oh, table, axis=-2)


@dataclasses.dataclass(frozen=True)
class StepStatics:
    """Hashable static configuration (jit static argument)."""

    queue_size: int = 3
    dt: float = 0.025            # fixed timestep (s); keep <= min unit_time
    horizon: float = 600.0
    slot_s: float = 1.0          # harvester slot length (s)

    @property
    def n_steps(self) -> int:
        return int(round(self.horizon / self.dt))


class StepParams(NamedTuple):
    """Immutable per-device configuration arrays.

    The shapes below are the *per-device* view consumed by the step
    functions; the fleet path stacks a leading ``D`` (device) axis on every
    leaf (see :class:`repro.fleet.state.FleetConfig`, an alias of this
    class) and ``vmap`` strips it back off.
    """

    # scheduler / energy scalars
    policy: jax.Array        # int32, repro.core.policy.POLICY_IDS
    imprecise: jax.Array     # bool: early exit enabled (zygarde, edf-m)
    is_edfm: jax.Array       # bool: EDF-M never runs optional units
    eta: jax.Array           # f32
    alpha: jax.Array         # f32, 1 / max relative deadline over the task set
    beta: jax.Array          # f32
    persistent: jax.Array    # bool: use zeta (Eq. 6) instead of zeta_I (Eq. 7)
    capacity: jax.Array      # f32, usable capacitor energy (J)
    start_energy: jax.Array  # f32; negative = cold-boot dead-zone debt
    e_man: jax.Array         # f32, minimum energy to run a fragment
    e_opt: jax.Array         # f32, Eq. 7 optional-unit energy threshold
    power_on: jax.Array      # f32, harvester power in the ON state (W)
    # timekeeping: deterministic linear clock drift (fleet-path CHRT model;
    # the scalar CHRTClock's random per-read offset has no batched
    # equivalent, so the step core models the *accumulated* error as a rate:
    # t_read = t * (1 + clock_drift))
    clock_drift: jax.Array   # f32; 0 = exact RTC
    # tunable per-unit utility-test thresholds (repro.adapt): when
    # use_exit_thr is set the utility test compares the live margin against
    # exit_thr instead of the precomputed `passes` table.  These are the
    # fields in-trajectory online adaptation rewrites between segments.
    use_exit_thr: jax.Array  # bool
    exit_thr: jax.Array      # (K, U) f32
    # task-set table, (K,): K periodic task streams per device
    period: jax.Array        # f32
    rel_deadline: jax.Array  # f32, relative deadline
    fragments: jax.Array     # f32, fragments per unit
    n_units: jax.Array       # int32, <= U (live units of each task)
    n_releases: jax.Array    # int32, jobs released within the horizon (<= J)
    # per-task workload tables
    unit_time: jax.Array     # (K, U) f32, seconds per unit
    unit_energy: jax.Array   # (K, U) f32, joules per unit
    margins: jax.Array       # (K, J, U) f32, utility-test margins
    passes: jax.Array        # (K, J, U) bool, utility test passes after unit
    correct: jax.Array       # (K, J, U) bool, unit prediction correct
    # harvester event stream, (S,) f32 — 0/1 flags or fractional amplitudes
    events: jax.Array

    @property
    def n_devices(self) -> int:
        """Fleet-level accessor (leading device axis stacked on every leaf)."""
        return self.policy.shape[0]

    @property
    def n_tasks(self) -> int:
        return self.period.shape[-1]


class DeviceCarry(NamedTuple):
    """Mutable per-device simulation state (no device axis; vmap adds it)."""

    energy: jax.Array        # f32 scalar; < 0 while paying cold-boot debt
    was_off: jax.Array       # bool scalar: last activity was a power-down
    next_rel: jax.Array      # int32 (K,): next job index to release, per task
    # round-robin task cursor: the task id the rr policy serves next (the
    # scalar simulator's rr_cursor); unused by the other policies
    rr_cursor: jax.Array     # int32 scalar
    # limited preemption (paper §4.1): once a unit starts, it runs to its
    # boundary — the scheduler only re-picks between units.  lock_job guards
    # against the slot being recycled for a new job while locked.
    lock_slot: jax.Array     # int32 scalar: queue slot mid-unit, -1 if none
    lock_job: jax.Array      # int32 scalar: job id the lock belongs to
    # fixed-size job queue, (Q,) each
    q_active: jax.Array      # bool
    q_release: jax.Array     # f32
    q_deadline: jax.Array    # f32 (absolute)
    q_task: jax.Array        # int32, index into the (K, ...) task tables
    q_job: jax.Array         # int32, index into the (K, J, U) profile tables
    q_unit: jax.Array        # int32, next unit to execute
    q_time_left: jax.Array   # f32, seconds left in the current unit
    q_exited: jax.Array      # int32, unit where the utility test passed (-1)
    q_last_pred: jax.Array   # int32, deepest executed unit (-1)
    q_mand_time: jax.Array   # f32, mandatory-completion time (-1)
    # live-profile registers (repro.serve.fleet_engine): when the step runs
    # in ``live`` mode the margins/passes/correct *tables* are never read —
    # the serving engine classifies the just-executed unit against its
    # evolving centroid bank and injects the outcome here instead.  Replay
    # mode neither reads nor writes them.
    q_margin: jax.Array      # f32, live margin after the last executed unit
    q_correct: jax.Array     # bool, live prediction correct at last unit
    q_apass: jax.Array       # bool, utility test has passed at some unit
    # metric accumulators, (K,) per task (mirror scheduler.SimResult.task_*)
    m_scheduled: jax.Array   # int32
    m_correct: jax.Array     # int32
    m_misses: jax.Array      # int32
    m_units: jax.Array       # int32
    m_optional: jax.Array    # int32
    # device-level energy/time accumulators (scalars)
    m_reboots: jax.Array     # int32
    m_busy: jax.Array        # f32
    m_idle: jax.Array        # f32
    m_wasted: jax.Array      # f32


class StepResult(NamedTuple):
    """Finalized metrics — SimResult-shaped, per device.

    With the fleet's stacked device axis, aggregate fields are ``(D,)``
    (summed over the task set, matching the scalar ``SimResult`` totals) and
    the ``task_*`` fields break the job counters down per task as ``(D, K)``
    arrays (see :class:`repro.fleet.state.FleetResult`, an alias).
    """

    released: jax.Array
    scheduled: jax.Array
    correct: jax.Array
    deadline_misses: jax.Array
    units_executed: jax.Array
    optional_units: jax.Array
    busy_time: jax.Array
    idle_no_energy: jax.Array
    reboots: jax.Array
    wasted_reexec: jax.Array
    sim_time: jax.Array
    # per-task breakdowns, (K,) / fleet (D, K)
    task_released: jax.Array
    task_scheduled: jax.Array
    task_correct: jax.Array
    task_misses: jax.Array
    task_units: jax.Array
    task_optional: jax.Array

    def device(self, i: int) -> dict:
        """Metrics of device ``i`` as a python dict (SimResult field names);
        scalar metrics become python numbers, per-task rows become lists."""
        out = {}
        for k, v in self._asdict().items():
            row = v[i]
            out[k] = row.item() if row.ndim == 0 else row.tolist()
        return out

    def as_dict(self) -> dict:
        """JSON-serializable dict mirroring ``SimResult.as_dict``: scalar
        leaves become python numbers, array leaves (the ``(D,)`` metric
        columns and ``(D, K)`` ``task_*`` breakdowns) become nested lists —
        what ``benchmarks/run.py`` writes into ``BENCH_<name>.json``."""
        out = {}
        for k, v in self._asdict().items():
            a = np.asarray(v)
            out[k] = a.item() if a.ndim == 0 else a.tolist()
        return out


def init_carry(params: StepParams, statics: StepStatics) -> DeviceCarry:
    """Initial carry for one device (call under vmap for a fleet)."""
    q = statics.queue_size
    k = params.period.shape[0]   # per-device view: task axis is leading
    f32 = jnp.float32
    i32 = jnp.int32
    zero_i = jnp.zeros((), i32)
    zeros_k = jnp.zeros((k,), i32)
    return DeviceCarry(
        energy=params.start_energy.astype(f32),
        was_off=jnp.zeros((), bool),
        next_rel=zeros_k,
        rr_cursor=zero_i,
        lock_slot=jnp.full((), -1, i32),
        lock_job=jnp.full((), -1, i32),
        q_active=jnp.zeros((q,), bool),
        q_release=jnp.zeros((q,), f32),
        q_deadline=jnp.zeros((q,), f32),
        q_task=jnp.zeros((q,), i32),
        q_job=jnp.zeros((q,), i32),
        q_unit=jnp.zeros((q,), i32),
        q_time_left=jnp.zeros((q,), f32),
        q_exited=jnp.full((q,), -1, i32),
        q_last_pred=jnp.full((q,), -1, i32),
        q_mand_time=jnp.full((q,), -1.0, f32),
        q_margin=jnp.zeros((q,), f32),
        q_correct=jnp.zeros((q,), bool),
        q_apass=jnp.zeros((q,), bool),
        m_scheduled=zeros_k,
        m_correct=zeros_k,
        m_misses=zeros_k,
        m_units=zeros_k,
        m_optional=zeros_k,
        m_reboots=zero_i,
        m_busy=jnp.zeros((), f32),
        m_idle=jnp.zeros((), f32),
        m_wasted=jnp.zeros((), f32),
    )


# --------------------------------------------------------------------------- #
# Transition stages.
# --------------------------------------------------------------------------- #


def finish_counts(params: StepParams, st: DeviceCarry, mask: jax.Array,
                  live: bool = False):
    """Tally (scheduled, correct, missed) for the queue slots in ``mask``,
    broken down per task — ``(..., K)`` int arrays each.  ``live`` reads the
    slot's live correctness register instead of the replay table.

    Batch-polymorphic and gather-free: leading axes on every leaf (vmap's
    device axis, or a Pallas tile's block axis) ride along untouched."""
    n_tasks = params.period.shape[-1]
    tk = jnp.clip(st.q_task, 0, n_tasks - 1)
    sched = mask & (st.q_mand_time >= 0.0) & (st.q_mand_time <= st.q_deadline)
    if live:
        corr = sched & (st.q_last_pred >= 0) & st.q_correct
    else:
        n_jobs = params.margins.shape[-2]
        n_u = params.margins.shape[-1]
        job = jnp.clip(st.q_job, 0, n_jobs - 1)
        lp = jnp.clip(st.q_last_pred, 0, n_u - 1)
        corr = sched & (st.q_last_pred >= 0) & _take(
            params.correct, (tk * n_jobs + job) * n_u + lp, 3)
    miss = mask & ~sched
    onehot = _oh_eq(tk, n_tasks)                           # (..., Q, K)

    def per_task(m):
        # int32 before the trailing expand: Mosaic refuses bool shape casts
        return jnp.sum(jnp.where(onehot, m.astype(jnp.int32)[..., None], 0),
                       axis=-2)

    return per_task(sched), per_task(corr), per_task(miss)


class StepTrace(NamedTuple):
    """Per-step event descriptors of ONE device transition (telemetry's
    in-scan emission; see :mod:`repro.telemetry.trace` for the decoding).

    Every retirement a step can produce flows through exactly one of three
    channels, each bounded to at most one event per task (admission order
    admits one release per task per step; same-task deadlines are spaced a
    full period apart, far more than ``dt`` at realistic ppm-scale clock
    drift) — so fixed-size ``(K,)`` words capture a step losslessly and the
    telemetry reduction never needs the ``(Q,)`` queue axis after the scan.

    Word packing (0 = no event): ``exited + 2`` in bits 0-5, the task id in
    bits 6-10 where present, ``job + 1`` in the bits above.  The ``*_dl``
    floats carry the retiring slot's deadline *register* (so slack needs no
    reconstruction); garbage where the matching word is 0.
    """

    adm: jax.Array       # (K,) i32: insert | dropped << 1 | evict << 2
    evict: jax.Array     # (K,) i32: victim (job+1)<<11 | task<<6 | exited+2
    evict_dl: jax.Array  # (K,) f32: victim q_deadline
    expire: jax.Array    # (K,) i32: expired (job+1)<<6 | exited+2
    expire_dl: jax.Array  # (K,) f32: expired-slot q_deadline
    complete: jax.Array  # i32: retiring job_done (job+1)<<11|task<<6|exited+2
    complete_dl: jax.Array  # f32: completed slot q_deadline


@jax.named_scope("admit")
def admit(params: StepParams, st: DeviceCarry, t, statics: StepStatics,
          live: bool = False, trace: bool = False):
    """Admit at most one released job per task (the builder asserts
    dt < period).  The static python loop over the task axis admits in task
    order — the same order the scalar path's stable release sort yields for
    simultaneous releases.

    ``trace`` (python-level, so the plain path's program is untouched)
    additionally returns the admission/eviction descriptor words of
    :class:`StepTrace` — read from registers the stage already computed.
    """
    q = statics.queue_size
    n_tasks = params.period.shape[-1]
    n_u = params.unit_time.shape[-1]
    k_iota = lax.broadcasted_iota(jnp.int32, st.next_rel.shape,
                                  st.next_rel.ndim - 1)       # (..., K)
    tr_adm, tr_evict, tr_evict_dl = [], [], []
    for k in range(n_tasks):
        nr_k = st.next_rel[..., k]
        rel_time = nr_k.astype(_F32) * params.period[..., k]
        releasing = (nr_k < params.n_releases[..., k]) & (rel_time <= t)

        free = ~st.q_active
        has_free = jnp.any(free, axis=-1)
        # overflow: evict the earliest-deadline job whose mandatory part is
        # done (optional-only work yields to the new arrival — mandatory
        # first, §5.2)
        evictable = st.q_active & (st.q_exited >= 0)
        has_evict = jnp.any(evictable, axis=-1)
        victim = argmin_first(jnp.where(evictable, st.q_deadline, jnp.inf))
        evict = releasing & ~has_free & has_evict
        vmask = _col(evict) & _oh_eq(victim, q)
        d_sched, d_corr, d_miss = finish_counts(params, st, vmask, live)

        insert = releasing & (has_free | has_evict)
        slot = jnp.where(has_free, first_true(free), victim)
        ins = _col(insert) & _oh_eq(slot, q)
        dropped = releasing & ~insert   # queue overflow, nothing evictable
        k_hot = k_iota == k

        if trace:
            # the victim's pre-step registers (a just-admitted job has
            # q_exited == -1, so it is never evictable — victims always
            # hold jobs that were queued before this step began).  Trace
            # emission is per-device only (telemetry wraps it in vmap).
            tr_adm.append(insert.astype(jnp.int32)
                          + (dropped.astype(jnp.int32) << 1)
                          + (evict.astype(jnp.int32) << 2))
            tr_evict.append(jnp.where(
                evict,
                ((st.q_job[victim] + 1) << 11) + (st.q_task[victim] << 6)
                + (st.q_exited[victim] + 2), 0).astype(jnp.int32))
            tr_evict_dl.append(st.q_deadline[victim].astype(_F32))

        st = st._replace(
            next_rel=st.next_rel + (k_hot & _col(releasing)),
            q_active=(st.q_active & ~vmask) | ins,
            q_release=jnp.where(ins, rel_time[..., None], st.q_release),
            q_deadline=jnp.where(
                ins, (rel_time + params.rel_deadline[..., k])[..., None],
                st.q_deadline),
            q_task=jnp.where(ins, k, st.q_task),
            q_job=jnp.where(ins, nr_k[..., None], st.q_job),
            q_unit=jnp.where(ins, 0, st.q_unit),
            q_time_left=jnp.where(
                ins, _flat(params.unit_time, 2)[..., k * n_u:k * n_u + 1],
                st.q_time_left),
            q_exited=jnp.where(ins, -1, st.q_exited),
            q_last_pred=jnp.where(ins, -1, st.q_last_pred),
            q_mand_time=jnp.where(ins, -1.0, st.q_mand_time),
            q_margin=jnp.where(ins, 0.0, st.q_margin),
            q_correct=st.q_correct & ~ins,
            q_apass=st.q_apass & ~ins,
            m_scheduled=st.m_scheduled + d_sched,
            m_correct=st.m_correct + d_corr,
            m_misses=st.m_misses + d_miss + (_col(dropped) & k_hot),
        )
    if trace:
        return st, (jnp.stack(tr_adm), jnp.stack(tr_evict),
                    jnp.stack(tr_evict_dl))
    return st


@jax.named_scope("expire")
def drop_expired(params: StepParams, st: DeviceCarry, t,
                 live: bool = False, trace: bool = False,
                 q_active_pre=None):
    # the device expires jobs against its *drifting* clock (fleet CHRT
    # model): a fast clock (drift > 0) drops jobs before their true deadline
    t_read = t * (1.0 + params.clock_drift)
    expired = st.q_active & (t_read[..., None] >= st.q_deadline)
    d_sched, d_corr, d_miss = finish_counts(params, st, expired, live)
    new = st._replace(
        q_active=st.q_active & ~expired,
        m_scheduled=st.m_scheduled + d_sched,
        m_correct=st.m_correct + d_corr,
        m_misses=st.m_misses + d_miss,
    )
    if trace:
        # at most one same-task deadline crosses per dt (deadlines are a
        # period apart), so a single packed word per task is lossless; the
        # q_active_pre guard drops jobs admitted this very step, which the
        # delta-view reference (step_events) never counts as retirements
        # (per-device only, like every trace branch)
        n_tasks = params.period.shape[-1]
        exp = expired if q_active_pre is None else expired & q_active_pre
        word = ((st.q_job + 1) << 6) + (st.q_exited + 2)
        onehot = exp[:, None] & (st.q_task[:, None]
                                 == jnp.arange(n_tasks)[None, :])
        tr_exp = jnp.sum(
            jnp.where(onehot, word[:, None], 0), axis=0).astype(jnp.int32)
        tr_exp_dl = jnp.sum(
            jnp.where(onehot, st.q_deadline[:, None], 0.0),
            axis=0).astype(_F32)
        return new, (tr_exp, tr_exp_dl)
    return new


def pick_inputs(params: StepParams, st: DeviceCarry, t,
                statics: StepStatics, live: bool = False):
    """Per-slot priority/energy ingredients shared by the jnp pick and the
    Pallas kernel: each slot gathers its own task's row of the (K, U) /
    (K, J, U) tables before the shared priority math runs.  ``live`` swaps
    the replayed utility margin for the slot's live margin register."""
    n_tasks = params.period.shape[-1]
    n_u = params.unit_time.shape[-1]
    tk = jnp.clip(st.q_task, 0, n_tasks - 1)
    u = jnp.clip(st.q_unit, 0, n_u - 1)
    unit_t = _take(params.unit_time, tk * n_u + u, 2)
    unit_e = _take(params.unit_energy, tk * n_u + u, 2)
    gate_e = jnp.maximum(unit_e / _take(params.fragments, tk),
                         params.e_man[..., None])
    drain = unit_e * (statics.dt / unit_t)
    if live:
        margin = st.q_margin
    else:
        n_jobs = params.margins.shape[-2]
        job = jnp.clip(st.q_job, 0, n_jobs - 1)
        lp = jnp.clip(st.q_last_pred, 0, params.margins.shape[-1] - 1)
        margin = _take(params.margins,
                       (tk * n_jobs + job) * params.margins.shape[-1] + lp, 3)
    utility = jnp.where(st.q_last_pred >= 0, margin, 0.0)
    mandatory = st.q_exited < 0
    laxity = st.q_deadline - t
    n_slots = params.events.shape[-1]
    slot = jnp.minimum((t / statics.slot_s).astype(jnp.int32), n_slots - 1)
    amp = _take1(params.events, slot)
    charge = amp * params.power_on * statics.dt
    # limited preemption: a slot mid-unit is forced until the unit boundary
    # (unless it expired or its slot was recycled for a newer job)
    ls = jnp.clip(st.lock_slot, 0, st.q_active.shape[-1] - 1)
    locked = ((st.lock_slot >= 0) & _take1(st.q_active, ls)
              & (_take1(st.q_job, ls) == st.lock_job))
    forced = jnp.where(locked, ls, -1).astype(jnp.int32)
    # rr task rotation: distance of each slot's task from the rr cursor
    # (identically 0 when K == 1, keeping the FIFO key bit-identical)
    task_rank = jnp.mod(tk - st.rr_cursor[..., None], n_tasks).astype(_F32)
    return (laxity, utility, mandatory, gate_e, drain, charge, forced,
            task_rank)


def select_and_charge(scores, threshold, forced, energy, charge, capacity,
                      gate_e, drain):
    """Post-score selection + fused capacitor update — the shared reference
    semantics of the pick stage.

    Reduces over the trailing (queue) axis; leading axes batch.  The jnp
    pick calls this with ``(Q,)`` scores and scalar per-device operands, the
    Pallas ``fleet_priority`` kernel with ``(block_d, Q)`` VMEM tiles — both
    therefore apply the exact same argmax / threshold / energy-gate math.
    Uses only iota/arithmetic (no gathers) so the body is Mosaic-safe.
    """
    sel = jnp.where(forced >= 0, forced,
                    argmax_first(scores)).astype(jnp.int32)
    picked = (forced >= 0) | (jnp.max(scores, axis=-1) > threshold)
    # lane-select the chosen slot's energy gate / drain (iota keeps the
    # expression gather-free inside Pallas tiles)
    onehot = (lax.broadcasted_iota(jnp.int32, scores.shape, scores.ndim - 1)
              == sel[..., None])
    gate_sel = jnp.sum(jnp.where(onehot, gate_e, 0.0), axis=-1)
    drain_sel = jnp.sum(jnp.where(onehot, drain, 0.0), axis=-1)
    run = picked & (energy >= gate_sel)
    e_new = jnp.minimum(energy + charge, capacity) - run * drain_sel
    return sel, picked, run, e_new


@jax.named_scope("pick")
def pick(params: StepParams, st: DeviceCarry, t, statics: StepStatics,
         live: bool = False):
    """Priority-argmax + fused capacitor charge/discharge (pure-jnp path).

    Per-device operands enter ``policy_scores`` as ``x[..., None]`` so they
    broadcast against the ``(..., Q)`` queue-shaped operands regardless of
    leading batch axes; the threshold comes back ``(..., 1)`` and is
    squeezed for :func:`select_and_charge`'s trailing-axis reduction."""
    (laxity, utility, mandatory, gate_e, drain, charge, forced,
     task_rank) = pick_inputs(params, st, t, statics, live)
    scores, thr = P.policy_scores(
        params.policy[..., None], st.q_active, laxity, st.q_release,
        utility, mandatory, params.alpha[..., None], params.beta[..., None],
        params.eta[..., None], st.energy[..., None], params.e_opt[..., None],
        _col(params.persistent), task_rank)
    return select_and_charge(scores, thr[..., 0], forced, st.energy, charge,
                             params.capacity, gate_e, drain)


@jax.named_scope("apply")
def apply_step(params: StepParams, st: DeviceCarry, t, sel, picked, run,
               e_new, statics: StepStatics, live: bool = False,
               outcomes=None, trace: bool = False, q_active_pre=None,
               t_end=None):
    """Advance the selected job by dt; handle unit/job completion.

    ``t_end`` is the step's end-of-interval clock.  Callers that know the
    integer step index should pass ``(i + 1) * dt`` — a single correctly-
    rounded multiply, bit-identical in every execution context.  The
    ``t + dt`` fallback is a mul feeding an add, which compilers may
    contract into a single-rounding FMA *differently per program* (the
    fused Pallas kernel vs the vmap scan), drifting ``q_mand_time`` by
    1 ulp and breaking carry bit-parity.

    ``live``/``outcomes`` form the live-profile hook
    (:mod:`repro.serve.fleet_engine`): ``outcomes`` is a
    ``(margin, passed, correct)`` scalar triple for the *selected* slot's
    just-completing unit, computed by classifying the real model features
    against the engine's evolving centroid bank.  At most one slot
    completes per step (the ``oh`` mask), so scalars suffice; the values
    land in the ``q_margin``/``q_correct`` registers and replace every
    read of the ``margins``/``passes``/``correct`` replay tables.  With
    ``live=False`` (and ``outcomes=None``) the replay path is untouched
    and bit-identical to before the hook existed.
    """
    q = statics.queue_size
    n_tasks = params.period.shape[-1]
    n_u = params.unit_time.shape[-1]
    u_max = n_u - 1
    oh = _oh_eq(sel, q)
    tk = jnp.clip(st.q_task, 0, n_tasks - 1)
    tk_sel = _take1(tk, sel)

    u_sel = jnp.clip(_take1(st.q_unit, sel), 0, u_max)
    frag_t = (_take1(params.unit_time, tk_sel * n_u + u_sel, 2)
              / _take1(params.fragments, tk_sel))

    # power-down / reboot bookkeeping (the initial cold boot counts wasted
    # half-fragment re-execution but not a reboot — matches the scalar path)
    reboot = run & st.was_off
    was_off = ~run & (picked | st.was_off)
    idle_inc = jnp.where(picked & ~run, statics.dt, 0.0)

    # execute dt of the selected unit
    time_left = st.q_time_left - jnp.where(_col(run) & oh,
                                           statics.dt, 0.0)
    complete = _col(run) & oh & (time_left <= statics.dt * 1e-3)

    u = jnp.clip(st.q_unit, 0, u_max)
    job = jnp.clip(st.q_job, 0, params.passes.shape[-2] - 1)
    n_units = _take(params.n_units, tk)        # (..., Q) per-slot task depth
    next_u = jnp.clip(st.q_unit + 1, 0, u_max)
    done_any = jnp.any(complete, axis=-1)
    mandatory = st.q_exited < 0

    last_pred = jnp.where(complete, u, st.q_last_pred)
    unit = jnp.where(complete, st.q_unit + 1, st.q_unit)
    time_left = jnp.where(
        complete, _take(params.unit_time, tk * n_u + next_u, 2),
        time_left)

    # utility test at the unit boundary (imprecise policies only); tuned
    # per-unit thresholds (repro.adapt) re-evaluate the test against the
    # live margin, otherwise the precomputed passes table applies
    if live:
        margin_sel, passed_sel, correct_sel = outcomes
        if jnp.ndim(passed_sel) == complete.ndim - 1:
            # batch-polymorphic: outcomes carry the leading device/tile
            # axes but not the queue axis — expand so the broadcasts below
            # align the right way up (value-identical on the vmap path,
            # where the outcomes are rank-0 scalars)
            margin_sel = margin_sel[..., None]
            passed_sel = _col(passed_sel)
            correct_sel = _col(correct_sel)
        passed = jnp.broadcast_to(passed_sel, complete.shape)
        q_margin = jnp.where(complete, margin_sel, st.q_margin)
        q_correct = select_bool(complete, correct_sel, st.q_correct)
        st = st._replace(q_margin=q_margin, q_correct=q_correct)
    else:
        n_jobs = params.margins.shape[-2]
        kju = (tk * n_jobs + job) * n_u + u
        passed = select_bool(
            _col(params.use_exit_thr),
            P.exit_test(_take(params.margins, kju, 3),
                        _take(params.exit_thr, tk * n_u + u, 2)),
            _take(params.passes, kju, 3))
    exit_now = (complete & _col(params.imprecise)
                & (st.q_exited < 0) & passed)
    exited = jnp.where(exit_now, u, st.q_exited)
    # never-confident full execution => the whole DNN was mandatory
    full_mand = complete & (exited < 0) & (st.q_unit + 1 >= n_units)
    exited = jnp.where(full_mand, n_units - 1, exited)
    if t_end is None:
        t_end = t + statics.dt
    mand_time = jnp.where(exit_now | full_mand, t_end, st.q_mand_time)

    job_done = complete & (
        (st.q_unit + 1 >= n_units)
        | (_col(params.is_edfm) & (exited >= 0))
    )
    st_done = st._replace(q_last_pred=last_pred, q_mand_time=mand_time)
    d_sched, d_corr, d_miss = finish_counts(params, st_done, job_done, live)

    # hold the lock while the unit is in progress (including power-gated
    # waits, like the scalar fragment loop); release at the unit boundary
    lock_on = picked & ~done_any
    # rr task rotation advances past the task whose unit just completed —
    # the unit-boundary analogue of the scalar rotation at each pick
    is_rr = params.policy == P.POLICY_IDS["rr"]
    rr_cursor = jnp.where(is_rr & done_any, jnp.mod(tk_sel + 1, n_tasks),
                          st.rr_cursor).astype(jnp.int32)
    sel_hot = _oh_eq(tk_sel, n_tasks)
    if trace:
        # only the selected slot can complete, so one scalar word per step
        # covers the job_done channel; the q_active_pre guard excludes a
        # job admitted and finished within the same step (no q_active flag
        # change, so the delta-view reference never sees it retire).
        # exited >= 0 always holds at job_done (full_mand backfills it).
        jd_sel = job_done[sel]
        if q_active_pre is not None:
            jd_sel = jd_sel & q_active_pre[sel]
        tr_comp = jnp.where(
            jd_sel,
            ((st.q_job[sel] + 1) << 11) + (tk_sel << 6) + (exited[sel] + 2),
            0).astype(jnp.int32)
        tr_comp_dl = st.q_deadline[sel].astype(_F32)
    out = st._replace(
        energy=e_new,
        was_off=was_off,
        rr_cursor=rr_cursor,
        lock_slot=jnp.where(lock_on, sel, -1).astype(jnp.int32),
        lock_job=jnp.where(lock_on, _take1(st.q_job, sel),
                           -1).astype(jnp.int32),
        q_active=st.q_active & ~job_done,
        q_unit=unit,
        q_time_left=time_left,
        q_exited=exited,
        q_last_pred=last_pred,
        q_mand_time=mand_time,
        m_scheduled=st.m_scheduled + d_sched,
        m_correct=st.m_correct + d_corr,
        m_misses=st.m_misses + d_miss,
        m_units=st.m_units + (_col(done_any) & sel_hot),
        m_optional=st.m_optional + (
            _col(done_any & ~_take1(mandatory, sel)) & sel_hot),
        m_reboots=st.m_reboots + (reboot & (st.m_busy > 0)),
        m_busy=st.m_busy + jnp.where(run, statics.dt, 0.0),
        m_idle=st.m_idle + idle_inc,
        m_wasted=st.m_wasted + jnp.where(reboot, 0.5 * frag_t, 0.0),
    )
    if trace:
        return out, (tr_comp, tr_comp_dl)
    return out


def device_step(params: StepParams, st: DeviceCarry, t,
                statics: StepStatics, trace: bool = False, t_end=None):
    """One full per-device transition: admit -> expire -> pick -> apply.

    ``trace=True`` (a python flag: the plain program is byte-identical)
    additionally returns the step's :class:`StepTrace` descriptor words —
    the in-scan fold :mod:`repro.telemetry.trace` consumes them.
    ``t_end`` forwards to :func:`apply_step` (see there for why callers
    with the integer step index should pass ``(i + 1) * dt``).
    """
    if trace:
        act0 = st.q_active
        st, (tr_adm, tr_ev, tr_ev_dl) = admit(params, st, t, statics,
                                              trace=True)
        st, (tr_exp, tr_exp_dl) = drop_expired(params, st, t, trace=True,
                                               q_active_pre=act0)
        sel, picked, run, e_new = pick(params, st, t, statics)
        st, (tr_comp, tr_comp_dl) = apply_step(
            params, st, t, sel, picked, run, e_new, statics, trace=True,
            q_active_pre=act0, t_end=t_end)
        return st, StepTrace(adm=tr_adm, evict=tr_ev, evict_dl=tr_ev_dl,
                             expire=tr_exp, expire_dl=tr_exp_dl,
                             complete=tr_comp, complete_dl=tr_comp_dl)
    st = admit(params, st, t, statics)
    st = drop_expired(params, st, t)
    sel, picked, run, e_new = pick(params, st, t, statics)
    return apply_step(params, st, t, sel, picked, run, e_new, statics,
                      t_end=t_end)


class StepEvents(NamedTuple):
    """Observable events of ONE device transition, derived purely from the
    ``(before, after)`` carry pair — the single source of truth consumed by
    :mod:`repro.telemetry`.

    Deriving events from carry *deltas* (rather than instrumenting the
    transition stages) keeps the step math byte-for-byte identical whether
    or not anyone is watching: the counters below are differences of the
    same ``m_*`` accumulators the metrics already use, so telemetry totals
    reconcile exactly against :class:`StepResult`, and the per-slot fields
    are best-effort reads of the queue registers at retirement (a slot
    recycled by an admit-evict in the same step reports its *pre-step*
    registers).
    """

    releases: jax.Array      # i32: jobs released this step (sum over tasks)
    misses: jax.Array        # i32: deadline misses this step
    scheduled: jax.Array     # i32: on-time completions this step
    retired: jax.Array       # (Q,) bool: slots that left the queue
    slack: jax.Array         # (Q,) f32: deadline - t_end for retired slots
    exit_depth: jax.Array    # (Q,) i32: q_exited at retirement (-1 = never)
    power_fail: jax.Array    # bool: the device powered down this step
    reboots: jax.Array       # i32: reboot-count delta
    queue_occ: jax.Array     # i32: active queue slots after the step
    energy: jax.Array        # f32: capacitor energy after the step


def step_events(st0: DeviceCarry, st1: DeviceCarry, t,
                statics: StepStatics) -> StepEvents:
    """Derive :class:`StepEvents` from one transition's before/after carries.

    Pure, per-device (vmap adds the fleet axis), and read-only — calling it
    cannot perturb the simulation.  ``retired`` covers both cleared slots
    and slots recycled for a new job by an overflow-evict in the same step;
    for the latter the pre-step registers are reported.
    """
    recycled = st0.q_active & st1.q_active & (
        (st1.q_job != st0.q_job) | (st1.q_task != st0.q_task))
    retired = (st0.q_active & ~st1.q_active) | recycled
    t_end = t + statics.dt
    return StepEvents(
        releases=jnp.sum(st1.next_rel - st0.next_rel).astype(jnp.int32),
        misses=jnp.sum(st1.m_misses - st0.m_misses).astype(jnp.int32),
        scheduled=jnp.sum(
            st1.m_scheduled - st0.m_scheduled).astype(jnp.int32),
        retired=retired,
        slack=(st0.q_deadline - t_end).astype(_F32),
        exit_depth=jnp.where(recycled, st0.q_exited, st1.q_exited),
        power_fail=st1.was_off & ~st0.was_off,
        reboots=(st1.m_reboots - st0.m_reboots).astype(jnp.int32),
        queue_occ=jnp.sum(st1.q_active).astype(jnp.int32),
        energy=st1.energy.astype(_F32),
    )


def finalize(params: StepParams, st: DeviceCarry,
             statics: StepStatics, live: bool = False) -> StepResult:
    """Flush live jobs and count never-admitted releases as misses; emit
    both the per-task (K,) counters and their aggregates."""
    d_sched, d_corr, d_miss = finish_counts(params, st, st.q_active, live)
    unreleased = params.n_releases - st.next_rel    # (K,)
    t_sched = st.m_scheduled + d_sched
    t_corr = st.m_correct + d_corr
    t_miss = st.m_misses + d_miss + unreleased
    return StepResult(
        released=jnp.sum(params.n_releases),
        scheduled=jnp.sum(t_sched),
        correct=jnp.sum(t_corr),
        deadline_misses=jnp.sum(t_miss),
        units_executed=jnp.sum(st.m_units),
        optional_units=jnp.sum(st.m_optional),
        busy_time=st.m_busy,
        idle_no_energy=st.m_idle,
        reboots=st.m_reboots,
        wasted_reexec=st.m_wasted,
        sim_time=jnp.full((), statics.horizon, _F32),
        task_released=params.n_releases,
        task_scheduled=t_sched,
        task_correct=t_corr,
        task_misses=t_miss,
        task_units=st.m_units,
        task_optional=st.m_optional,
    )


@functools.partial(jax.jit, static_argnames=("statics",))
def simulate_device(params: StepParams, statics: StepStatics) -> StepResult:
    """Simulate ONE device: a scalar ``lax.scan`` over the step core with no
    ``vmap`` anywhere — the reference the fleet path is bit-exact against
    (see :func:`repro.core.scheduler.simulate_stepped`)."""
    carry0 = init_carry(params, statics)

    def step(st, i):
        return device_step(params, st, i.astype(_F32) * statics.dt,
                           statics,
                           t_end=(i + 1).astype(_F32) * statics.dt), None

    carry, _ = lax.scan(step, carry0, jnp.arange(statics.n_steps))
    return finalize(params, carry, statics)
