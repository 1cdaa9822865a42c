"""Vectorized live serving: real agile-model execution inside the fleet path.

:class:`repro.serve.engine.ServeEngine` is the *faithful* live path — an
event-driven python loop serving one job at a time, executing DNN units and
adapting k-means centroids in exactly the order the scheduler chose.  It
cannot scale past a handful of devices.  The fleet simulator scales to
thousands of devices but only *replays* precomputed ``(K, J, U)`` profile
tables.  This module closes the gap: one jitted ``lax.scan`` serves live
traffic for a whole fleet, with real unit outcomes and runtime centroid
adaptation threaded through the unified device step.

The key factorisation: per-unit *features* are a pure function of the input
— runtime adaptation moves only the k-means *centroids*, never the DNN
weights — so the engine precomputes features for every (job, unit) in one
batched scan-over-units pass (``_AgileBase.unit_features``) outside the
scheduling scan, and keeps only the state that actually evolves (the
centroid bank) inside it.  Each timestep then:

1. runs the step core's admit / drop-expired / pick stages in ``live`` mode
   (``vmap`` over devices, margins read from the live registers);
2. gathers the selected slot's (task, job, unit) identity per device;
3. classifies the completing unit's *real* features against the device's
   *current* centroid bank (same L1 top-2 arithmetic as
   :func:`repro.core.kmeans.classify`);
4. injects the ``(margin, passed, correct)`` outcome into
   :func:`repro.core.step.apply_step`;
5. adapts the bank where the utility test passed for the first time
   (weighted-average update + centroid propagation to deeper units, paper
   §4.3), exactly as ``DynamicJobProfile`` does one job at a time.

Because classification/adaptation are elementwise per device and the step
core is the same ``vmap``-ed transition the replay fleet uses, the live
fleet is *bit-exact* against a scalar :class:`ServeEngine` run on workloads
where the event-driven and fixed-step clocks coincide (persistent power,
charged start, unit times commensurate with ``dt`` — see
``tests/test_fleet_engine.py``).

Bank modes:

* ``per-device`` (default): every device owns a full centroid bank —
  ``ServeBank`` leaves carry a leading ``D`` axis and shard with the fleet
  (:func:`repro.launch.sharding.shard_serve_carry`).  This is the mode the
  scalar parity holds in.
* ``shared``: one global bank; every device's first-pass exits fold into a
  single collaborative :func:`repro.core.kmeans.online_update` per (task,
  unit) each step — the fleet-scale collaborative-adaptation substrate.

The scan carry (:class:`repro.fleet.state.ServeCarry`) is a flat pytree, so
``run(..., n_segments=N)`` checkpoints it at segment boundaries exactly like
:func:`repro.fleet.simulator.run_segments` — bit-identical to the monolithic
scan for any ``N``.
"""
from __future__ import annotations

import functools
import time
from dataclasses import dataclass
from typing import NamedTuple, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..core import step as S
from ..core.energy import Capacitor, Harvester
from ..core.scheduler import JobProfile, TaskSpec
from ..fleet import grid
from ..fleet.simulator import finalize_fleet
from ..kernels._tiling import pairwise_sum
from ..telemetry import state as T
from ..telemetry import trace as T_trace
from ..telemetry.spans import span
from ..fleet.state import (
    FleetConfig,
    FleetResult,
    FleetStatics,
    ServeBank,
    ServeCarry,
    ServeLog,
    init_state,
)
from .engine import Request, ServeConfig, per_task

_F32 = jnp.float32
_I32 = jnp.int32

# padded cluster rows sit this far from everything: never in the L1 top-2
_FAR = 1e15
# the kernel's second-minimum mask value (repro.kernels.l1_topk2.POS)
_POS = 1e30


class ServeTables(NamedTuple):
    """Read-only per-request / per-classifier tables consumed by the scan.

    Shapes use ``K`` tasks, ``J`` jobs, ``U`` units, ``C`` clusters, ``S``
    selected features, ``F`` padded full-feature width (always one wider
    than the largest real feature dim: the extra column is zero everywhere
    and is where padded ``fidx`` entries point, so padding is L1-exact).
    With per-device request streams every *feature/label* leaf gains a
    leading ``D`` axis; the classifier metadata never does.
    """

    sel_feats: jax.Array     # ([D,] K, J, U, S) f32 — selected-dim features
    full_feats: jax.Array    # ([D,] K, J, U, F) f32 — full-dim (adaptation)
    labels: jax.Array        # ([D,] K, J) i32 — request ground truth
    clabels: jax.Array       # (K, U, C) i32 — cluster -> class label
    fidx: jax.Array          # (K, U, S) i32 — SelectKBest dims (pad -> F-1)
    thr: jax.Array           # (K, U) f32 — bank utility thresholds


@dataclass(frozen=True)
class BankMeta:
    """Static (python) shape metadata for the stacked bank."""

    n_units: tuple           # per task
    n_clusters: tuple        # per (task, unit)
    feat_dim: tuple          # per (task, unit) real feature width
    n_sel: tuple             # per (task, unit) real selected count


def stack_banks(models: Sequence) -> tuple[ServeBank, dict, BankMeta]:
    """Stack every model's per-unit :class:`UnitClassifier` bank into the
    padded ``(K, U, C, F)`` tables of a :class:`ServeBank` (+ the read-only
    classifier metadata for :class:`ServeTables`).

    Padding conventions (all L1- and update-exact, see module docstring):
    dummy cluster rows at ``_FAR`` with label -1 and count 1; features
    zero-padded to a common width ``F`` that always includes one guaranteed
    all-zero trailing column for padded ``fidx`` entries.
    """
    K = len(models)
    n_units = tuple(m.n_units for m in models)
    U = max(n_units)
    n_clusters = tuple(
        tuple(int(uc.centroids.shape[0]) for uc in m.bank) for m in models)
    feat_dim = tuple(
        tuple(int(uc.centroids.shape[1]) for uc in m.bank) for m in models)
    n_sel = tuple(
        tuple(int(uc.feature_idx.shape[0]) for uc in m.bank) for m in models)
    C = max(max(r) for r in n_clusters)
    S = max(max(r) for r in n_sel)
    F = max(max(r) for r in feat_dim) + 1    # +1: the all-zero pad column

    cents = np.full((K, U, C, F), _FAR, np.float32)
    counts = np.ones((K, U, C), np.float32)
    clabels = np.full((K, U, C), -1, np.int32)
    fidx = np.full((K, U, S), F - 1, np.int32)
    thr = np.zeros((K, U), np.float32)
    for k, m in enumerate(models):
        for u, uc in enumerate(m.bank):
            c = np.asarray(uc.centroids, np.float32)
            kc, fu = c.shape
            cents[k, u, :kc, :fu] = c
            cents[k, u, :kc, fu:] = 0.0
            counts[k, u, :kc] = np.asarray(uc.counts, np.float32)
            clabels[k, u, :kc] = np.asarray(uc.labels, np.int32)
            ns = n_sel[k][u]
            fidx[k, u, :ns] = np.asarray(uc.feature_idx, np.int32)
            thr[k, u] = float(uc.threshold)
    bank = ServeBank(centroids=jnp.asarray(cents), counts=jnp.asarray(counts))
    tables = dict(clabels=jnp.asarray(clabels), fidx=jnp.asarray(fidx),
                  thr=jnp.asarray(thr))
    return bank, tables, BankMeta(n_units, n_clusters, feat_dim, n_sel)


def build_feature_tables(
    models: Sequence,
    requests_per_task: Sequence[Sequence[Request]],
    meta: BankMeta,
    bank_tables: dict,
    *,
    feature_batch: Optional[int] = None,
    n_jobs: Optional[int] = None,
) -> dict:
    """Precompute the (job, unit) feature tables for one request stream.

    Features come from ``unit_features`` (scan-over-units, chunked by
    ``feature_batch``); the selected-dim gather happens host-side against
    the *initial* feature selection — valid for the whole run because
    ``feature_idx`` never adapts.  ``n_jobs`` fixes the job axis (so
    per-device streams of different lengths stack); default = longest
    stream given.
    """
    K = len(models)
    J = int(n_jobs or max(len(r) for r in requests_per_task))
    fidx = np.asarray(bank_tables["fidx"])
    U, S = fidx.shape[1], fidx.shape[2]
    F = max(max(r) for r in meta.feat_dim) + 1
    sel = np.zeros((K, J, U, S), np.float32)
    full = np.zeros((K, J, U, F), np.float32)
    labels = np.full((K, J), -1, np.int32)
    for k, (m, reqs) in enumerate(zip(models, requests_per_task)):
        if not reqs:
            continue
        feats = m.unit_features([r.x for r in reqs],
                                batch_size=feature_batch)
        for u, f in enumerate(feats):
            full[k, :len(reqs), u, :f.shape[1]] = f
            ns = meta.n_sel[k][u]
            sel[k, :len(reqs), u, :ns] = f[:, fidx[k, u, :ns]]
        labels[k, :len(reqs)] = [r.label for r in reqs]
    return dict(sel_feats=sel, full_feats=full, labels=labels)


@jax.named_scope("classify")
def classify_unit(bank: ServeBank, tables: ServeTables, tk, u, job):
    """Single-row live classification for one device's completing unit.

    The pure-jnp row variant of :func:`repro.core.kmeans.classify`: same
    elementwise ``|x - c|`` reduced in the same fixed order
    (:func:`repro.kernels._tiling.pairwise_sum`), same one-hot-masked
    second minimum (mask value :data:`_POS`), same scale-free margin — so
    the result is bit-identical to the scalar path's ``l1_topk2`` kernel
    (interpret mode) on the same operands (asserted in
    ``tests/test_fleet_engine.py``).  Returns
    ``(margin, cluster_idx, pred)``.
    """
    fsel = tables.sel_feats[tk, job, u]                       # (S,)
    idxs = tables.fidx[tk, u]                                 # (S,)
    csel = bank.centroids[tk, u][:, idxs]                     # (C, S)
    dist = pairwise_sum(jnp.abs(fsel[None, :] - csel))        # (C,)
    d1 = jnp.min(dist)
    ci = jnp.argmin(dist).astype(_I32)
    d2 = jnp.min(jnp.where(jnp.arange(dist.shape[0]) == ci, _POS, dist))
    margin = (d2 - d1) / jnp.maximum(d1 + d2, 1e-9)
    pred = tables.clabels[tk, u, ci]
    return margin, ci, pred


def select_centroids(centroids, fidx):
    """The centroid bank restricted to each classifier's selected feature
    dims: ``(..., K, U, C, F)`` gathered by ``fidx`` ``(K, U, S)`` ->
    ``(..., K, U, C, S)`` — the only columns classification reads."""
    idx = jnp.broadcast_to(fidx[..., None, :],
                           centroids.shape[:-1] + fidx.shape[-1:])
    return jnp.take_along_axis(centroids, idx, axis=-1)


class ServeLookup(NamedTuple):
    """What :func:`serve_step` reads, in the flat-row form of its lookups.

    Built outside the step (:func:`serve_lookup`) so the step itself never
    reshapes or gathers a table: inside the fused Pallas kernel a reshape
    that splits or merges the tiled axes is refused, and a gather over the
    full feature width cannot run in a device tile.  The ``[D,]`` axis is
    present on the per-device request streams / banks only.
    """

    feat_rows: jax.Array     # ([D,] K*J*U, S) f32 — selected-dim features
    cent_rows: jax.Array     # ([D,] K*U*C, S) f32 — centroids, same dims
    labels: jax.Array        # ([D,] K*J) i32 — request ground truth
    clabels: jax.Array       # (K*U*C,) i32 — cluster -> class label
    thr: jax.Array           # (K*U,) f32 — bank utility thresholds


@jax.named_scope("lookup")
def serve_lookup(tables: ServeTables, centroids) -> ServeLookup:
    """:class:`ServeLookup` of ``tables`` against the bank ``centroids``
    (``([D,] K, U, C, F)``)."""
    sel = select_centroids(centroids, tables.fidx)
    sf = tables.sel_feats
    return ServeLookup(
        feat_rows=sf.reshape(sf.shape[:-4] + (-1, sf.shape[-1])),
        cent_rows=sel.reshape(sel.shape[:-4] + (-1, sel.shape[-1])),
        labels=tables.labels.reshape(tables.labels.shape[:-2] + (-1,)),
        clabels=tables.clabels.reshape(-1),
        thr=tables.thr.reshape(-1))


@jax.named_scope("lookup")
def refresh_cent_rows(cent_rows, centroids, fidx, tk, ci, moved):
    """``cent_rows`` (``([D,] K*U*C, S)``, :attr:`ServeLookup.cent_rows`)
    brought up to the adapted bank ``centroids`` (``([D,] K, U, C, F)``).

    A per-device adaptation moves rows ``(tk_d, :, ci_d)`` of each device
    ``d`` with ``moved`` set, and no others: the assigned row and the rows
    its propagation refreshes.  So only those U rows a device are selected
    again — a row gather, then ``fidx[tk_d]``'s columns of it — and every
    other row keeps its bits.  A shared bank (no device axis) takes
    updates from many devices at once; its small view is selected whole.
    Either way the result is ``select_centroids(centroids, fidx)`` bit for
    bit, since a gather copies values."""
    if centroids.ndim == fidx.ndim + 1:
        return select_centroids(centroids, fidx).reshape(cent_rows.shape)
    K, U, C, _ = centroids.shape[1:]
    Sn = fidx.shape[-1]

    def one(rows, cents, t, c, m):
        new = jnp.take_along_axis(cents[t, :, c], fidx[t], axis=-1)  # (U, S)
        hit = (m & (jnp.arange(K)[:, None, None, None] == t)
               & (jnp.arange(C)[None, None, :, None] == c))
        rows = jnp.where(hit, new[None, :, None, :],
                         rows.reshape((K, U, C, Sn)))
        return rows.reshape((K * U * C, Sn))

    return jax.vmap(one)(cent_rows, centroids, tk, ci, moved)


@jax.named_scope("classify")
def _classify_rows(look: ServeLookup, n_tasks: int, tk, u, job):
    """Batch-polymorphic twin of :func:`classify_unit`.

    ``tk``/``u``/``job`` carry arbitrary leading axes (the scan passes
    ``(D,)``, the fused kernel a ``(bd,)`` tile); the :class:`ServeLookup`
    leaves may or may not share those leading axes (shared vs per-device
    modes).  All gathers go through the dual-lowering
    :func:`repro.core.step.take_rows` / ``_take1`` helpers so the same
    trace compiles as ``take_along_axis`` under XLA and as one-hot iota
    contractions inside Mosaic — and the arithmetic (fixed-order L1
    reduction over the same ``(..., C, S)`` operand, first-min tie-break,
    one-hot-masked second minimum, scale-free margin) matches
    :func:`classify_unit` bit-for-bit.
    """
    Ub = look.thr.shape[-1] // n_tasks
    Wl = look.labels.shape[-1] // n_tasks
    C = look.clabels.shape[-1] // look.thr.shape[-1]
    ku = tk * Ub + u
    fsel = S.take_rows(look.feat_rows, (tk * Wl + job) * Ub + u)   # (.., S)
    # the C centroid rows of (task, unit), assembled row by row: a one-hot
    # row pick per cluster, placed with x + 0 == x (exact)
    shape = fsel.shape[:-1] + (C,) + fsel.shape[-1:]
    iota_c = lax.broadcasted_iota(_I32, shape, len(shape) - 2)
    csel = jnp.zeros(shape, _F32)
    for c in range(C):
        row = S.take_rows(look.cent_rows, ku * C + c)
        csel = csel + jnp.where(iota_c == c, row[..., None, :], 0.0)
    dist = pairwise_sum(jnp.abs(fsel[..., None, :] - csel))
    d1 = jnp.min(dist, axis=-1)
    ci = S.argmin_first(dist).astype(_I32)
    iota_d = lax.broadcasted_iota(_I32, dist.shape, dist.ndim - 1)
    d2 = jnp.min(jnp.where(iota_d == ci[..., None], _POS, dist), axis=-1)
    margin = (d2 - d1) / jnp.maximum(d1 + d2, 1e-9)
    pred = S._take1(look.clabels, ku * C + ci)
    return margin, ci, pred


def flat_log(log: ServeLog) -> ServeLog:
    """``(..., K, J)`` log leaves -> ``(..., K*J)``, the form
    :func:`serve_step` updates."""
    return ServeLog(*[l.reshape(l.shape[:-2] + (-1,)) for l in log])


def serve_step(cfg: FleetConfig, look: ServeLookup, dev, log: ServeLog, t,
               job0, *, statics: FleetStatics):
    """One live-serving timestep for every device — batch-polymorphic.

    The whole-fleet twin of :meth:`FleetServeEngine._scan_steps`'s per-step
    body, written over arbitrary leading device axes so the exact same
    trace runs as the scan body (XLA, leading ``(D,)``) *and* inside the
    fused Pallas segment kernel (a ``(bd,)`` VMEM tile under
    :func:`repro.core.step.onehot_lowering`): admit → drop-expired → pick →
    classify against the bank (``look``, :func:`serve_lookup`) → inject
    ``(margin, passed, correct)`` into :func:`repro.core.step.apply_step`
    → latch the utility pass → write the per-job outcome log, whose leaves
    come and go flat (:func:`flat_log`).

    ``job0`` (``(K,)`` i32) rebases global job ids into the streamed table
    window: row ``j`` of the ``(..., K, Wl)`` feature/label/log leaves holds
    job ``job0[k] + j``.  The monolithic path passes zeros, making the
    rebasing the identity.  Bank adaptation stays fleet-level (the
    propagation convs don't tile) — the engine applies it after this step
    from the returned ``(first_pass, tk, u, job, ci)`` aux; the ordering
    swap is exact because the log never reads the bank.

    Like :func:`repro.core.step.apply_step`'s live mode, ``t_end`` is left
    to the ``t + dt`` fallback in *both* execution contexts so the serve
    paths stay bit-identical to each other and to the scalar engine.
    """
    K = cfg.period.shape[-1]
    n_u = cfg.unit_time.shape[-1]
    Ue = cfg.exit_thr.shape[-1]
    Wl = look.labels.shape[-1] // K
    Ub = look.thr.shape[-1] // K
    Q = statics.queue_size

    dev = S.admit(cfg, dev, t, statics, True)
    dev = S.drop_expired(cfg, dev, t, True)
    sel, picked, run, e_new = S.pick(cfg, dev, t, statics, True)

    # selected-slot identity, pre-apply
    tk = jnp.clip(S._take1(dev.q_task, sel), 0, K - 1)
    u = jnp.clip(S._take1(dev.q_unit, sel), 0, n_u - 1)
    job = jnp.clip(S._take1(dev.q_job, sel) - S._take1(job0, tk),
                   0, Wl - 1)
    complete = run & (S._take1(dev.q_time_left, sel) - statics.dt
                      <= statics.dt * 1e-3)
    exited_pre = S._take1(dev.q_exited, sel)
    apass_pre = S._take1(dev.q_apass, sel)
    ddl = S._take1(dev.q_deadline, sel)
    nu_sel = S._take1(cfg.n_units, tk)
    thr_cfg = S._take1(cfg.exit_thr, tk * Ue + u, 2)

    margin, ci, pred = _classify_rows(look, K, tk, u, job)
    correct = pred == S._take1(look.labels, tk * Wl + job)
    pass_bank = margin > S._take1(look.thr, tk * Ub + u)
    passed = S.select_bool(cfg.use_exit_thr, margin > thr_cfg, pass_bank)

    dev = S.apply_step(cfg, dev, t, sel, picked, run, e_new, statics, True,
                       (margin, passed, correct))

    # engine-owned utility-pass latch: adaptation fires at the FIRST
    # bank-threshold pass (like DynamicJobProfile — even under EDF, where
    # the scheduler itself never exits early)
    first_pass = complete & pass_bank & ~apass_pre
    oh = S._oh_eq(sel, Q)
    dev = dev._replace(
        q_apass=dev.q_apass | (oh & S._col(complete & pass_bank)))

    # per-job outcome log (mirrors apply_step's completion math)
    exit_now = complete & cfg.imprecise & (exited_pre < 0) & passed
    exited_mid = jnp.where(exit_now, u, exited_pre)
    full_mand = complete & (exited_mid < 0) & (u + 1 >= nu_sel)
    mand_now = exit_now | full_mand
    sched_now = (t + statics.dt) <= ddl
    m_jd = S._col(complete) & S._oh_eq(tk * Wl + job, K * Wl)

    def put(old, new, mask=None):
        mm = m_jd if mask is None else m_jd & S._col(mask)
        if old.dtype == jnp.bool_:
            return S.select_bool(mm, S._col(new), old)
        return jnp.where(mm, new[..., None], old)

    log = ServeLog(
        units=put(log.units, u + 1),
        pred=put(log.pred, pred),
        correct=put(log.correct, correct),
        margin=put(log.margin, margin),
        exit_unit=put(log.exit_unit, u, first_pass),
        sched=put(log.sched, sched_now, mand_now),
    )
    return dev, log, (first_pass, tk, u, job, ci)


def _shift_log(log: ServeLog, shift):
    """Advance the per-task log window by ``shift`` jobs.

    Row ``j`` of the new window is row ``j + shift[k]`` of the old; rows
    shifted in from beyond the old window reset to the t=0 defaults (the
    same values :meth:`FleetServeEngine.build`'s ``log0`` uses, so a job
    that is never served reads identically in streamed and monolithic
    runs).  ``shift`` is a traced ``(K,)`` i32 — every chunk shares one
    compiled program.
    """
    Wl = log.units.shape[-1]
    K = shift.shape[-1]
    jj = lax.broadcasted_iota(_I32, (K, Wl), 1)
    src = jj + shift[..., None]
    valid = src < Wl
    srcc = jnp.clip(src, 0, Wl - 1)

    def gather(leaf, default):
        idx = jnp.broadcast_to(srcc, leaf.shape)
        moved = jnp.take_along_axis(leaf, idx, axis=-1)
        return jnp.where(valid, moved, jnp.asarray(default, leaf.dtype))

    return ServeLog(
        units=gather(log.units, 0),
        pred=gather(log.pred, -1),
        correct=gather(log.correct, False),
        margin=gather(log.margin, 0.0),
        exit_unit=gather(log.exit_unit, -1),
        sched=gather(log.sched, False),
    )


def _device_peak_bytes() -> int:
    """Peak live device bytes, or 0 where the backend keeps no memory
    statistics (plain-CPU ``memory_stats()`` returns ``None``)."""
    stats = jax.local_devices()[0].memory_stats()
    if not stats:
        return 0
    return int(stats.get("peak_bytes_in_use", 0))


@dataclass
class FleetServeResult:
    """Outcome of one vectorized live-serving run.

    ``fleet`` holds the step core's SimResult-shaped ``(D,)`` aggregates
    (live-mode finalize: correctness from the live registers); the per-job
    arrays are the numpy view of the :class:`ServeLog` (``(D, K, J)``
    each).  ``carry`` is the end-of-horizon :class:`ServeCarry` for
    checkpoint/resume; ``wall_s`` is the host-clock length of the run's
    ``serve.scan`` span (:func:`repro.telemetry.span`): the jitted scan
    through the finalize, feature precompute excluded, so ``jobs_per_sec``
    counts scan time only.
    """

    fleet: FleetResult
    units: np.ndarray
    pred: np.ndarray
    correct: np.ndarray
    margin: np.ndarray
    exit_unit: np.ndarray
    sched: np.ndarray
    carry: ServeCarry
    jobs: int
    wall_s: float
    telemetry: Optional[T.Telemetry] = None
    #: steady-state/compile split (streaming runs): ``wall_s`` above counts
    #: staging + execution only; one-time chunk-runner compiles land here
    compile_s: float = 0.0
    #: backend peak live bytes after the run (0 on stats-less backends)
    peak_bytes: int = 0
    #: device bytes of ONE staged feature-window table — the O(chunk)
    #: resident footprint that replaces the O(total jobs) tables of `run`
    chunk_table_bytes: int = 0
    n_chunks: int = 1

    @property
    def jobs_per_sec(self) -> float:
        return self.jobs / max(self.wall_s, 1e-9)


class FleetServeEngine:
    """Vectorized live serving of agile-model tasks across a device fleet.

    Same constructor shape as the scalar :class:`ServeEngine` plus the
    fleet knobs: ``bank_mode`` ("per-device" | "shared") and
    ``feature_batch`` (chunk size of the feature precompute; ``1``
    reproduces the scalar engine's per-sample arithmetic exactly).
    """

    def __init__(
        self,
        models: Sequence,
        harvester: Harvester,
        eta: float,
        cap: Optional[Capacitor] = None,
        config: Optional[ServeConfig] = None,
        *,
        bank_mode: str = "per-device",
        feature_batch: Optional[int] = None,
        adapt_weight: float = 32.0,
    ):
        if bank_mode not in ("per-device", "shared"):
            raise ValueError(f"unknown bank_mode {bank_mode!r}")
        self.models = list(models)
        self.harvester = harvester
        self.eta = eta
        self.cap = cap or Capacitor()
        self.config = config or ServeConfig()
        self.bank_mode = bank_mode
        self.feature_batch = feature_batch
        self.adapt_weight = float(adapt_weight)
        self.bank0, self._bank_tables, self.meta = stack_banks(self.models)
        self._runners: dict = {}
        # AOT-compiled streaming chunk runners, keyed by (static config,
        # arg shape/dtype signature).  jit's own dispatch cache is NOT
        # populated by ``lower().compile()``, so the executables are cached
        # and invoked directly — same-shape chunks never recompile.
        self._compiled: dict = {}

    # ------------------------------------------------------------------ #
    # Builders.
    # ------------------------------------------------------------------ #

    def _task_specs(self, n_jobs_per_task: Sequence[int]) -> list[TaskSpec]:
        """TaskSpecs with *dummy* zero profiles: live mode never reads the
        replay tables, but the grid builder still sizes ``n_releases`` and
        the clip bounds from them."""
        cfg = self.config
        periods = per_task(cfg.period, len(self.models))
        deadlines = per_task(cfg.deadline, len(self.models))
        tasks = []
        for tid, (m, n_jobs) in enumerate(zip(self.models,
                                              n_jobs_per_task)):
            nu = m.n_units
            ut = (np.asarray(cfg.unit_time, float)
                  if cfg.unit_time is not None else np.full(nu, 0.2))
            ue = (np.asarray(cfg.unit_energy, float)
                  if cfg.unit_energy is not None else np.full(nu, 5e-3))
            zeros = JobProfile(np.zeros(nu), np.zeros(nu, bool),
                               np.zeros(nu, bool))
            tasks.append(TaskSpec(
                task_id=tid, period=periods[tid], deadline=deadlines[tid],
                unit_time=ut[:nu], unit_energy=ue[:nu],
                profiles=[zeros] * n_jobs,
                fragments_per_unit=cfg.fragments_per_unit,
            ))
        return tasks

    def build(
        self,
        requests,
        n_devices: Optional[int] = None,
        *,
        seeds: Optional[Sequence[int]] = None,
    ) -> tuple[FleetConfig, FleetStatics, ServeTables, ServeCarry, bool]:
        """Materialise configs, statics, feature tables and the t=0 carry.

        ``requests`` is either one stream shared by every device —
        ``requests[task][job]`` — or per-device streams
        ``requests[device][task][job]`` (detected by nesting).  Returns
        ``(cfg, statics, tables, carry0, per_dev_tables)``.
        """
        cfg = self.config
        per_dev = not isinstance(requests[0][0], Request)
        if per_dev:
            D = len(requests)
            if n_devices is not None and n_devices != D:
                raise ValueError(
                    f"n_devices={n_devices} but {D} request streams given")
            streams = requests
        else:
            D = int(n_devices or 1)
            streams = [requests] * D
        if len(streams[0]) != len(self.models):
            raise ValueError(
                f"{len(streams[0])} request streams per device for "
                f"{len(self.models)} models")

        n_jobs = [max(len(s[k]) for s in streams)
                  for k in range(len(self.models))]
        tasks = self._task_specs(n_jobs)
        dt = grid._check_dt(
            grid._default_dt(tasks) if cfg.sim_dt is None
            else float(cfg.sim_dt), tasks)
        statics = FleetStatics(queue_size=cfg.queue_size, dt=dt,
                               horizon=cfg.horizon,
                               slot_s=self.harvester.slot_s)
        seeds = (list(seeds) if seeds is not None
                 else [cfg.seed] * D)
        if len(seeds) != D:
            raise ValueError(f"{len(seeds)} seeds for {D} devices")
        with span("serve.build.configs"):
            events = {s: grid.sample_events(self.harvester, cfg.horizon, s)
                      for s in set(seeds)}
            devs = [grid.device_config(
                tasks, self.harvester, self.eta, self.cap,
                policy=cfg.policy, horizon=cfg.horizon, events=events[s],
                e_opt_fraction=cfg.e_opt_fraction,
                start_charged=cfg.start_charged,
            ) for s in seeds]
            fleet_cfg = grid.stack_configs(devs)

        # one shared stream is featurized once, not once per device
        featurized = streams if per_dev else streams[:1]
        with span("serve.build.featurize",
                  frames=sum(len(r) for s in featurized for r in s)):
            feats = [build_feature_tables(
                self.models, s, self.meta, self._bank_tables,
                feature_batch=self.feature_batch, n_jobs=max(n_jobs))
                for s in featurized]
            if per_dev:
                stacked = {k: jnp.asarray(np.stack([f[k] for f in feats]))
                           for k in feats[0]}
            else:
                stacked = {k: jnp.asarray(v) for k, v in feats[0].items()}
            tables = ServeTables(**stacked, **self._bank_tables)

        with span("serve.build.carry"):
            dev0 = jax.vmap(lambda c: init_state(c, statics))(fleet_cfg)
            bank0 = self.bank0
            if self.bank_mode == "per-device":
                bank0 = jax.tree.map(
                    lambda l: jnp.broadcast_to(l, (D,) + l.shape), bank0)
            K, J = len(self.models), max(n_jobs)
            log0 = ServeLog(
                units=jnp.zeros((D, K, J), _I32),
                pred=jnp.full((D, K, J), -1, _I32),
                correct=jnp.zeros((D, K, J), bool),
                margin=jnp.zeros((D, K, J), _F32),
                exit_unit=jnp.full((D, K, J), -1, _I32),
                sched=jnp.zeros((D, K, J), bool),
            )
        return (fleet_cfg, statics, tables,
                ServeCarry(dev=dev0, bank=bank0, log=log0), per_dev)

    # ------------------------------------------------------------------ #
    # The jitted scan.
    # ------------------------------------------------------------------ #

    def _adapt_per_device(self, bank: ServeBank, x_full, tk, u, ci, do):
        """One device's weighted-average bank update + centroid propagation
        (unbatched; the runner vmaps it over the fleet).

        Bit-matches ``km.adapt`` + ``_propagate_from`` on one sample: the
        assigned row becomes ``(w c + x) / (w + 1)`` (the kernel's one-hot
        matmul contributes exactly ``x``), every other row is untouched
        (the kernel computes ``(w c) / w`` — exact for ``w = 32``), and the
        propagation chain refreshes row ``ci`` of each deeper unit from the
        *progressively updated* shallower tables, exactly as the scalar
        loop does."""
        w = self.adapt_weight
        K_, U_, C_, _ = bank.centroids.shape
        m3 = (do
              & (jnp.arange(K_)[:, None, None] == tk)
              & (jnp.arange(U_)[None, :, None] == u)
              & (jnp.arange(C_)[None, None, :] == ci))
        # the barrier keeps the divisor out of constant folding: XLA would
        # otherwise rewrite /(w+1) into *(1/(w+1)) under jit, drifting one
        # ulp off the scalar path's true division
        denom = lax.optimization_barrier(jnp.float32(w + 1.0))
        cents = jnp.where(m3[..., None],
                          (w * bank.centroids + x_full) / denom,
                          bank.centroids)
        counts = bank.counts + m3
        for k, m in enumerate(self.models):
            for v in range(m.n_units - 1):
                act = do & (tk == k) & (u <= v)
                kc = self.meta.n_clusters[k][v]
                f_in = self.meta.feat_dim[k][v]
                f_out = self.meta.feat_dim[k][v + 1]
                r = counts[k, v, :kc, None]
                src = cents[k, v, :kc, :f_in]
                img = jax.nn.relu(m.unit_apply_flat(v + 1, r * src)) / r
                row = (jnp.arange(kc) == ci) & act
                new = jnp.where(row[:, None], img,
                                cents[k, v + 1, :kc, :f_out])
                cents = cents.at[k, v + 1, :kc, :f_out].set(new)
        return ServeBank(centroids=cents, counts=counts)

    def _adapt_shared(self, bank: ServeBank, x_full, tk, u, ci, do):
        """Collaborative shared-bank update: all devices exiting at (k, u)
        this step fold into ONE :func:`km.online_update` (batch-averaged —
        the documented semantic difference vs sequential per-device
        adaptation), then one propagation sweep refreshes every touched
        row of the deeper units."""
        from ..core import kmeans as km

        cents, counts = bank.centroids, bank.counts
        C_ = cents.shape[2]
        for k, m in enumerate(self.models):
            hot = jnp.zeros((C_,), bool)
            for v in range(m.n_units):
                kc = self.meta.n_clusters[k][v]
                fu = self.meta.feat_dim[k][v]
                mrow = do & (tk == k) & (u == v)
                idxk = jnp.where(mrow, ci, -1)
                new_c, new_n = km.online_update(
                    cents[k, v, :kc, :fu], counts[k, v, :kc],
                    x_full[:, :fu], idxk, weight=self.adapt_weight)
                cents = cents.at[k, v, :kc, :fu].set(new_c)
                counts = counts.at[k, v, :kc].set(new_n)
                if v == m.n_units - 1:
                    break
                hot = hot | jnp.any(
                    mrow[:, None] & (jnp.arange(C_)[None, :] == ci[:, None]),
                    axis=0)
                f_out = self.meta.feat_dim[k][v + 1]
                r = counts[k, v, :kc, None]
                src = cents[k, v, :kc, :fu]
                img = jax.nn.relu(m.unit_apply_flat(v + 1, r * src)) / r
                new = jnp.where(hot[:kc, None], img,
                                cents[k, v + 1, :kc, :f_out])
                cents = cents.at[k, v + 1, :kc, :f_out].set(new)
        return ServeBank(centroids=cents, counts=counts)

    def _scan_steps(self, cfg: FleetConfig, tables: ServeTables,
                    carry, i0, tel=None, job0=None, *,
                    statics: FleetStatics,
                    n_steps: int, adapt: bool, shared: bool,
                    per_dev_tables: bool,
                    tcfg: Optional[T.TelemetryConfig] = None):
        """Scan ``n_steps`` live timesteps from step index ``i0``.

        The per-step transition is the batch-polymorphic
        :func:`serve_step` (shared verbatim with the fused Pallas kernel),
        plus the fleet-level bank adaptation from its aux outputs.
        ``job0`` (``(K,)`` i32, default zeros) rebases global job ids into
        streamed table windows — see :meth:`run_stream`.

        With ``tcfg`` set, the scan emits the telemetry columns of the
        requested tier and reduces them into ``tel`` post-scan, returning
        ``(ServeCarry, Telemetry, ring_columns)``: at the ``"counters"``
        tier the plain step body emits three registers it already computed
        (``ring_columns`` is ``None``); at the ``"full"`` tier the stages
        run their descriptor-emitting twins
        (:class:`repro.core.step.StepTrace`), the events are bit-packed
        per step, and the caller folds the rare ring/histogram events
        host-side via :func:`repro.telemetry.trace.fold_events_host`.  The
        serve numerics cannot change: tracing only adds outputs."""
        trace = tcfg is not None and tcfg.level == "full"
        counters = tcfg is not None and not trace
        spec = (T_trace.make_pack_spec(int(cfg.period.shape[1]),
                                       statics.queue_size,
                                       int(cfg.unit_time.shape[-1]) + 1)
                if trace else None)
        K = cfg.period.shape[1]
        u_max = cfg.unit_time.shape[2] - 1
        J = tables.labels.shape[-1]
        Q = statics.queue_size
        if job0 is None:
            job0 = jnp.zeros((K,), _I32)
        tab_axes = ServeTables(
            sel_feats=0 if per_dev_tables else None,
            full_feats=0 if per_dev_tables else None,
            labels=0 if per_dev_tables else None,
            clabels=None, fidx=None, thr=None)
        bank_ax = None if shared else 0

        def gather(c, s, a, r):
            """Selected-slot identity for one device, pre-apply."""
            tk = jnp.clip(s.q_task[a], 0, K - 1)
            u = jnp.clip(s.q_unit[a], 0, u_max)
            job = jnp.clip(s.q_job[a] - job0[tk], 0, J - 1)
            complete = r & (s.q_time_left[a] - statics.dt
                            <= statics.dt * 1e-3)
            return (tk, u, job, complete, s.q_exited[a], s.q_apass[a],
                    s.q_deadline[a], c.n_units[tk], c.imprecise,
                    c.use_exit_thr, c.exit_thr[tk, u])

        @jax.named_scope("adapt")
        def adapt_bank(bank, rows, tk, u, job, ci, first_pass):
            """The adapted bank, and its selected-column view ``rows``
            (``None`` where the caller classifies from the bank itself)
            refreshed to match it."""
            Ub = tables.fidx.shape[-2]
            if per_dev_tables:
                x_full = tables.full_feats[
                    jnp.arange(tk.shape[0]), tk, job, u]
            else:
                ff = tables.full_feats.reshape(
                    (K * J * Ub, tables.full_feats.shape[-1]))
                x_full = S.take_rows(ff, (tk * J + job) * Ub + u)

            def _upd(args):
                b, r, xf, tkk, uu, cii, fp = args
                if shared:
                    b = self._adapt_shared(b, xf, tkk, uu, cii, fp)
                else:
                    b = jax.vmap(self._adapt_per_device)(
                        b, xf, tkk, uu, cii, fp)
                if r is not None:
                    r = refresh_cent_rows(r, b.centroids, tables.fidx, tkk,
                                          cii, fp)
                return b, r

            # each device passes rarely, but across the fleet some device
            # passes on most steps; a step where none does skips the
            # propagation convs and the refresh
            return lax.cond(
                jnp.any(first_pass), _upd, lambda args: args[:2],
                (bank, rows, x_full, tk, u, ci, first_pass))

        look0 = serve_lookup(tables, carry.bank.centroids)
        # an adapting bank carries its selected columns through the scan,
        # refreshed where adaptation moves them; a frozen one's stay look0's
        rows0 = look0.cent_rows if adapt and not trace else None

        def step(carry, i):
            (dev, bank, log), rows = carry
            t = i.astype(_F32) * statics.dt
            look = look0 if rows is None else look0._replace(cent_rows=rows)
            dev, flog, (first_pass, tk, u, job, ci) = serve_step(
                cfg, look, dev, flat_log(log), t, job0, statics=statics)
            log = ServeLog(*[f.reshape(l.shape) for f, l in zip(flog, log)])
            if adapt:
                bank, rows = adapt_bank(bank, rows, tk, u, job, ci,
                                        first_pass)
            new_carry = (ServeCarry(dev=dev, bank=bank, log=log), rows)
            if counters:
                return new_carry, T_trace.emit_counters(dev)
            return new_carry, None

        def step_trace(carry, i):
            (dev, bank, log), _ = carry
            dev0 = dev
            t = i.astype(_F32) * statics.dt
            act0 = dev.q_active
            dev, (tr_adm, tr_ev, tr_ev_dl) = jax.vmap(
                lambda c, s: S.admit(c, s, t, statics, True,
                                     trace=True))(cfg, dev)
            dev, (tr_exp, tr_exp_dl) = jax.vmap(
                lambda c, s, a0: S.drop_expired(c, s, t, True,
                                                trace=True,
                                                q_active_pre=a0)
            )(cfg, dev, act0)
            sel, picked, run, e_new = jax.vmap(
                lambda c, s: S.pick(c, s, t, statics, True))(cfg, dev)
            (tk, u, job, complete, exited_pre, apass_pre, ddl, nu_sel,
             imprec, use_thr, thr_cfg) = jax.vmap(gather)(cfg, dev, sel, run)

            margin, ci, pred = jax.vmap(
                classify_unit, in_axes=(bank_ax, tab_axes, 0, 0, 0))(
                bank, tables, tk, u, job)
            if per_dev_tables:
                label = tables.labels[jnp.arange(tk.shape[0]), tk, job]
            else:
                label = tables.labels[tk, job]
            correct = pred == label
            pass_bank = margin > tables.thr[tk, u]
            passed = jnp.where(use_thr, margin > thr_cfg, pass_bank)

            dev, (tr_comp, tr_comp_dl) = jax.vmap(
                lambda c, s, a, p, r, e, mg, ps, co, a0: S.apply_step(
                    c, s, t, a, p, r, e, statics, True, (mg, ps, co),
                    trace=True, q_active_pre=a0))(
                cfg, dev, sel, picked, run, e_new, margin, passed,
                correct, act0)
            tr = S.StepTrace(adm=tr_adm, evict=tr_ev,
                             evict_dl=tr_ev_dl, expire=tr_exp,
                             expire_dl=tr_exp_dl, complete=tr_comp,
                             complete_dl=tr_comp_dl)

            # engine-owned utility-pass latch: adaptation fires at the FIRST
            # bank-threshold pass (like DynamicJobProfile — even under EDF,
            # where the scheduler itself never exits early)
            first_pass = complete & pass_bank & ~apass_pre
            oh = jnp.arange(Q)[None, :] == sel[:, None]
            dev = dev._replace(
                q_apass=dev.q_apass | (oh & (complete & pass_bank)[:, None]))

            if adapt:
                bank, _ = adapt_bank(bank, None, tk, u, job, ci, first_pass)

            # per-job outcome log (mirrors apply_step's completion math)
            exit_now = complete & imprec & (exited_pre < 0) & passed
            exited_mid = jnp.where(exit_now, u, exited_pre)
            full_mand = complete & (exited_mid < 0) & (u + 1 >= nu_sel)
            mand_now = exit_now | full_mand
            sched_now = (t + statics.dt) <= ddl
            m_jd = (complete[:, None, None]
                    & (jnp.arange(K)[None, :, None] == tk[:, None, None])
                    & (jnp.arange(J)[None, None, :] == job[:, None, None]))

            def put(old, new, mask=None):
                mm = m_jd if mask is None else m_jd & mask[:, None, None]
                return jnp.where(mm, new[:, None, None], old)

            log = ServeLog(
                units=put(log.units, u + 1),
                pred=put(log.pred, pred),
                correct=put(log.correct, correct),
                margin=put(log.margin, margin),
                exit_unit=put(log.exit_unit, u, first_pass),
                sched=put(log.sched, sched_now, mand_now),
            )
            new_carry = (ServeCarry(dev=dev, bank=bank, log=log), None)
            return new_carry, T_trace.emit_full(spec, tr, dev0, dev)

        if trace:
            step = step_trace

        st0 = carry.dev
        (carry, _), ys = lax.scan(step, (carry, rows0),
                                  i0 + jnp.arange(n_steps))
        if tcfg is None:
            return carry
        if counters:
            return carry, T_trace.reduce_counters(tel, st0, carry.dev, ys,
                                                  n_steps), None
        tel, ring = T_trace.reduce_full(spec, tel, st0, carry.dev, ys, i0,
                                        n_steps, statics.dt)
        return carry, tel, ring

    def _runner(self, statics: FleetStatics, n_steps: int, adapt: bool,
                shared: bool, per_dev_tables: bool, tcfg=None):
        key = (statics, n_steps, adapt, shared, per_dev_tables, tcfg)
        if key not in self._runners:
            fn = functools.partial(
                self._scan_steps, statics=statics, n_steps=n_steps,
                adapt=adapt, shared=shared, per_dev_tables=per_dev_tables,
                tcfg=tcfg)
            fn.__name__ = "_scan_steps"    # the executable: jit__scan_steps
            self._runners[key] = jax.jit(fn)
        return self._runners[key]

    # ------------------------------------------------------------------ #
    # Public entry point.
    # ------------------------------------------------------------------ #

    def run(
        self,
        requests,
        n_devices: Optional[int] = None,
        *,
        seeds: Optional[Sequence[int]] = None,
        n_segments: int = 1,
        carry: Optional[ServeCarry] = None,
        mesh=None,
        telemetry: Optional[T.TelemetryConfig] = None,
        mode: str = "scan",
    ) -> FleetServeResult:
        """Serve every request stream live through one jitted fleet scan.

        ``n_segments > 1`` materialises the :class:`ServeCarry` at segment
        boundaries (checkpointable, bit-identical to ``n_segments=1``);
        ``carry`` resumes from a previous run's carry.  ``mesh`` places the
        carry/config/tables with the device axis partitioned
        (:func:`repro.launch.sharding.shard_serve_carry`; ``D`` must be a
        mesh-size multiple).  ``telemetry`` (a
        :class:`repro.telemetry.TelemetryConfig`) threads a ``(D, ...)``
        telemetry pytree through the serve scan and fills
        ``FleetServeResult.telemetry`` — the serve outcome itself is
        bit-exact either way.

        ``mode="fused"`` runs each segment as ONE ``pallas_call``
        (:func:`repro.kernels.ops.serve_fused_steps`): the classify +
        live-register update execute in-tile with the centroid bank
        VMEM-resident, bit-exact vs the scan.  Adaptation moves centroids
        through whole-model convs (``unit_apply_flat``) that don't tile,
        so the fused mode requires ``adapt=False`` — and it has no
        telemetry/mesh hooks.
        """
        with span("serve.engine.run"):
            if mode not in ("scan", "fused"):
                raise ValueError(f"unknown serve mode {mode!r}")
            adapt = bool(self.config.adapt)
            if mode == "fused":
                if adapt:
                    raise ValueError(
                        "mode='fused' requires adapt=False: bank adaptation "
                        "propagates centroids through whole-model convs that "
                        "cannot run inside a device tile")
                if telemetry is not None or mesh is not None:
                    raise ValueError(
                        "mode='fused' does not support telemetry= or mesh=")
            cfg, statics, tables, carry0, per_dev = self.build(
                requests, n_devices, seeds=seeds)
            if carry is not None:
                carry0 = carry
            shared = self.bank_mode == "shared"
            tel = (None if telemetry is None
                   else T.init_fleet_telemetry(telemetry, cfg))
            if mesh is not None:
                from ..launch.sharding import (
                    shard_fleet_carry,
                    shard_fleet_config,
                    shard_serve_carry,
                    shard_serve_tables,
                )

                D = cfg.n_devices
                if D % mesh.size:
                    raise ValueError(f"D={D} devices must divide over "
                                     f"mesh size {mesh.size}")
                cfg = shard_fleet_config(mesh, cfg)
                carry0 = shard_serve_carry(mesh, carry0, shared_bank=shared)
                tables = shard_serve_tables(mesh, tables, per_device=per_dev)
                if tel is not None:
                    tel = shard_fleet_carry(mesh, tel)

            sizes = [len(c) for c in
                     np.array_split(np.arange(statics.n_steps), n_segments)]
            with span("serve.scan", steps=statics.n_steps) as scan:
                i0 = 0
                out = carry0
                for n in sizes:
                    if not n:
                        continue
                    if mode == "fused":
                        from ..kernels import ops

                        out = ops.serve_fused_steps(
                            cfg, out, serve_lookup(tables, out.bank.centroids),
                            jnp.int32(i0),
                            jnp.zeros((len(self.models),), _I32),
                            statics=statics, n_steps=n, shared_bank=shared,
                            per_dev_tables=per_dev)
                        i0 += n
                        continue
                    runner = self._runner(statics, n, adapt, shared, per_dev,
                                          telemetry)
                    if telemetry is None:
                        out = runner(cfg, tables, out, jnp.int32(i0))
                    else:
                        out, tel, ring = runner(cfg, tables, out,
                                                jnp.int32(i0), tel)
                        if ring is not None:
                            spec = T_trace.make_pack_spec(
                                int(cfg.period.shape[1]), statics.queue_size,
                                int(tel.exit_hist.shape[1]))
                            tel = T_trace.fold_events_host(
                                spec, tel, tuple(np.asarray(c) for c in ring),
                                i0, statics.dt)
                    i0 += n
                fleet = finalize_fleet(cfg, out.dev, statics, live=True)
                jax.block_until_ready(fleet)
            with span("serve.fetch"):
                log = out.log
                exit_unit = np.asarray(log.exit_unit)
                if adapt:
                    # each first pass refreshed <= U view rows of its device
                    with span("serve.bank.refresh",
                              refreshes=int((exit_unit >= 0).sum())):
                        pass
                return FleetServeResult(
                    fleet=fleet,
                    units=np.asarray(log.units),
                    pred=np.asarray(log.pred),
                    correct=np.asarray(log.correct),
                    margin=np.asarray(log.margin),
                    exit_unit=exit_unit,
                    sched=np.asarray(log.sched),
                    carry=out,
                    jobs=int(np.asarray(fleet.released).sum()),
                    wall_s=scan.seconds,
                    telemetry=tel,
                )

    # ------------------------------------------------------------------ #
    # Streaming entry point: O(chunk) device memory for any job total.
    # ------------------------------------------------------------------ #

    @staticmethod
    def _count_releases(period: float, horizon: float,
                        max_jobs: int) -> int:
        """Replicate ``grid._n_releases``'s float release accumulation
        (bit-for-bit, including the ``t += period`` slip) with the cap
        taken from the streamed job total instead of ``len(profiles)``."""
        t, j = 0.0, 0
        while t < horizon and j < max_jobs:
            t += period
            j += 1
        return j

    def build_stream(
        self,
        requests,
        n_devices: Optional[int] = None,
        *,
        seeds: Optional[Sequence[int]] = None,
        total_jobs=None,
    ):
        """Like :meth:`build`, but O(1) in the total job count.

        The grid builder gets single-job placeholder profiles (live mode
        never reads the replay tables) with ``n_releases`` overridden to
        the streamed totals, and the feature/label tables stay host-side
        numpy — :meth:`run_stream` stages a bounded window of them per
        chunk.  ``total_jobs`` (int or per-task sequence, default = the
        base stream length) sets how many jobs each task serves; totals
        beyond the base stream cycle it (job ``j`` reuses request
        ``j % len(base)``).

        Returns ``(cfg, statics, base_tables, dev0, bank0, per_dev,
        totals, base_len)`` with ``base_tables`` a numpy dict.
        """
        cfg = self.config
        K = len(self.models)
        per_dev = not isinstance(requests[0][0], Request)
        if per_dev:
            D = len(requests)
            if n_devices is not None and n_devices != D:
                raise ValueError(
                    f"n_devices={n_devices} but {D} request streams given")
            streams = requests
        else:
            D = int(n_devices or 1)
            streams = [requests]
        if len(streams[0]) != K:
            raise ValueError(
                f"{len(streams[0])} request streams per device for "
                f"{K} models")
        base_len = [max(len(s[k]) for s in streams) for k in range(K)]
        if any(b <= 0 for b in base_len):
            raise ValueError("every task needs at least one base request")
        if total_jobs is None:
            totals = list(base_len)
        elif np.ndim(total_jobs) == 0:
            totals = [int(total_jobs)] * K
        else:
            totals = [int(x) for x in total_jobs]

        tasks = self._task_specs([1] * K)
        dt = grid._check_dt(
            grid._default_dt(tasks) if cfg.sim_dt is None
            else float(cfg.sim_dt), tasks)
        statics = FleetStatics(queue_size=cfg.queue_size, dt=dt,
                               horizon=cfg.horizon,
                               slot_s=self.harvester.slot_s)
        seeds = (list(seeds) if seeds is not None else [cfg.seed] * D)
        if len(seeds) != D:
            raise ValueError(f"{len(seeds)} seeds for {D} devices")
        events = {s: grid.sample_events(self.harvester, cfg.horizon, s)
                  for s in set(seeds)}
        devs = [grid.device_config(
            tasks, self.harvester, self.eta, self.cap,
            policy=cfg.policy, horizon=cfg.horizon, events=events[s],
            e_opt_fraction=cfg.e_opt_fraction,
            start_charged=cfg.start_charged,
        ) for s in seeds]
        fleet_cfg = grid.stack_configs(devs)
        n_rel = np.array([self._count_releases(tasks[k].period, cfg.horizon,
                                               totals[k])
                          for k in range(K)], np.int32)
        fleet_cfg = fleet_cfg._replace(
            n_releases=jnp.asarray(np.broadcast_to(n_rel, (D, K)).copy()))

        feats = [build_feature_tables(
            self.models, s, self.meta, self._bank_tables,
            feature_batch=self.feature_batch, n_jobs=max(base_len))
            for s in (streams if per_dev else streams[:1])]
        if per_dev:
            base = {k: np.stack([f[k] for f in feats]) for k in feats[0]}
        else:
            base = feats[0]

        dev0 = jax.vmap(lambda c: init_state(c, statics))(fleet_cfg)
        bank0 = self.bank0
        if self.bank_mode == "per-device":
            bank0 = jax.tree.map(
                lambda l: jnp.broadcast_to(l, (D,) + l.shape), bank0)
        return (fleet_cfg, statics, base, dev0, bank0, per_dev, totals,
                base_len)

    def _stream_step_chunk(self, cfg, tables, carry, i0, job0, shift, *,
                           statics, n_steps, adapt, shared,
                           per_dev_tables, mode):
        """One donated chunk: advance the log window, scan the chunk."""
        carry = carry._replace(log=_shift_log(carry.log, shift))
        if mode == "fused":
            from ..kernels import ops

            return ops.serve_fused_steps(
                cfg, carry, serve_lookup(tables, carry.bank.centroids), i0,
                job0, statics=statics,
                n_steps=n_steps, shared_bank=shared,
                per_dev_tables=per_dev_tables)
        return self._scan_steps(cfg, tables, carry, i0, None, job0,
                                statics=statics, n_steps=n_steps,
                                adapt=adapt, shared=shared,
                                per_dev_tables=per_dev_tables, tcfg=None)

    def _stream_step_chunk_tel(self, cfg, tables, carry, i0, job0, shift,
                               tel, *, statics, n_steps, adapt, shared,
                               per_dev_tables, tcfg):
        carry = carry._replace(log=_shift_log(carry.log, shift))
        carry, tel, _ = self._scan_steps(cfg, tables, carry, i0, tel, job0,
                                         statics=statics, n_steps=n_steps,
                                         adapt=adapt, shared=shared,
                                         per_dev_tables=per_dev_tables,
                                         tcfg=tcfg)
        return carry, tel

    def _stream_runner(self, *, statics, n_steps, adapt, shared,
                       per_dev_tables, mode, tcfg, args):
        """AOT-compiled chunk runner with the carry (and telemetry)
        buffers DONATED — chunk N+1's carry reuses chunk N's memory, so
        peak live bytes don't grow with the chunk count.  ``lower().
        compile()`` bypasses jit's dispatch cache, so executables are
        cached here keyed by (static config, arg shapes/dtypes): every
        same-shape chunk reuses one compilation."""
        if tcfg is None:
            fn = functools.partial(
                self._stream_step_chunk, statics=statics, n_steps=n_steps,
                adapt=adapt, shared=shared, per_dev_tables=per_dev_tables,
                mode=mode)
            donate = (2,)
        else:
            fn = functools.partial(
                self._stream_step_chunk_tel, statics=statics,
                n_steps=n_steps, adapt=adapt, shared=shared,
                per_dev_tables=per_dev_tables, tcfg=tcfg)
            donate = (2, 6)
        sig = tuple((tuple(l.shape), str(l.dtype))
                    for l in jax.tree.leaves(args))
        key = (statics, n_steps, adapt, shared, per_dev_tables, mode,
               tcfg, sig)
        hit = self._compiled.get(key)
        if hit is not None:
            return hit, 0.0
        t0 = time.perf_counter()
        compiled = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
        cs = time.perf_counter() - t0
        self._compiled[key] = compiled
        return compiled, cs

    def run_stream(
        self,
        requests,
        n_devices: Optional[int] = None,
        *,
        seeds: Optional[Sequence[int]] = None,
        total_jobs=None,
        n_chunks: int = 1,
        mode: str = "scan",
        collect_log: bool = True,
        telemetry: Optional[T.TelemetryConfig] = None,
    ) -> FleetServeResult:
        """Serve a job stream of any length with O(chunk) device memory.

        The horizon is split into ``n_chunks`` step ranges; each chunk
        stages only the bounded window of per-job feature/label rows its
        steps can touch (computed from periods, deadlines and clock
        drift), rebases job ids with ``job0``, and runs one donated,
        AOT-cached chunk program — the :class:`ServeCarry` buffers are
        reused in place between chunks and the full per-job log is
        assembled host-side.  Bit-exact vs :meth:`run` on the same
        requests for ANY chunking.  ``total_jobs`` streams past the base
        request list by cycling it (job ``j`` serves request
        ``j % len(base)``), which is how a single call serves millions of
        jobs.  ``mode="fused"`` routes each chunk through the fused
        Pallas segment kernel.  ``telemetry`` supports the ``"counters"``
        tier (the ``"full"`` tier's ring fold is per-run host state —
        use :meth:`run`).
        """
        cfg_s = self.config
        adapt = bool(cfg_s.adapt)
        shared = self.bank_mode == "shared"
        if mode not in ("scan", "fused"):
            raise ValueError(f"unknown serve mode {mode!r}")
        if mode == "fused" and (adapt or telemetry is not None):
            raise ValueError(
                "mode='fused' requires adapt=False and no telemetry")
        if telemetry is not None and telemetry.level == "full":
            raise ValueError(
                "run_stream supports the 'counters' telemetry tier only")

        (fleet_cfg, statics, base, dev0, bank0, per_dev, totals,
         base_len) = self.build_stream(requests, n_devices, seeds=seeds,
                                       total_jobs=total_jobs)
        D = int(fleet_cfg.policy.shape[0])
        K = len(self.models)
        periods = np.array(per_task(cfg_s.period, K), float)
        deadl = np.array(per_task(cfg_s.deadline, K), float)
        drift = float(np.max(np.abs(np.asarray(fleet_cfg.clock_drift))))
        n_steps = statics.n_steps
        nc = int(max(1, min(n_chunks, max(n_steps, 1))))
        segs = [s for s in np.array_split(np.arange(n_steps), nc)
                if len(s)]
        bounds = [(int(s[0]), int(s[-1]) + 1) for s in segs]

        # per-chunk job windows: a job live during [t0, t1) must release
        # before t1 and expire after t0 (with the slow-clock drift bound
        # t_read = t * (1 + drift) stretching lifetimes by ≤ 1 + 2*drift);
        # ±2 rows absorb the f32 release-accumulation slip
        lo_list, hi_list = [], []
        for s0, s1 in bounds:
            t0s, t1s = s0 * statics.dt, s1 * statics.dt
            lo_list.append(np.floor(
                (t0s / (1.0 + 2.0 * drift) - deadl) / periods
            ).astype(np.int64) - 2)
            hi_list.append(np.floor(t1s / periods).astype(np.int64) + 2)
        Wl = int(max(int(np.max(h - l))
                     for l, h in zip(lo_list, hi_list)))
        Wl = max(Wl, 1)

        sel_b, full_b, lab_b = (base["sel_feats"], base["full_feats"],
                                base["labels"])

        def stage(w0):
            idx = w0[:, None] + np.arange(Wl)[None, :]
            ps, pf, pl = [], [], []
            for k in range(K):
                src = idx[k] % base_len[k]
                ps.append(np.take(sel_b[..., k, :, :, :], src, axis=-3))
                pf.append(np.take(full_b[..., k, :, :, :], src, axis=-3))
                pl.append(np.take(lab_b[..., k, :], src, axis=-1))
            return (np.stack(ps, axis=-4), np.stack(pf, axis=-4),
                    np.stack(pl, axis=-2))

        log0 = ServeLog(
            units=jnp.zeros((D, K, Wl), _I32),
            pred=jnp.full((D, K, Wl), -1, _I32),
            correct=jnp.zeros((D, K, Wl), bool),
            margin=jnp.zeros((D, K, Wl), _F32),
            exit_unit=jnp.full((D, K, Wl), -1, _I32),
            sched=jnp.zeros((D, K, Wl), bool),
        )
        carry = ServeCarry(dev=dev0, bank=bank0, log=log0)
        # donated chunk inputs must not alias non-donated args: init_state
        # forwards some config leaves by reference (e.g. dev.energy IS
        # cfg.start_energy when starting charged), and XLA rejects
        # `f(a, donate(a))`.  One up-front copy of the O(chunk) carry
        # breaks every such alias; later chunks reuse donated buffers.
        carry = jax.tree.map(jnp.array, carry)
        tel = (None if telemetry is None
               else T.init_fleet_telemetry(telemetry, fleet_cfg))

        full_log = None
        if collect_log:
            Jt = max(max(totals), 1)
            full_log = dict(
                units=np.zeros((D, K, Jt), np.int32),
                pred=np.full((D, K, Jt), -1, np.int32),
                correct=np.zeros((D, K, Jt), bool),
                margin=np.zeros((D, K, Jt), np.float32),
                exit_unit=np.full((D, K, Jt), -1, np.int32),
                sched=np.zeros((D, K, Jt), bool),
            )

        compile_s = 0.0
        wall = 0.0
        chunk_bytes = 0
        prev_w0 = lo_list[0]
        win_cols = np.arange(Wl)
        for (s0, s1), w0 in zip(bounds, lo_list):
            selw, fullw, labw = stage(w0)
            shift = (w0 - prev_w0).astype(np.int64)
            assert (shift >= 0).all(), "job windows must advance"
            prev_w0 = w0
            t_a = time.perf_counter()
            tabs = ServeTables(sel_feats=jnp.asarray(selw),
                               full_feats=jnp.asarray(fullw),
                               labels=jnp.asarray(labw),
                               **self._bank_tables)
            i0 = jnp.int32(s0)
            j0 = jnp.asarray(w0, _I32)
            sh = jnp.asarray(shift, _I32)
            stage_s = time.perf_counter() - t_a
            args = ((fleet_cfg, tabs, carry, i0, j0, sh) if tel is None
                    else (fleet_cfg, tabs, carry, i0, j0, sh, tel))
            runner, cs = self._stream_runner(
                statics=statics, n_steps=s1 - s0, adapt=adapt,
                shared=shared, per_dev_tables=per_dev, mode=mode,
                tcfg=telemetry, args=args)
            compile_s += cs
            t_r = time.perf_counter()
            res = runner(*args)
            jax.block_until_ready(res)
            wall += time.perf_counter() - t_r + stage_s
            if tel is None:
                carry = res
            else:
                carry, tel = res
            chunk_bytes = max(chunk_bytes, sum(
                int(np.prod(l.shape)) * l.dtype.itemsize
                for l in jax.tree.leaves(tabs)))
            if collect_log:
                win = {f: np.asarray(getattr(carry.log, f))
                       for f in ServeLog._fields}
                for k in range(K):
                    cols = w0[k] + win_cols
                    ok = (cols >= 0) & (cols < totals[k])
                    if ok.any():
                        for f in full_log:
                            full_log[f][:, k, cols[ok]] = win[f][:, k, ok]

        t_r = time.perf_counter()
        fleet = finalize_fleet(fleet_cfg, carry.dev, statics, live=True)
        jax.block_until_ready(fleet)
        wall += time.perf_counter() - t_r
        if full_log is None:
            full_log = {f: np.asarray(getattr(carry.log, f))
                        for f in ServeLog._fields}
        return FleetServeResult(
            fleet=fleet,
            units=full_log["units"],
            pred=full_log["pred"],
            correct=full_log["correct"],
            margin=full_log["margin"],
            exit_unit=full_log["exit_unit"],
            sched=full_log["sched"],
            carry=carry,
            jobs=int(np.asarray(fleet.released).sum()),
            wall_s=wall,
            telemetry=tel,
            compile_s=compile_s,
            peak_bytes=_device_peak_bytes(),
            chunk_table_bytes=chunk_bytes,
            n_chunks=len(bounds),
        )
