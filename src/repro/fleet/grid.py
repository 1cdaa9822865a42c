"""Grid construction for fleet sweeps.

Host-side (numpy) builders that translate the scalar simulator's objects —
:class:`repro.core.scheduler.TaskSpec`, :class:`repro.core.energy.Harvester`,
:class:`repro.core.energy.Capacitor`, :class:`repro.core.scheduler.SimConfig`
— into the stacked :class:`repro.fleet.state.FleetConfig` arrays consumed by
:func:`repro.fleet.simulator.simulate_fleet`.

Every builder accepts either one :class:`TaskSpec` or a *task set* (any
sequence of them), mirroring the scalar ``simulate(tasks, ...)`` signature:
the per-task tables are stacked on the ``K`` axis, padded to a common
``U`` (units) / ``J`` (jobs) so heterogeneous task sets share one array —
the live region is bounded by the per-task ``n_units`` / ``n_releases``.

The cartesian sweep mirrors the paper's benchmark grids (Figs. 17-21, 24-25):
policy × eta × harvester pattern × capacitor size × seed, one device per
grid point, all simulated by a single jitted call.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Union

import numpy as np

import jax
import jax.numpy as jnp

from ..core import policy as P
from ..core.energy import PERSISTENT, Capacitor, Harvester
from ..core.scheduler import Clock, SimConfig, TaskSpec
from ..telemetry.spans import span
from .state import FleetConfig, FleetStatics

_F32 = np.float32

TaskSet = Union[TaskSpec, Sequence[TaskSpec]]


def as_task_set(tasks: TaskSet) -> tuple[TaskSpec, ...]:
    """Normalise a single TaskSpec or a sequence of them to a tuple."""
    if isinstance(tasks, TaskSpec):
        return (tasks,)
    out = tuple(tasks)
    if not out:
        raise ValueError("empty task set")
    if len({t.task_id for t in out}) != len(out):
        raise ValueError("task_ids within one task set must be unique")
    return out


def _n_releases(task: TaskSpec, horizon: float) -> int:
    # replicates the scalar release loop bit-for-bit — including its float
    # *accumulation* of t += period, which can slip one extra release under
    # the horizon when the period is not exactly representable (e.g. 1.2 s
    # accumulated 10× is 11.999999999999998 < 12.0, where the closed-form
    # ceil(horizon / period) says 10)
    t, j = 0.0, 0
    while t < horizon and j < len(task.profiles):
        t += task.period
        j += 1
    return j


def _check_dt(dt: float, tasks: TaskSet) -> float:
    """The fixed timestep must stay within one fragment time of every task
    (else a step's continuous drain exceeds the energy gate and the
    capacitor goes negative) and below every period (admission is one job
    per task per step)."""
    tasks = as_task_set(tasks)
    frag_t = min(
        float(np.min(np.asarray(t.unit_time)) / t.fragments_per_unit)
        for t in tasks)
    if dt > frag_t * (1 + 1e-9):
        raise ValueError(
            f"dt={dt} exceeds one fragment time ({frag_t}); the energy gate "
            "only covers one fragment of drain per step")
    if dt >= min(t.period for t in tasks):
        raise ValueError("dt must be smaller than every task period")
    return dt


def _default_dt(tasks: TaskSet) -> float:
    """One fragment time of the finest-grained task — the scalar path's
    execution quantum."""
    return min(
        float(np.min(np.asarray(t.unit_time)) / t.fragments_per_unit)
        for t in as_task_set(tasks))


def _pad_trailing(a: np.ndarray, shape: tuple, edge_axes: tuple) -> np.ndarray:
    """Zero/edge-pad ``a`` up to ``shape``; axes in ``edge_axes`` replicate
    the last valid entry (keeps padded unit times nonzero so the drain
    division in the simulator stays finite — the padding is never read by an
    active queue slot)."""
    widths = [(0, s - d) for s, d in zip(shape, a.shape)]
    if not any(w for _, w in widths):
        return a
    if edge_axes:
        a = np.pad(a, [w if i in edge_axes else (0, 0)
                       for i, w in enumerate(widths)], mode="edge")
        widths = [(0, s - d) for s, d in zip(shape, a.shape)]
    return np.pad(a, widths, mode="constant")


def device_config(
    tasks: TaskSet,
    harvester: Harvester,
    eta: float,
    cap: Capacitor,
    *,
    policy: str,
    horizon: float,
    events: np.ndarray,
    e_opt_fraction: float = 0.7,
    e_man: Optional[float] = None,
    start_charged: bool = False,
    clock_drift: float = 0.0,
    exit_thresholds: Optional[np.ndarray] = None,
) -> dict:
    """One device's configuration as a dict of (unbatched) numpy arrays.

    ``tasks`` is the device's task set (one TaskSpec or a sequence); the
    per-task tables land on a leading ``K`` axis.  ``clock_drift`` is the
    fleet CHRT model's linear drift rate (0 = exact RTC).
    ``exit_thresholds`` (shape ``(U,)`` shared by every task, or ``(K, U)``
    per task) switches the utility test from the precomputed ``passes``
    table to a live margin-vs-threshold comparison — the knob
    :mod:`repro.adapt` tunes.
    """
    tasks = as_task_set(tasks)
    if any(t.release_jitter for t in tasks):
        raise ValueError("fleet simulator requires release_jitter == 0")
    if policy == "rr" and len(tasks) > 1 and horizon >= P.RR_TASK_W:
        # the rr task-rotation rank outweighs releases only below this
        # horizon (repro.core.policy.RR_TASK_W); beyond it the rotation
        # would silently lose to release order
        raise ValueError(
            f"rr task rotation requires horizon < {P.RR_TASK_W:g} s "
            f"(got {horizon}); releases must stay below the rotation weight")
    n_units = np.array([len(t.unit_time) for t in tasks], np.int32)
    u_max = int(n_units.max())
    j_max = max(len(t.profiles) for t in tasks)

    unit_time = np.stack([
        _pad_trailing(np.asarray(t.unit_time, _F32), (u_max,), (0,))
        for t in tasks])
    unit_energy = np.stack([
        _pad_trailing(np.asarray(t.unit_energy, _F32), (u_max,), (0,))
        for t in tasks])

    def profile_table(t: TaskSpec, field: str, dtype) -> np.ndarray:
        tab = np.stack([np.asarray(getattr(p, field), dtype)
                        for p in t.profiles])
        return _pad_trailing(tab, (j_max, u_max), (1,))

    margins = np.stack([profile_table(t, "margins", _F32) for t in tasks])
    passes = np.stack([profile_table(t, "passes", bool) for t in tasks])
    correct = np.stack([profile_table(t, "correct", bool) for t in tasks])

    if exit_thresholds is None:
        exit_thr = np.zeros((len(tasks), u_max), _F32)
    else:
        exit_thr = np.asarray(exit_thresholds, _F32)
        if exit_thr.ndim == 1:
            exit_thr = np.broadcast_to(
                _pad_trailing(exit_thr, (u_max,), (0,)),
                (len(tasks), u_max)).copy()
        else:
            exit_thr = _pad_trailing(exit_thr, (len(tasks), u_max), (1,))

    # scalar-path normalisation: alpha from the *longest* relative deadline
    # in the set, the fragment-energy floor from the most expensive fragment
    max_frag_e = max(float(np.max(np.asarray(t.unit_energy)))
                     / t.fragments_per_unit for t in tasks)
    debt = 0.5 * cap.capacitance_f * cap.v_min ** 2
    return dict(
        policy=np.int32(P.POLICY_IDS[policy]),
        imprecise=np.bool_(policy in P.IMPRECISE_POLICIES),
        is_edfm=np.bool_(policy == "edf-m"),
        eta=_F32(eta),
        alpha=_F32(1.0 / max(t.deadline for t in tasks)),
        beta=_F32(1.0),
        persistent=np.bool_(eta >= 1.0 and harvester.p_stay_on >= 1.0),
        capacity=_F32(cap.capacity_j),
        start_energy=_F32(cap.capacity_j if start_charged else -debt),
        e_man=_F32(max_frag_e if e_man is None else e_man),
        e_opt=_F32(e_opt_fraction * cap.capacity_j),
        clock_drift=_F32(clock_drift),
        use_exit_thr=np.bool_(exit_thresholds is not None),
        exit_thr=exit_thr,
        power_on=_F32(harvester.power_on),
        period=np.array([t.period for t in tasks], _F32),
        rel_deadline=np.array([t.deadline for t in tasks], _F32),
        fragments=np.array([t.fragments_per_unit for t in tasks], _F32),
        n_units=n_units,
        n_releases=np.array([_n_releases(t, horizon) for t in tasks],
                            np.int32),
        unit_time=unit_time,
        unit_energy=unit_energy,
        margins=margins,
        passes=passes,
        correct=correct,
        events=np.asarray(events, _F32),
    )


def sample_events(harvester: Harvester, horizon: float, seed: int) -> np.ndarray:
    """Harvester ON/OFF slots exactly as the scalar ``simulate()`` draws them
    (fresh ``default_rng(seed)``, ``init=1``) — seed-matched parity hinges on
    reproducing this stream bit-for-bit."""
    n_slots = int(horizon / harvester.slot_s) + 2
    rng = np.random.default_rng(seed)
    return harvester.sample_events(rng, n_slots, init=1).astype(_F32)


def stack_configs(devices: Sequence[dict]) -> FleetConfig:
    """Stack per-device dicts into a FleetConfig of (D, ...) jnp arrays."""
    fields = FleetConfig._fields
    with span("fleet.build.stack"):
        return FleetConfig(**{
            f: jnp.asarray(np.stack([d[f] for d in devices])) for f in fields
        })


def from_sim_config(
    tasks: TaskSet,
    harvester: Harvester,
    eta: float,
    cap: Optional[Capacitor] = None,
    sim: Optional[SimConfig] = None,
    dt: Optional[float] = None,
) -> tuple[FleetConfig, FleetStatics]:
    """Single-device FleetConfig mirroring ``simulate(tasks, ...)``'s setup —
    the parity-test bridge between the scalar and fleet paths.  ``tasks``
    may be one TaskSpec or a whole task set, exactly like the scalar call."""
    tasks = as_task_set(tasks)
    sim = sim or SimConfig()
    cap = cap or Capacitor()
    clock_drift = 0.0
    if type(sim.clock) is not Clock:
        if hasattr(sim.clock, "equivalent_drift"):
            # fleet CHRT model: the scalar clock's random per-read error maps
            # onto a deterministic per-device drift rate
            clock_drift = sim.clock.equivalent_drift(sim.horizon)
        else:
            raise NotImplementedError(
                f"fleet path has no model for clock {type(sim.clock)}")
    # default dt = one fragment time: the scalar path's execution quantum
    dt = _check_dt(_default_dt(tasks) if dt is None else float(dt), tasks)
    statics = FleetStatics(queue_size=sim.queue_size, dt=dt,
                           horizon=sim.horizon, slot_s=harvester.slot_s)
    dev = device_config(
        tasks, harvester, eta, cap,
        policy=sim.policy, horizon=sim.horizon,
        events=sample_events(harvester, sim.horizon, sim.seed),
        e_opt_fraction=sim.e_opt_fraction, e_man=sim.e_man,
        start_charged=sim.start_charged, clock_drift=clock_drift,
    )
    return stack_configs([dev]), statics


# --------------------------------------------------------------------------- #
# Sweep API.
# --------------------------------------------------------------------------- #


@dataclasses.dataclass(frozen=True)
class SweepGrid:
    """Cartesian benchmark grid: one device per (policy, eta, harvester,
    capacitor, seed) tuple, sharing a single task-set workload (``task``
    accepts one TaskSpec or a sequence — every device then runs the whole
    set)."""

    task: TaskSet
    policies: Sequence[str] = ("zygarde",)
    etas: Sequence[float] = (1.0,)
    harvesters: Sequence[Harvester] = ()
    capacitors: Sequence[Capacitor] = ()
    seeds: Sequence[int] = (0,)
    clock_drifts: Sequence[float] = (0.0,)   # fleet CHRT drift-rate axis
    horizon: float = 600.0
    dt: Optional[float] = None      # default: one fragment time
    queue_size: int = 3
    e_opt_fraction: float = 0.7
    e_man: Optional[float] = None
    start_charged: bool = False

    @property
    def tasks(self) -> tuple[TaskSpec, ...]:
        return as_task_set(self.task)

    def axes(self) -> tuple[tuple, ...]:
        """The grid's axes in device order: policy, eta, harvester,
        capacitor, seed, clock drift (the harvester and capacitor axes
        default to one persistent source and one default capacitor)."""
        return (tuple(self.policies), tuple(self.etas),
                tuple(self.harvesters or (PERSISTENT,)),
                tuple(self.capacitors or (Capacitor(),)),
                tuple(self.seeds), tuple(self.clock_drifts))

    def points(self):
        policies, etas, harvesters, capacitors, seeds, drifts = self.axes()
        for pol in policies:
            for eta in etas:
                for h in harvesters:
                    for cap in capacitors:
                        for seed in seeds:
                            for drift in drifts:
                                yield dict(policy=pol, eta=eta, harvester=h,
                                           capacitor=cap, seed=seed,
                                           clock_drift=drift)


@jax.jit
def _expand(rows: FleetConfig, config_idx, draw_idx) -> FleetConfig:
    """Gather the distinct rows out to the device axis: ``events`` by
    ``draw_idx``, every other field by ``config_idx``."""
    return FleetConfig(**{
        f: getattr(rows, f)[draw_idx if f == "events" else config_idx]
        for f in FleetConfig._fields})


def build(grid: SweepGrid) -> tuple[FleetConfig, FleetStatics, list[dict]]:
    """Materialise the grid as a FleetConfig + per-device metadata rows.

    A device's configuration depends on its seed only through its harvest
    draw, so ``device_config`` runs once per distinct (policy, eta,
    harvester, capacitor, clock drift) point and ``sample_events`` once per
    (harvester, seed); the few rows go to the device and are gathered out
    to the ``D`` devices there, in :meth:`SweepGrid.points` order."""
    with span("fleet.build"):
        axes = grid.axes()
        shape = tuple(len(a) for a in axes)
        n_dev = int(np.prod(shape))
        if not n_dev:
            raise ValueError("empty sweep grid")
        policies, etas, harvesters, capacitors, seeds, drifts = axes
        tasks = grid.tasks
        slot_lens = {h.slot_s for h in harvesters}
        if len(slot_lens) != 1:
            raise ValueError("all harvesters in one sweep must share slot_s")
        dt = _check_dt(
            _default_dt(tasks) if grid.dt is None else grid.dt, tasks)
        statics = FleetStatics(queue_size=grid.queue_size, dt=dt,
                               horizon=grid.horizon, slot_s=slot_lens.pop())

        # the seed axis (4) is the only one a configuration row ignores
        config_shape = shape[:4] + shape[5:]
        with span("fleet.build.configs", devices=n_dev,
                  configs=int(np.prod(config_shape))):
            draws, draw_of = [], {}
            draw_table = np.empty((len(harvesters), len(seeds)), np.int32)
            for hi, h in enumerate(harvesters):
                for si, seed in enumerate(seeds):
                    if (hi, seed) not in draw_of:
                        draw_of[hi, seed] = len(draws)
                        draws.append(sample_events(h, grid.horizon, seed))
                    draw_table[hi, si] = draw_of[hi, seed]
            rows = [device_config(
                tasks, harvesters[hi], etas[ei], capacitors[ci],
                policy=policies[pi], horizon=grid.horizon,
                events=draws[draw_table[hi, 0]],
                e_opt_fraction=grid.e_opt_fraction, e_man=grid.e_man,
                start_charged=grid.start_charged, clock_drift=drifts[ri])
                for pi, ei, hi, ci, ri in np.ndindex(config_shape)]
            # each device's position on every axis, in points() order
            pos = np.indices(shape).reshape(len(shape), -1)
            config_idx = np.ravel_multi_index(
                np.delete(pos, 4, axis=0), config_shape).astype(np.int32)
            draw_idx = draw_table[pos[2], pos[4]]
        with span("fleet.build.stack"):
            stacked = {f: np.stack([d[f] for d in rows])
                       for f in FleetConfig._fields if f != "events"}
            cfg = _expand(FleetConfig(events=np.stack(draws), **stacked),
                          config_idx, draw_idx)

        meta = [dict(
            policy=pt["policy"], eta=pt["eta"],
            harvester=pt["harvester"].name, seed=pt["seed"],
            capacitance_f=pt["capacitor"].capacitance_f,
            clock_drift=pt["clock_drift"],
            n_tasks=len(tasks),
        ) for pt in grid.points()]
        return cfg, statics, meta


def sweep(grid: SweepGrid, use_pallas=None, mesh=None, mode=None):
    """Simulate the whole grid in one jitted call.

    Returns ``(FleetResult, meta)``: stacked (D,) metric arrays (plus the
    ``(D, K)`` per-task breakdowns) and the per-device metadata rows
    identifying each grid point.  ``mesh`` (e.g.
    :func:`repro.launch.mesh.make_fleet_mesh`) partitions the device axis
    across backends — results are bit-identical to the unsharded call.
    """
    from .simulator import simulate_fleet_sharded

    cfg, statics, meta = build(grid)
    res = simulate_fleet_sharded(cfg, statics, mesh=mesh,
                                 use_pallas=use_pallas, mode=mode)
    return res, meta
