"""Fixed-timestep, JAX-native fleet frontend over the unified step core.

Where :func:`repro.core.scheduler.simulate` is a scalar python event loop
(one device / seed / config per call), this simulator steps the *entire*
fleet state — capacitor energies, fixed-size job queues, harvester event
streams — with one ``jax.lax.scan`` over time, ``jax.vmap``-ing the
per-device transition across the device axis.  One jitted call therefore
evaluates a whole policy × eta × harvester × capacitor × seed grid.

The per-device transition itself — release/admit, drop-expired, priority
pick via :mod:`repro.core.policy`, fragment apply, capacitor
charge/discharge, metric accumulation — lives in :mod:`repro.core.step` as
pure ``(StepParams, DeviceCarry, t) -> DeviceCarry`` functions with no
device axis; this module only adds the batching (``vmap``), the time scan,
and the optional Pallas pick (:mod:`repro.kernels.fleet_priority`, whose
in-tile semantics are the same :func:`repro.core.step.select_and_charge`).
Because batching elementwise transitions is exact, the fleet path is
*bit-exact* against the scalar-stepped frontend
:func:`repro.core.scheduler.simulate_stepped` on the shared clock — the
parity harness in ``tests/test_parity.py`` asserts equality, not calibrated
tolerances.

Two execution shapes:

* :func:`simulate_fleet` — one monolithic scan over the whole horizon.
* :func:`run_segments` — the same horizon in ``n_segments`` chunks,
  returning/accepting the full carry pytree (:class:`DeviceState`) between
  chunks and calling a host ``hook`` at each boundary.  The hook may
  rewrite the *tunable* FleetConfig fields (eta, e_opt, exit thresholds)
  mid-trajectory — the substrate of the paper's online adaptation loop
  (:mod:`repro.adapt.online`).  With no hook the chunked scan is
  bit-identical to the monolithic one for any ``n_segments``.

Fidelity notes vs the event-driven scalar simulator: execution is quantized
to ``dt`` (keep ``dt`` at or below one fragment time), fragment energy is
drained continuously rather than per-fragment, and job admission/expiry are
checked every ``dt`` rather than only at unit boundaries — so counts agree
within a small tolerance rather than bit-exactly; the parity tests in
``tests/test_fleet.py`` and the task-set harness in ``tests/test_parity.py``
pin the agreement down.  Limited preemption itself is preserved: a started
unit holds a lock (``lock_slot``/``lock_job``) and runs to its boundary
before the scheduler re-picks, exactly as in paper §4.1.  Round-robin
rotates a per-device task cursor (``rr_cursor``) at unit boundaries, the
array analogue of the scalar simulator's rotation at each pick.
"""
from __future__ import annotations

import functools
import inspect
import warnings
from typing import Callable, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..core import step as S
from ..telemetry import export as T_export
from ..telemetry import state as T
from ..telemetry import trace as T_trace
from ..telemetry.spans import span
from .state import DeviceState, FleetConfig, FleetResult, FleetStatics, \
    init_state

_F32 = jnp.float32

#: the FleetConfig fields adaptation hooks may rewrite mid-trajectory —
#: run_segments diffs them after each hook to stamp knob-update telemetry
TUNABLE_FIELDS = ("eta", "e_opt", "exit_thr", "use_exit_thr", "persistent")

#: execution modes of the time loop:
#: - "vmap": lax.scan over vmap(device_step) — the XLA-fused reference
#: - "pallas": same scan, but the pick stage runs in the fleet_priority
#:   kernel (one pallas_call per *step*; kept as the per-stage kernel demo)
#: - "fused": the whole segment's time loop runs inside ONE pallas_call
#:   (repro.kernels.fleet_step) with the carry tile VMEM-resident
FLEET_MODES = ("vmap", "pallas", "fused")


def _resolve_mode(mode: Optional[str],
                  use_pallas: Optional[bool] = None) -> str:
    """Fold the legacy ``use_pallas`` flag and the ``mode`` kwarg into one
    mode string.  ``use_pallas`` is DEPRECATED: passing it (either value)
    warns; the mode strings (:data:`FLEET_MODES`) are the API.  An explicit
    ``mode`` wins when both are given."""
    if use_pallas is not None:
        warnings.warn(
            "use_pallas= is deprecated; pass mode='pallas' (or 'vmap' / "
            "'fused') instead", DeprecationWarning, stacklevel=3)
        if mode is None:
            return "pallas" if use_pallas else "vmap"
    if mode is None:
        return "vmap"
    if mode not in FLEET_MODES:
        raise ValueError(f"mode must be one of {FLEET_MODES}, got {mode!r}")
    return mode


def _pick_pallas(cfg: FleetConfig, states: DeviceState, t,
                 statics: FleetStatics):
    """Batched pick via the Pallas fleet_priority kernel (whole-fleet call).

    The kernel tile gains the task dimension: the raw per-slot task ids and
    the per-device rr cursors ride into VMEM and the rotation rank is
    computed inside the kernel, next to the priority-argmax."""
    from ..kernels import ops  # local import: kernels pull in pallas

    (laxity, utility, mandatory, gate_e, drain, charge, forced,
     _task_rank) = jax.vmap(
        lambda c, s: S.pick_inputs(c, s, t, statics))(cfg, states)
    return ops.fleet_priority(
        cfg.policy, states.q_active, laxity, states.q_release, utility,
        mandatory, cfg.alpha, cfg.beta, cfg.eta, cfg.persistent,
        states.energy, cfg.e_opt, charge, cfg.capacity, gate_e, drain,
        forced, states.q_task, states.rr_cursor,
        n_tasks=cfg.period.shape[-1])


def _fleet_step(cfg: FleetConfig, states: DeviceState, i,
                statics: FleetStatics, use_pallas: bool) -> DeviceState:
    """One fleet timestep: vmap of the step core's device transition (or
    the split admit/expire/Pallas-pick/apply pipeline when the pick runs in
    the kernel, which needs the whole device batch at once)."""
    t = i.astype(_F32) * statics.dt
    t_end = (i + 1).astype(_F32) * statics.dt
    if not use_pallas:
        return jax.vmap(
            lambda c, s: S.device_step(c, s, t, statics, t_end=t_end)
        )(cfg, states)
    states = jax.vmap(lambda c, s: S.admit(c, s, t, statics))(cfg, states)
    states = jax.vmap(lambda c, s: S.drop_expired(c, s, t))(cfg, states)
    sel, picked, run, e_new = _pick_pallas(cfg, states, t, statics)
    return jax.vmap(
        lambda c, s, a, p, r, e: S.apply_step(c, s, t, a, p, r, e, statics,
                                              t_end=t_end)
    )(cfg, states, sel, picked, run, e_new)


@functools.partial(jax.jit, static_argnames=("statics",))
def init_fleet(cfg: FleetConfig, statics: FleetStatics) -> DeviceState:
    """The t=0 carry pytree for every device in ``cfg`` (the value
    :func:`run_segments` accepts/returns between horizon chunks)."""
    return jax.vmap(lambda c: init_state(c, statics))(cfg)


@functools.partial(jax.jit,
                   static_argnames=("statics", "n_steps", "use_pallas"))
def _scan_steps(cfg: FleetConfig, states: DeviceState, i0,
                statics: FleetStatics, n_steps: int,
                use_pallas: bool) -> DeviceState:
    """Scan ``n_steps`` timesteps starting at step index ``i0`` (traced, so
    all equal-length segments share one compilation)."""
    def step(states, i):
        return _fleet_step(cfg, states, i, statics, use_pallas), None

    states, _ = lax.scan(step, states, i0 + jnp.arange(n_steps))
    return states


def _scan_steps_fused(cfg: FleetConfig, states: DeviceState, i0,
                      statics: FleetStatics, n_steps: int) -> DeviceState:
    """Fused twin of :func:`_scan_steps`: the entire ``n_steps`` time loop
    runs inside ONE ``pallas_call`` (:mod:`repro.kernels.fleet_step`) with a
    ``block_d``-row carry tile VMEM-resident — no per-step dispatch, no HBM
    carry round-trips inside the segment.  Bit-exact against the scan (the
    kernel body is the same :func:`repro.core.step.device_step`)."""
    from ..kernels import ops  # local import: kernels pull in pallas

    return ops.fleet_fused_steps(cfg, states, i0, statics=statics,
                                 n_steps=n_steps)


def _fleet_step_trace(cfg: FleetConfig, states: DeviceState, i,
                      statics: FleetStatics, use_pallas: bool):
    """Descriptor-emitting twin of :func:`_fleet_step`: the same stages in
    the same order, additionally returning the step's packed
    :class:`repro.core.step.StepTrace` event words (a few bytes/device)."""
    t = i.astype(_F32) * statics.dt
    t_end = (i + 1).astype(_F32) * statics.dt
    if not use_pallas:
        return jax.vmap(
            lambda c, s: S.device_step(c, s, t, statics, trace=True,
                                       t_end=t_end)
        )(cfg, states)
    act0 = states.q_active
    states, (tr_adm, tr_ev, tr_ev_dl) = jax.vmap(
        lambda c, s: S.admit(c, s, t, statics, trace=True))(cfg, states)
    states, (tr_exp, tr_exp_dl) = jax.vmap(
        lambda c, s, a0: S.drop_expired(c, s, t, trace=True,
                                        q_active_pre=a0)
    )(cfg, states, act0)
    sel, picked, run, e_new = _pick_pallas(cfg, states, t, statics)
    states, (tr_comp, tr_comp_dl) = jax.vmap(
        lambda c, s, a, p, r, e, a0: S.apply_step(
            c, s, t, a, p, r, e, statics, trace=True, q_active_pre=a0,
            t_end=t_end)
    )(cfg, states, sel, picked, run, e_new, act0)
    return states, S.StepTrace(adm=tr_adm, evict=tr_ev, evict_dl=tr_ev_dl,
                               expire=tr_exp, expire_dl=tr_exp_dl,
                               complete=tr_comp, complete_dl=tr_comp_dl)


def _pack_spec(cfg: FleetConfig, statics: FleetStatics,
               tel: T.Telemetry) -> T_trace.PackSpec:
    return T_trace.make_pack_spec(int(cfg.period.shape[1]),
                                  statics.queue_size,
                                  int(tel.exit_hist.shape[1]))


@functools.partial(
    jax.jit, static_argnames=("statics", "n_steps", "use_pallas", "level"))
def _scan_steps_trace(cfg: FleetConfig, states: DeviceState,
                      tel: T.Telemetry, i0, statics: FleetStatics,
                      n_steps: int, use_pallas: bool, level: str):
    """Like :func:`_scan_steps`, but emitting the telemetry columns of the
    requested collection tier and reducing them into ``tel`` once per
    segment, after the scan but inside the same jit.

    ``"counters"`` reuses the plain step body and emits three registers it
    already computed; ``"full"`` runs the descriptor-emitting step twin and
    emits the bit-packed event columns (:class:`repro.telemetry.trace
    .PackSpec`), which are also returned for the sparse host-side
    ring/histogram fold (``None`` at the counters tier)."""
    st0 = states
    if level == "counters":
        def step(states, i):
            new = _fleet_step(cfg, states, i, statics, use_pallas)
            return new, T_trace.emit_counters(new)

        states, ys = lax.scan(step, states, i0 + jnp.arange(n_steps))
        return states, T_trace.reduce_counters(tel, st0, states, ys,
                                               n_steps), None

    spec = _pack_spec(cfg, statics, tel)

    def step(states, i):
        new, tr = _fleet_step_trace(cfg, states, i, statics, use_pallas)
        return new, T_trace.emit_full(spec, tr, states, new)

    states, ys = lax.scan(step, states, i0 + jnp.arange(n_steps))
    tel, ring = T_trace.reduce_full(spec, tel, st0, states, ys, i0,
                                    n_steps, statics.dt)
    return states, tel, ring


def _scan_steps_tel(cfg: FleetConfig, states: DeviceState, tel: T.Telemetry,
                    i0, statics: FleetStatics, n_steps: int,
                    use_pallas: bool,
                    tcfg: T.TelemetryConfig):
    """Telemetry-carrying twin of :func:`_scan_steps` (host wrapper).

    The jitted scan emits the tier's telemetry columns and reduces the
    dense statistics per segment; at the ``"full"`` tier the rare
    ring/histogram events are then folded host-side from the packed
    columns (:func:`repro.telemetry.trace.fold_events_host`, O(events)).
    The simulation carry is asserted bit-exact against the uninstrumented
    scan in ``tests/test_telemetry.py``, and the default-tier overhead is
    gated < 5% in ``benchmarks/check_regression.py``."""
    states, tel, ring = _scan_steps_trace(cfg, states, tel, i0, statics,
                                          n_steps, use_pallas, tcfg.level)
    if ring is not None:
        tel = T_trace.fold_events_host(
            _pack_spec(cfg, statics, tel), tel,
            tuple(np.asarray(col) for col in ring), int(i0), statics.dt)
    return states, tel


@functools.partial(
    jax.jit,
    static_argnames=("statics", "n_steps", "use_pallas", "tcfg"))
def _scan_steps_tel_reference(cfg: FleetConfig, states: DeviceState,
                              tel: T.Telemetry, i0, statics: FleetStatics,
                              n_steps: int, use_pallas: bool,
                              tcfg: T.TelemetryConfig):
    """The slow reference: fold :func:`repro.telemetry.state.record_step`
    from the before/after carry pair at every step, inside the scan.  Kept
    as the semantic spec the trace pipeline is tested against (and as the
    simplest possible implementation to read)."""
    def step(carry, i):
        states, tel = carry
        t = i.astype(_F32) * statics.dt
        new = _fleet_step(cfg, states, i, statics, use_pallas)
        ev = jax.vmap(
            lambda s0, s1: S.step_events(s0, s1, t, statics))(states, new)
        tel = jax.vmap(lambda tl, e: T.record_step(tl, e, t))(tel, ev)
        return (new, tel), None

    (states, tel), _ = lax.scan(step, (states, tel),
                                i0 + jnp.arange(n_steps))
    return states, tel


@functools.partial(jax.jit, static_argnames=("statics", "live"))
def finalize_fleet(cfg: FleetConfig, states: DeviceState,
                   statics: FleetStatics, live: bool = False) -> FleetResult:
    """Flush the carry into a :class:`FleetResult` (vmap of the step core's
    finalize).  ``live`` counts correctness from the live registers
    (:mod:`repro.serve.fleet_engine`) instead of the replay tables."""
    return jax.vmap(lambda c, s: S.finalize(c, s, statics, live))(cfg, states)


@functools.partial(jax.jit, static_argnames=("statics", "use_pallas"))
def _simulate_fleet_plain(cfg: FleetConfig, statics: FleetStatics,
                          use_pallas: bool = False) -> FleetResult:
    states0 = jax.vmap(lambda c: init_state(c, statics))(cfg)

    def step(states, i):
        return _fleet_step(cfg, states, i, statics, use_pallas), None

    states, _ = lax.scan(step, states0, jnp.arange(statics.n_steps))
    return jax.vmap(lambda c, s: S.finalize(c, s, statics))(cfg, states)


def _simulate_fleet_fused(cfg: FleetConfig,
                          statics: FleetStatics) -> FleetResult:
    """Monolithic fused run: init, ONE whole-horizon ``pallas_call``, and
    finalize — the fused analogue of :func:`_simulate_fleet_plain`."""
    states = _scan_steps_fused(cfg, init_fleet(cfg, statics), jnp.int32(0),
                               statics, statics.n_steps)
    return finalize_fleet(cfg, states, statics)


def simulate_fleet(cfg: FleetConfig, statics: FleetStatics,
                   use_pallas: Optional[bool] = None,
                   telemetry: Optional[T.TelemetryConfig] = None,
                   mode: Optional[str] = None):
    """Simulate every device in ``cfg`` in one jitted scan.

    Returns a :class:`FleetResult` of ``(D,)`` metric arrays — plus
    ``(D, K)`` per-task breakdowns — aligned with the device axis of ``cfg``
    (see :func:`repro.fleet.grid.sweep` for the grid bookkeeping).

    ``mode`` selects the time-loop execution shape (:data:`FLEET_MODES`):
    ``"vmap"`` (default), ``"pallas"`` (per-step pick kernel; the legacy
    ``use_pallas=True``), or ``"fused"`` — the whole horizon in ONE
    ``pallas_call`` with the carry VMEM-resident
    (:mod:`repro.kernels.fleet_step`).  All three are bit-exact against
    each other.  ``mode="fused"`` does not support ``telemetry`` (the
    per-step trace columns would defeat the in-kernel loop; use the vmap
    path to instrument).

    ``telemetry`` (a :class:`repro.telemetry.TelemetryConfig`)
    additionally instruments the scan and returns
    ``(FleetResult, Telemetry)``: the scan emits a few telemetry columns
    per step and the statistics reduce once per segment
    (:mod:`repro.telemetry.trace`) — at the default ``"counters"`` tier
    that is near-free; the ``"full"`` tier adds per-step event
    descriptors, with the rare ring/histogram events folded host-side.
    With the default ``None`` the instrumentation is compiled out
    entirely — the emitted program is the pre-telemetry one, and the
    FleetResult is bit-exact either way.
    """
    mode = _resolve_mode(mode, use_pallas)
    if mode == "fused" and telemetry is not None:
        raise ValueError(
            "mode='fused' does not support telemetry; use mode='vmap'")
    with span("fleet.simulate"):
        if mode == "fused":
            return _simulate_fleet_fused(cfg, statics)
        use_pallas = mode == "pallas"
        if telemetry is None:
            return _simulate_fleet_plain(cfg, statics, use_pallas)
        res, tel, ring = _simulate_fleet_tel(cfg, statics, use_pallas,
                                             telemetry)
        if ring is not None:
            tel = T_trace.fold_events_host(
                _pack_spec(cfg, statics, tel), tel,
                tuple(np.asarray(col) for col in ring), 0, statics.dt)
        return res, tel


@functools.partial(jax.jit,
                   static_argnames=("statics", "use_pallas", "telemetry"))
def _simulate_fleet_tel(cfg: FleetConfig, statics: FleetStatics,
                        use_pallas: bool, telemetry: T.TelemetryConfig):
    """One fused program for the instrumented monolithic run — init, scan,
    telemetry reduction, and finalize dispatch together, exactly like
    :func:`_simulate_fleet_plain` (four separate dispatches would charge
    the telemetry path for unfused init/finalize kernels the plain path
    fuses away, polluting the measured overhead)."""
    states0 = jax.vmap(lambda c: init_state(c, statics))(cfg)
    tel0 = T.init_fleet_telemetry(telemetry, cfg)
    states, tel, ring = _scan_steps_trace(
        cfg, states0, tel0, jnp.int32(0), statics, statics.n_steps,
        use_pallas, telemetry.level)
    res = jax.vmap(lambda c, s: S.finalize(c, s, statics))(cfg, states)
    return res, tel, ring


# hook signature: (segment_index, t_end, cfg, carry) -> new cfg or None
# (hooks that also declare a ``telemetry`` keyword additionally receive the
# cumulative TelemetrySummary when telemetry is enabled)
SegmentHook = Callable[[int, float, FleetConfig, DeviceState],
                       Optional[FleetConfig]]


def _hook_takes_telemetry(hook) -> bool:
    """Does ``hook`` accept a ``telemetry=`` keyword?  Bare 4-arg hooks stay
    supported unchanged; hooks opt into summaries by naming the kwarg (or
    taking **kwargs)."""
    try:
        sig = inspect.signature(hook)
    except (TypeError, ValueError):
        return False
    params = sig.parameters.values()
    if any(p.kind is inspect.Parameter.VAR_KEYWORD for p in params):
        return True
    return "telemetry" in sig.parameters


def _knob_change_mask(old_cfg: FleetConfig, new_cfg: FleetConfig):
    """(D,) bool: which devices had any TUNABLE_FIELDS leaf rewritten by a
    hook (host-side numpy compare; runs once per segment boundary)."""
    changed = None
    for f in TUNABLE_FIELDS:
        a = np.asarray(getattr(old_cfg, f))
        b = np.asarray(getattr(new_cfg, f))
        diff = a != b
        while diff.ndim > 1:          # per-task knobs: any task changed
            diff = diff.any(axis=-1)
        changed = diff if changed is None else (changed | diff)
    return changed


def run_segments(cfg: FleetConfig, statics: FleetStatics,
                 n_segments: int = 1, *,
                 hook: Optional[SegmentHook] = None,
                 carry: Optional[DeviceState] = None,
                 start_step: int = 0,
                 use_pallas: Optional[bool] = None,
                 mode: Optional[str] = None,
                 mesh=None,
                 telemetry: Optional[T.TelemetryConfig] = None,
                 telemetry_carry: Optional[T.Telemetry] = None):
    """Segment-at-a-time fleet simulation over the checkpointable carry.

    Splits the scan over steps ``[start_step, statics.n_steps)`` into
    ``n_segments`` contiguous chunks (lengths differ by at most one step,
    so at most two distinct compilations) and materialises the full carry
    pytree (:class:`DeviceState`) at every boundary.  After each segment
    the host ``hook(seg, t_end, cfg, carry)`` runs and may return a
    modified FleetConfig — rewriting *tunable* fields (``eta``, ``e_opt``,
    ``exit_thr``/``use_exit_thr``, ``persistent``) mid-trajectory is how
    :mod:`repro.adapt.online` implements the paper's runtime eta
    re-estimation loop.  Returning ``None`` keeps the current config.

    ``carry`` + ``start_step`` resume a previous run: pass the returned
    carry together with the number of steps it has already lived through
    (the simulation clock is ``t = step * dt``, and the carry holds
    absolute release/deadline times, so resuming must NOT restart the
    clock at zero).  ``carry=None`` starts from :func:`init_fleet` at step
    ``start_step`` (normally 0).  ``mesh`` partitions the device axis
    exactly like :func:`simulate_fleet_sharded` — the carry shards
    alongside the config (:func:`repro.launch.sharding.shard_fleet_carry`),
    the hook then observes the padded device axis (hook-returned configs
    are re-placed on the mesh so config and carry stay aligned
    shard-for-shard), and the returned result/carry are sliced back to the
    real devices.

    With ``hook=None`` the chunked scan is bit-identical to
    :func:`simulate_fleet` for any ``n_segments``: the same step indices
    run through the same jitted step body, only the carry round-trips
    through host memory between chunks.

    ``telemetry`` (a static :class:`repro.telemetry.TelemetryConfig`)
    threads a ``(D, ...)`` :class:`repro.telemetry.Telemetry` pytree
    alongside the carry and changes the return to
    ``(FleetResult, DeviceState, Telemetry)``.  Hooks that declare a
    ``telemetry`` keyword then receive the cumulative
    :class:`repro.telemetry.TelemetrySummary` at each boundary, and config
    rewrites by hooks are stamped into the telemetry as ``knob_update``
    events.  ``telemetry_carry`` resumes a prior telemetry pytree the same
    way ``carry`` resumes the simulation.  The simulation numerics are
    identical either way — only the return arity changes.

    ``mode`` selects the time-loop execution shape exactly as in
    :func:`simulate_fleet`; ``mode="fused"`` runs each segment as ONE
    ``pallas_call`` (the carry still round-trips at every boundary, so
    hooks and checkpoint resume work unchanged and stay bit-exact against
    the vmap path).  Fused excludes ``telemetry`` and ``mesh``.

    Returns ``(FleetResult, DeviceState)`` — the finalized metrics and the
    end-of-horizon carry — plus the ``Telemetry`` when enabled.
    """
    mode = _resolve_mode(mode, use_pallas)
    use_pallas = mode == "pallas"
    if mode == "fused":
        if telemetry is not None:
            raise ValueError(
                "mode='fused' does not support telemetry; use mode='vmap'")
        if mesh is not None:
            raise ValueError(
                "mode='fused' does not support mesh sharding yet")
    remaining = statics.n_steps - int(start_step)
    if not 0 <= int(start_step) <= statics.n_steps:
        raise ValueError(
            f"start_step must be in [0, {statics.n_steps}], got {start_step}")
    if not 1 <= n_segments <= max(remaining, 1):
        raise ValueError(
            f"n_segments must be in [1, {max(remaining, 1)}], "
            f"got {n_segments}")
    if telemetry is None and telemetry_carry is not None:
        raise ValueError("telemetry_carry requires telemetry=TelemetryConfig")
    n_real = cfg.n_devices
    if mesh is not None:
        from ..launch.sharding import shard_fleet_carry, shard_fleet_config

        cfg = shard_fleet_config(mesh, cfg)
        if carry is not None:
            carry = shard_fleet_carry(mesh, carry)
        if telemetry_carry is not None:
            telemetry_carry = shard_fleet_carry(mesh, telemetry_carry)
    if carry is None:
        carry = init_fleet(cfg, statics)
    tel = None
    if telemetry is not None:
        tel = telemetry_carry
        if tel is None:
            tel = T.init_fleet_telemetry(telemetry, cfg)
            if mesh is not None:
                from ..launch.sharding import shard_fleet_carry

                tel = shard_fleet_carry(mesh, tel)
    hook_wants_tel = hook is not None and telemetry is not None \
        and _hook_takes_telemetry(hook)

    sizes = [len(c) for c in np.array_split(np.arange(remaining),
                                            n_segments)]
    i0 = int(start_step)
    for seg, n in enumerate(sizes):
        if n:
            if mode == "fused":
                carry = _scan_steps_fused(cfg, carry, jnp.int32(i0),
                                          statics, n)
            elif telemetry is None:
                carry = _scan_steps(cfg, carry, jnp.int32(i0), statics, n,
                                    use_pallas)
            else:
                carry, tel = _scan_steps_tel(
                    cfg, carry, tel, jnp.int32(i0), statics, n, use_pallas,
                    telemetry)
            i0 += n
        if hook is not None:
            t_end = i0 * statics.dt
            if hook_wants_tel:
                new_cfg = hook(seg, t_end, cfg, carry,
                               telemetry=T_export.summarize(tel, t_end))
            else:
                new_cfg = hook(seg, t_end, cfg, carry)
            if new_cfg is not None:
                if telemetry is not None:
                    changed = _knob_change_mask(cfg, new_cfg)
                    if changed is not None and changed.any():
                        tel = T.record_knob_updates(tel, changed, t_end)
                cfg = new_cfg
                if mesh is not None:
                    # keep hook-returned leaves placed like the carry (the
                    # hook typically swaps in fresh host arrays)
                    cfg = shard_fleet_config(mesh, cfg)
    res = finalize_fleet(cfg, carry, statics)
    if mesh is not None and jax.tree.leaves(res)[0].shape[0] != n_real:
        res = jax.tree.map(lambda x: x[:n_real], res)
        carry = jax.tree.map(lambda x: x[:n_real], carry)
        if tel is not None:
            tel = jax.tree.map(lambda x: x[:n_real], tel)
    if telemetry is None:
        return res, carry
    return res, carry, tel


def simulate_fleet_sharded(cfg: FleetConfig, statics: FleetStatics,
                           mesh=None, use_pallas: Optional[bool] = None,
                           mode: Optional[str] = None) -> FleetResult:
    """:func:`simulate_fleet` with the device axis partitioned over ``mesh``.

    The fleet axis is embarrassingly parallel (no cross-device collectives in
    the scan body), so placing each ``FleetConfig`` leaf with a
    ``NamedSharding`` over its leading axis lets GSPMD split the whole
    simulation across the mesh devices with zero communication.  The device
    count is padded up to a mesh-size multiple (wrapping around the existing
    configs) and the padding is stripped from the result, so the output is
    bit-identical to the unsharded call for every real device.

    ``mesh=None`` falls back to the plain single-backend path.
    """
    mode = _resolve_mode(mode, use_pallas)
    if mesh is None:
        return simulate_fleet(cfg, statics, mode=mode)
    # local import: repro.launch is a heavier dependency tree than the fleet
    from ..launch.sharding import shard_fleet_config

    n_real = cfg.n_devices
    cfg = shard_fleet_config(mesh, cfg)
    res = simulate_fleet(cfg, statics, mode=mode)
    return jax.tree.map(lambda x: x[:n_real], res)
