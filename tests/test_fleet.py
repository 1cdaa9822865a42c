"""Vectorized fleet simulator: parity with the scalar event-driven
``simulate()``, sweep semantics, and the Pallas fleet_priority kernel.

Parity notes: the fleet path is fixed-timestep (dt = one fragment time by
default) while the scalar path is event-driven, so counts on energy-starved
boundary cases may differ by a few jobs; on deterministic persistent-power
workloads and on matched harvester event streams the counts agree exactly
or within the small tolerances asserted here.
"""
import dataclasses
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from _subproc import sub_env
from _workloads import MODES, make_task, profile
from repro import fleet
from repro.core import energy, policy
from repro.core.scheduler import (
    CHRTClock,
    Job,
    SimConfig,
    simulate,
    zeta,
    zeta_intermittent,
)

# workload builders (profile/make_task) and the calibrated parity bounds are
# shared with tests/test_parity.py via tests/_workloads.py
PERSISTENT = MODES["persistent"][0]


def fleet_device(task, harvester, eta, sim, **kw):
    cfg, statics = fleet.from_sim_config(task, harvester, eta, sim=sim, **kw)
    return fleet.simulate_fleet(cfg, statics).device(0)


# --------------------------------------------------------------------------- #
# Shared policy functions: the scalar priority API is a view over
# repro.core.policy (one source of truth for scalar + fleet + kernel).
# --------------------------------------------------------------------------- #


def test_scalar_priorities_delegate_to_policy_module():
    j = Job(make_task(), 0, 0.0, 2.0, profile(4))
    got = zeta(j, t_now=1.0, alpha=0.5, beta=1.0)
    want = policy.zeta_priority(2.0 - 1.0, j.utility, True, 0.5, 1.0)
    assert got == pytest.approx(float(want))
    got_i = zeta_intermittent(j, 1.0, 0.5, 1.0, eta=0.6, e_curr=0.2, e_opt=0.5)
    want_i = policy.zeta_intermittent_priority(
        1.0, j.utility, True, 0.5, 1.0, 0.6, 0.2, 0.5)
    assert got_i == pytest.approx(float(want_i))


# --------------------------------------------------------------------------- #
# Fleet vs scalar parity on matched single-device configs.
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("pol", ["edf", "edf-m", "rr", "zygarde"])
def test_parity_persistent_underload_exact(pol):
    task = make_task(n_jobs=20, period=1.0, deadline=2.0, unit_t=0.05)
    sim = SimConfig(policy=pol, horizon=40.0)
    scalar = simulate([task], PERSISTENT, eta=1.0, sim=sim)
    d = fleet_device(task, PERSISTENT, 1.0, sim)
    assert d["released"] == scalar.released == 20
    assert d["scheduled"] == scalar.scheduled
    assert d["deadline_misses"] == scalar.deadline_misses == 0
    assert d["units_executed"] == scalar.units_executed
    assert d["reboots"] == scalar.reboots == 0


@pytest.mark.parametrize("pol", ["edf", "edf-m", "zygarde"])
def test_parity_persistent_overload(pol):
    """Overload (U > 1): imprecise-vs-full behaviour must carry over."""
    task = make_task(n_jobs=30, period=0.5, deadline=1.0, unit_t=0.2,
                     exit_at=0)
    sim = SimConfig(policy=pol, horizon=30.0)
    scalar = simulate([task], PERSISTENT, 1.0, sim=sim)
    d = fleet_device(task, PERSISTENT, 1.0, sim)
    assert d["released"] == scalar.released
    assert abs(d["scheduled"] - scalar.scheduled) <= 1
    assert abs(d["deadline_misses"] - scalar.deadline_misses) <= 1


@pytest.mark.parametrize("seed", [0, 3, 7])
def test_parity_intermittent_matched_events(seed):
    """With the harvester event stream matched bit-for-bit (same rng draw
    as the scalar path), intermittent counts line up too."""
    task = make_task(n_jobs=20, period=1.0, deadline=2.0, unit_t=0.1,
                     unit_e=5e-2)
    weak = energy.Harvester("weak", 0.8, 0.8, 0.02)
    sim = SimConfig(policy="zygarde", horizon=40.0, seed=seed)
    scalar = simulate([task], weak, 0.5, sim=sim)
    d = fleet_device(task, weak, 0.5, sim)
    assert d["scheduled"] == scalar.scheduled
    assert d["deadline_misses"] == scalar.deadline_misses
    assert abs(d["reboots"] - scalar.reboots) <= 1
    assert d["idle_no_energy"] > 0


@pytest.mark.parametrize("pol", ["zygarde", "edf-m", "edf"])
def test_parity_intermittent_mid_power(pol):
    """Energy-starved boundary regime: discretization may move a couple of
    jobs across the deadline, no more."""
    task = make_task(n_jobs=25, period=1.0, deadline=2.0, unit_t=0.1,
                     unit_e=8e-3)
    harv = energy.Harvester("h", 0.95, 0.95, 0.08)
    for seed in (1, 5):
        sim = SimConfig(policy=pol, horizon=40.0, seed=seed)
        scalar = simulate([task], harv, 0.7, sim=sim)
        d = fleet_device(task, harv, 0.7, sim)
        assert d["released"] == scalar.released
        assert abs(d["scheduled"] - scalar.scheduled) <= 3
        assert abs(d["deadline_misses"] - scalar.deadline_misses) <= 3


def test_fleet_accounting_invariant():
    """released == scheduled + missed for every device of a mixed sweep."""
    harv = energy.Harvester("h", 0.9, 0.9, 0.05)
    res, meta = fleet.sweep(fleet.SweepGrid(
        task=make_task(n_jobs=25),
        policies=("zygarde", "edf", "edf-m", "rr"),
        etas=(0.3, 0.9),
        harvesters=(harv,),
        seeds=(0, 1),
        horizon=20.0,
    ))
    rel = np.asarray(res.released)
    assert (np.asarray(res.scheduled) + np.asarray(res.deadline_misses)
            == rel).all()
    assert (np.asarray(res.correct) <= np.asarray(res.scheduled)).all()
    assert (np.asarray(res.busy_time) <= np.asarray(res.sim_time) + 1e-5).all()
    assert len(meta) == rel.shape[0] == 16


def test_fleet_zygarde_beats_edf_under_overload():
    """Paper Figs. 17-20 carry over to the fleet path."""
    task = make_task(n_jobs=30, period=0.5, deadline=1.0, unit_t=0.2,
                     exit_at=0)
    res, meta = fleet.sweep(fleet.SweepGrid(
        task=task, policies=("edf", "edf-m", "zygarde"),
        harvesters=(PERSISTENT,), horizon=30.0,
    ))
    by_pol = {m["policy"]: int(res.scheduled[i]) for i, m in enumerate(meta)}
    assert by_pol["edf-m"] > by_pol["edf"]
    assert by_pol["zygarde"] > by_pol["edf"]


# --------------------------------------------------------------------------- #
# Sweep scale: >= 1000 device-configs in one jitted vmap call.
# --------------------------------------------------------------------------- #


def test_sweep_1000_devices_single_call():
    harv = energy.Harvester("h", 0.95, 0.95, 0.08)
    sun = energy.Harvester("sun", 0.9, 0.9, 0.05)
    grid = fleet.SweepGrid(
        task=make_task(n_jobs=15),
        policies=("zygarde", "edf", "edf-m", "rr"),
        etas=(0.2, 0.5, 0.8, 0.9, 1.0),
        harvesters=(harv, sun),
        capacitors=tuple(energy.Capacitor(capacitance_f=c)
                         for c in (0.01, 0.025, 0.05, 0.1, 0.2)),
        seeds=(0, 1, 2, 3, 4),
        horizon=10.0,
    )
    cfg, statics, meta = fleet.build(grid)
    assert cfg.n_devices == 4 * 5 * 2 * 5 * 5 == 1000
    res = fleet.simulate_fleet(cfg, statics)   # ONE jitted scan+vmap call
    assert res.released.shape == (1000,)
    assert len(meta) == 1000
    assert int(np.asarray(res.released).min()) == 10
    # eta/capacitor/policy variation actually changes outcomes
    assert len(np.unique(np.asarray(res.scheduled))) > 3


# --------------------------------------------------------------------------- #
# Fleet-path CHRT clock model: per-device drift rates.
# --------------------------------------------------------------------------- #


def test_zero_drift_is_exact_rtc():
    """clock_drift = 0 must leave the simulation bit-identical."""
    harv = energy.Harvester("h", 0.9, 0.9, 0.05)
    grid = fleet.SweepGrid(task=make_task(n_jobs=20), etas=(0.5, 0.9),
                           harvesters=(harv,), seeds=(0, 1), horizon=20.0)
    base, _ = fleet.sweep(grid)
    drifted, meta = fleet.sweep(
        dataclasses.replace(grid, clock_drifts=(0.0,)))
    assert all(m["clock_drift"] == 0.0 for m in meta)
    for name in base._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(base, name)),
            np.asarray(getattr(drifted, name)), err_msg=name)


def test_fast_clock_drops_jobs_earlier():
    """A fast clock (positive drift) expires jobs before their true
    deadline: misses grow monotonically along the drift axis."""
    harv = energy.Harvester("h", 0.9, 0.9, 0.05)
    drifts = (0.0, 0.05, 0.2)
    res, meta = fleet.sweep(fleet.SweepGrid(
        task=make_task(n_jobs=25, unit_e=8e-3),
        harvesters=(harv,), seeds=(0, 1, 2), clock_drifts=drifts,
        horizon=25.0,
    ))
    misses = np.asarray(res.deadline_misses, np.int64)
    by_drift = {d: int(misses[[i for i, m in enumerate(meta)
                               if m["clock_drift"] == d]].sum())
                for d in drifts}
    assert by_drift[0.0] <= by_drift[0.05] <= by_drift[0.2]
    assert by_drift[0.2] > by_drift[0.0]
    # accounting invariant survives drift
    assert (np.asarray(res.scheduled) + misses
            == np.asarray(res.released)).all()


def test_chrt_clock_maps_to_fleet_drift():
    """from_sim_config accepts a CHRTClock by converting it to the
    equivalent drift rate (instead of the old NotImplementedError)."""
    task = make_task(n_jobs=20)
    sim = SimConfig(policy="zygarde", horizon=40.0, clock=CHRTClock())
    cfg, _ = fleet.from_sim_config(task, PERSISTENT, 1.0, sim=sim)
    drift = float(np.asarray(cfg.clock_drift)[0])
    assert drift == pytest.approx(CHRTClock().equivalent_drift(40.0))
    assert drift > 0  # the CHRT reads fast on average (Table 5)


# --------------------------------------------------------------------------- #
# Sharded sweeps: device-axis partitioning must not change results.
# --------------------------------------------------------------------------- #

_SHARD_SUB = """
import numpy as np
from repro import fleet
from repro.core import energy
from repro.core.scheduler import JobProfile, TaskSpec
from repro.launch.mesh import make_fleet_mesh

n_units = 4
margins = np.linspace(0.05, 0.5, n_units)
passes = np.zeros(n_units, bool); passes[1:] = True
prof = JobProfile(margins, passes, np.ones(n_units, bool))
task = TaskSpec(task_id=0, period=1.0, deadline=2.0,
                unit_time=np.full(n_units, 0.1),
                unit_energy=np.full(n_units, 8e-3),
                profiles=[prof] * 15)
# 6 devices over a 4-way mesh: exercises the wrap-around padding too
grid = fleet.SweepGrid(task=task, policies=("zygarde", "edf"),
                       etas=(0.4, 0.9, 1.0),
                       harvesters=(energy.Harvester("h", 0.9, 0.9, 0.06),),
                       horizon=15.0)
res_u, meta = fleet.sweep(grid)
res_s, _ = fleet.sweep(grid, mesh=make_fleet_mesh())
for name in res_u._fields:
    np.testing.assert_array_equal(np.asarray(getattr(res_u, name)),
                                  np.asarray(getattr(res_s, name)),
                                  err_msg=name)

# segmented execution shards the carry pytree alongside the config
# (launch.sharding.shard_fleet_carry): still bit-identical, and the
# returned result/carry are sliced back to the 6 real devices
cfg_b, statics_b, _ = fleet.build(grid)
res_g, carry_g = fleet.run_segments(cfg_b, statics_b, 5,
                                    mesh=make_fleet_mesh())
for name in res_u._fields:
    np.testing.assert_array_equal(np.asarray(getattr(res_u, name)),
                                  np.asarray(getattr(res_g, name)),
                                  err_msg="segmented " + name)
import jax
assert all(leaf.shape[0] == 6 for leaf in jax.tree.leaves(carry_g))

# the adapt objective shards its candidate population the same way
import dataclasses
from repro import adapt
prob = adapt.TuneProblem(task=task, harvesters=grid.harvesters,
                         seeds=(0, 1), horizon=15.0)
x = {"eta": np.linspace(0.1, 1.0, 5, dtype=np.float32),
     "e_opt_fraction": np.linspace(0.1, 0.9, 5, dtype=np.float32)}
plain = prob.objective()(x)
sharded = dataclasses.replace(prob, mesh=make_fleet_mesh()).objective()(x)
# per-device counts are bit-identical (asserted above); the per-candidate
# score reduction crosses shards, so its summation order may differ by ulps
np.testing.assert_allclose(np.asarray(plain), np.asarray(sharded),
                           rtol=1e-6, atol=0)
print("SHARD_OK", len(meta))
"""


def test_sharded_sweep_matches_unsharded_4dev():
    """fleet.sweep over a real 4-device mesh (forced host devices, so a
    subprocess) is bit-identical to the single-device call."""
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(_SHARD_SUB)],
        capture_output=True, text=True, timeout=600,
        env=sub_env(host_devices=4),
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SHARD_OK 6" in out.stdout


def test_sharded_sweep_trivial_mesh_inprocess():
    """mesh over the in-process device count (1 on CPU) is also exact."""
    from repro.launch.mesh import make_fleet_mesh

    harv = energy.Harvester("h", 0.9, 0.9, 0.06)
    grid = fleet.SweepGrid(task=make_task(n_jobs=15), etas=(0.4, 1.0),
                           harvesters=(harv,), horizon=15.0)
    res_u, _ = fleet.sweep(grid)
    res_s, _ = fleet.sweep(grid, mesh=make_fleet_mesh())
    for name in res_u._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(res_u, name)),
            np.asarray(getattr(res_s, name)), err_msg=name)
    # run_segments on the same mesh shards the carry like the config
    cfg, statics, _ = fleet.build(grid)
    res_g, carry = fleet.run_segments(cfg, statics, 3, mesh=make_fleet_mesh())
    for name in res_u._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(res_u, name)),
            np.asarray(getattr(res_g, name)), err_msg="segmented " + name)
    import jax
    assert all(leaf.shape[0] == cfg.n_devices
               for leaf in jax.tree.leaves(carry))


# --------------------------------------------------------------------------- #
# Pallas fleet_priority kernel: bit-identical to the pure-jnp pick.
# --------------------------------------------------------------------------- #


def test_pallas_priority_kernel_matches_jnp_path():
    harv = energy.Harvester("h", 0.9, 0.9, 0.06)
    grid = fleet.SweepGrid(
        task=make_task(n_jobs=15, unit_e=8e-3),
        policies=("zygarde", "edf", "edf-m", "rr"),
        etas=(0.4, 1.0),
        harvesters=(harv,),
        seeds=(0, 2),
        horizon=15.0,
    )
    cfg, statics, _ = fleet.build(grid)
    ref = fleet.simulate_fleet(cfg, statics, mode="vmap")
    ker = fleet.simulate_fleet(cfg, statics, mode="pallas")
    for name in ref._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(ref, name)), np.asarray(getattr(ker, name)),
            err_msg=name)
