"""Surface: fresh policy x eta x harvester x seed sweeps of the fleet
simulator, as a researcher runs them.

One call builds the stacked fleet configuration of a new block of harvest
seeds with ``repro.fleet.build`` and simulates it with
``repro.fleet.simulate_fleet`` in the mix's mode, host build included.
The block's seeds come from ``--seed`` and the call's index; every call
has the same shape, so the work per call does not depend on the seed.

The check re-simulates a sample of the window's devices, drawn from the
seed, with the plain reference (:mod:`reference.sched`) from the
configuration's own terms, and counts the devices whose result differs in
any field.
"""
from __future__ import annotations

import numpy as np

import jax

from reference import sched

#: a call index far from the window's: the warm-up block's seeds
WARMUP_CALL = 2 ** 32


def block_seeds(seed: int, call: int, n: int) -> list[int]:
    ss = np.random.SeedSequence([seed % 2 ** 64, call])
    return [int(s) for s in np.random.default_rng(ss).integers(0, 2 ** 62, n)]


def grid_points(config: dict, seeds: list[int]) -> list[dict]:
    """Devices of one sweep in the sweep's cartesian order: policy, then
    eta, then harvester, then seed."""
    g = config["grid"]
    return [dict(policy=p, eta=e, harvester=h, seed=s,
                 capacitor=config["capacitor"])
            for p in g["policies"] for e in g["etas"]
            for h in g["harvesters"] for s in seeds]


def _program_tasks(config):
    from repro.core.scheduler import JobProfile, TaskSpec

    out = []
    for k, t in enumerate(config["tasks"]):
        prof = JobProfile(np.asarray(t["margins"], np.float64),
                          np.asarray(t["passes"], bool),
                          np.asarray(t["correct"], bool))
        out.append(TaskSpec(
            task_id=k, period=t["period"], deadline=t["deadline"],
            unit_time=np.asarray(t["unit_time"], np.float64),
            unit_energy=np.asarray(t["unit_energy"], np.float64),
            profiles=[prof] * t["jobs"], fragments_per_unit=t["fragments"]))
    return out


class State:
    def __init__(self, ctx, reuse=None):
        from repro import fleet
        from repro.core import energy

        self.ctx = ctx
        self.fleet = fleet
        self.config = ctx.config
        self.params = ctx.params
        self.horizon = float(ctx.params.get("horizon", ctx.config["horizon_s"]))
        self.n_seeds = int(ctx.params["seeds_per_call"])
        self.tasks = _program_tasks(ctx.config)
        g = ctx.config["grid"]
        c = ctx.config["capacitor"]
        self.grid_kw = dict(
            task=self.tasks, policies=tuple(g["policies"]),
            etas=tuple(g["etas"]),
            harvesters=tuple(energy.Harvester(h["name"], h["p_on"],
                                              h["p_off"], h["power"],
                                              h["slot_s"])
                             for h in g["harvesters"]),
            capacitors=(energy.Capacitor(c["farad"], c["v_max"],
                                         c["v_min"]),),
            horizon=self.horizon, queue_size=ctx.config["queue_size"])
        self.kept = []          # (grid point, meta row, result row)
        self.rng = np.random.default_rng(
            np.random.SeedSequence([ctx.seed % 2 ** 64, 1]))

    def sweep(self, call: int):
        span = self.ctx.span
        seeds = block_seeds(self.ctx.seed, call, self.n_seeds)
        grid = self.fleet.SweepGrid(seeds=tuple(seeds), **self.grid_kw)
        with span("build"):
            cfg, statics, meta = self.fleet.build(grid)
        with span("simulate"):
            res = self.fleet.simulate_fleet(cfg, statics,
                                            mode=self.params["mode"])
            res = jax.tree.map(np.asarray, jax.block_until_ready(res))
        return seeds, statics, meta, res


def setup(ctx) -> State:
    st = State(ctx)
    _, statics, _, _ = st.sweep(WARMUP_CALL)
    ctx.extra["steps_per_call"] = statics.n_steps
    return st


def call(st: State, i: int) -> dict:
    seeds, statics, meta, res = st.sweep(i)
    n_dev = len(meta)
    if i < int(st.params["check_calls"]):
        points = grid_points(st.config, seeds)
        pick = st.rng.choice(n_dev, int(st.params["check_devices_per_call"]),
                             replace=False)
        for d in pick:
            st.kept.append((points[d], meta[d],
                            {f: getattr(res, f)[d] for f in res._fields}))
    return {"device_steps": n_dev * statics.n_steps, "attempted": n_dev}


def mismatch_share(kept, ref: dict) -> float:
    """Share of the kept devices whose result differs from ``ref`` in any
    field, or whose grid point is not the one the sweep stated."""
    bad = 0
    for i, (point, meta, row) in enumerate(kept):
        same_point = (meta["policy"] == point["policy"]
                      and meta["eta"] == point["eta"]
                      and meta["harvester"] == point["harvester"]["name"]
                      and meta["seed"] == point["seed"])
        same = all(np.array_equal(np.asarray(row[f]), ref[f][i])
                   for f in sched.RESULT_FIELDS)
        bad += not (same_point and same)
    return bad / max(len(kept), 1)


def reference(st: State, fdt=None) -> dict:
    import jax.numpy as jnp

    return sched.simulate(st.config["tasks"], [p for p, _, _ in st.kept],
                          st.horizon, st.config["queue_size"],
                          fdt=jnp.float32 if fdt is None else fdt)


def numbers(st: State, control: bool = False) -> dict:
    """The compared numbers of the kept devices; ``control`` puts the
    reference computed in bfloat16 in the program's place."""
    import jax.numpy as jnp

    ref = reference(st)
    kept = st.kept
    if control:
        ctl = reference(st, jnp.bfloat16)
        kept = [(p, m, {f: ctl[f][i] for f in sched.RESULT_FIELDS})
                for i, (p, m, _) in enumerate(kept)]
    return {"device_mismatch_share": mismatch_share(kept, ref)}


def check(st: State) -> list[dict]:
    limits = st.ctx.traffic["checks"]
    out = [{"name": k, "value": v, "limit": limits[k]}
           for k, v in numbers(st).items()]
    out.append({"name": "devices_checked_short",
                "value": float(len(st.kept) == 0), "limit": 0.0})
    return out
