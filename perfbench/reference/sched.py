"""Plain reference of the fixed-timestep Zygarde device scheduler.

Written from the semantics the configurations state (paper §4-5: periodic
task releases into a three-slot queue, expiry at the deadline, the zeta /
zeta_I, EDF, EDF-M and round-robin priorities, fragment execution gated by
the capacitor's energy, the utility test at unit boundaries), with no
import from the program.  Every device of a batch steps in lock-step; the
leading axis of every array is the device.

``fdt`` is the floating type of every real-valued quantity (clock, energy,
priorities).  float32 is the configurations' own precision; the benchmark's
control runs the same code in bfloat16.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

POLICIES = {"zygarde": 0, "edf": 1, "edf-m": 2, "rr": 3}
IMPRECISE = ("zygarde", "edf-m")
NEG = -1e30            # score of an empty slot
TIE = 1e-9             # EDF deadline ties break by release order
RR_W = 1e4             # round-robin task rotation outweighs release order

RESULT_FIELDS = ("released", "scheduled", "correct", "deadline_misses",
                 "units_executed", "optional_units", "busy_time",
                 "idle_no_energy", "reboots", "wasted_reexec", "sim_time",
                 "task_released", "task_scheduled", "task_correct",
                 "task_misses", "task_units", "task_optional")


# --------------------------------------------------------------------------- #
# Inputs, from the configuration's own terms.
# --------------------------------------------------------------------------- #


def markov_events(p_on: float, p_off: float, n_slots: int, seed: int):
    """ON/OFF harvest slots of a two-state Markov source that starts ON:
    uniform draws ``u = default_rng(seed).random(n_slots)``; the state
    flips in a slot where ``u`` exceeds the current state's stay
    probability."""
    u = np.random.default_rng(seed).random(n_slots)
    out = np.empty(n_slots, np.float32)
    state = 1
    for i in range(n_slots):
        if u[i] > (p_on if state else p_off):
            state = 1 - state
        out[i] = state
    return out


def releases_within(period: float, horizon: float, cap: int) -> int:
    """Jobs released before ``horizon`` when the release clock advances by
    repeated addition of the period (at most ``cap``)."""
    t, j = 0.0, 0
    while t < horizon and j < cap:
        t += period
        j += 1
    return j


def task_tables(tasks, horizon: float) -> dict:
    """Per-task tables ``(K,)`` / ``(K, U)`` of a task set.  Each task is a
    dict with ``period``, ``deadline``, ``unit_time`` / ``unit_energy``
    (one entry per unit), ``fragments``, ``jobs`` and, for replayed
    profiles, ``margins`` / ``passes`` / ``correct`` per unit.  Rows of
    shallower tasks repeat their last unit up to the deepest task."""
    n_units = [len(t["unit_time"]) for t in tasks]
    U = max(n_units)

    def pad(vals, dtype):
        vals = list(vals)
        return np.asarray(vals + [vals[-1]] * (U - len(vals)), dtype)

    out = dict(
        period=np.asarray([t["period"] for t in tasks], np.float32),
        deadline=np.asarray([t["deadline"] for t in tasks], np.float32),
        fragments=np.asarray([t["fragments"] for t in tasks], np.float32),
        n_units=np.asarray(n_units, np.int32),
        n_releases=np.asarray([releases_within(t["period"], horizon,
                                               t["jobs"]) for t in tasks],
                              np.int32),
        unit_time=np.stack([pad(t["unit_time"], np.float32) for t in tasks]),
        unit_energy=np.stack([pad(t["unit_energy"], np.float32)
                              for t in tasks]),
    )
    if "margins" in tasks[0]:
        for f, dt in (("margins", np.float32), ("passes", bool),
                      ("correct", bool)):
            out[f] = np.stack([pad(t[f], dt) for t in tasks])
    return out


def clock_step(tasks) -> float:
    """The timestep: one fragment of the shortest unit of any task."""
    return min(float(np.min(np.asarray(t["unit_time"], np.float64)))
               / t["fragments"] for t in tasks)


def device_rows(devices, tasks, horizon: float) -> dict:
    """Per-device scalars ``(N,)`` and harvest events ``(N, S)``.  Each
    device is a dict with ``policy``, ``eta``, ``seed`` and a
    ``harvester`` (``p_on``, ``p_off``, ``power``, ``slot_s``) and
    ``capacitor`` (``farad``, ``v_max``, ``v_min``)."""
    max_frag_e = max(float(np.max(np.asarray(t["unit_energy"], np.float64)))
                     / t["fragments"] for t in tasks)
    max_deadline = max(t["deadline"] for t in tasks)
    rows = {k: [] for k in ("policy", "imprecise", "is_edfm", "eta",
                            "alpha", "persistent", "capacity",
                            "start_energy", "e_man", "e_opt", "power_on",
                            "events")}
    for d in devices:
        h, c = d["harvester"], d["capacitor"]
        cap_j = 0.5 * c["farad"] * (c["v_max"] ** 2 - c["v_min"] ** 2)
        rows["policy"].append(POLICIES[d["policy"]])
        rows["imprecise"].append(d["policy"] in IMPRECISE)
        rows["is_edfm"].append(d["policy"] == "edf-m")
        rows["eta"].append(d["eta"])
        rows["alpha"].append(1.0 / max_deadline)
        rows["persistent"].append(d["eta"] >= 1.0 and h["p_on"] >= 1.0)
        rows["capacity"].append(cap_j)
        rows["start_energy"].append(-0.5 * c["farad"] * c["v_min"] ** 2)
        rows["e_man"].append(max_frag_e)
        rows["e_opt"].append(0.7 * cap_j)
        rows["power_on"].append(h["power"])
        rows["events"].append(markov_events(
            h["p_on"], h["p_off"], int(horizon / h["slot_s"]) + 2,
            d["seed"]))
    out = {k: np.asarray(v, np.float32) for k, v in rows.items()}
    for k in ("imprecise", "is_edfm", "persistent"):
        out[k] = np.asarray(rows[k], bool)
    out["policy"] = np.asarray(rows["policy"], np.int32)
    return out


# --------------------------------------------------------------------------- #
# The transition.
# --------------------------------------------------------------------------- #


def _pick(a, idx):
    """``a[n, idx[n]]`` for ``a`` of shape ``(N, M)``."""
    return jnp.take_along_axis(a, idx[:, None], axis=1)[:, 0]


def init_state(dev, tab, n: int, q: int, fdt):
    K = tab["period"].shape[0]
    zi = lambda *s: jnp.zeros((n,) + s, jnp.int32)  # noqa: E731
    zf = lambda *s: jnp.zeros((n,) + s, fdt)  # noqa: E731
    return dict(
        energy=jnp.asarray(dev["start_energy"], fdt), was_off=jnp.zeros(n, bool),
        next_rel=zi(K), rr=zi(), lock_slot=zi() - 1, lock_job=zi() - 1,
        active=jnp.zeros((n, q), bool), release=zf(q), deadline=zf(q),
        task=zi(q), job=zi(q), unit=zi(q), time_left=zf(q),
        exited=zi(q) - 1, last_pred=zi(q) - 1, mand_time=zf(q) - 1,
        margin=zf(q), correct=jnp.zeros((n, q), bool),
        apass=jnp.zeros((n, q), bool),
        m_sched=zi(K), m_corr=zi(K), m_miss=zi(K), m_units=zi(K),
        m_opt=zi(K), m_reboots=zi(), m_busy=zf(), m_idle=zf(),
        m_wasted=zf())


def _retire(st, tab, mask, live):
    """Per-task (scheduled, correct, missed) counts of the slots in
    ``mask``: a job counts as scheduled when its mandatory part finished
    by its deadline, as correct when it was also classified right at its
    deepest executed unit."""
    K = tab["period"].shape[0]
    sched = mask & (st["mand_time"] >= 0) & (st["mand_time"] <= st["deadline"])
    if live:
        ok = st["correct"]
    else:
        ok = tab["correct"][st["task"], jnp.maximum(st["last_pred"], 0)]
    corr = sched & (st["last_pred"] >= 0) & ok
    miss = mask & ~sched
    hot = st["task"][..., None] == jnp.arange(K)            # (N, Q, K)
    per = lambda m: jnp.sum(hot & m[..., None], axis=1, dtype=jnp.int32)  # noqa: E731
    return per(sched), per(corr), per(miss)


def admit(st, dev, tab, t, live=False):
    """Release at most one job per task, in task order, into a free slot,
    or over the earliest-deadline job whose mandatory part is done."""
    K = tab["period"].shape[0]
    q = st["active"].shape[1]
    slots = jnp.arange(q)
    for k in range(K):
        nr = st["next_rel"][:, k]
        rel = nr.astype(t.dtype) * tab["period"][k].astype(t.dtype)
        releasing = (nr < tab["n_releases"][k]) & (rel <= t)
        free = ~st["active"]
        has_free = free.any(1)
        evictable = st["active"] & (st["exited"] >= 0)
        has_evict = evictable.any(1)
        victim = jnp.argmin(jnp.where(evictable, st["deadline"], jnp.inf), 1)
        evict = releasing & ~has_free & has_evict
        vmask = evict[:, None] & (slots == victim[:, None])
        d_s, d_c, d_m = _retire(st, tab, vmask, live)
        insert = releasing & (has_free | has_evict)
        slot = jnp.where(has_free, jnp.argmax(free, 1), victim)
        ins = insert[:, None] & (slots == slot[:, None])
        dropped = releasing & ~insert
        khot = jnp.arange(K) == k
        dl = rel + tab["deadline"][k].astype(t.dtype)
        st = dict(
            st,
            next_rel=st["next_rel"] + (khot & releasing[:, None]),
            active=(st["active"] & ~vmask) | ins,
            release=jnp.where(ins, rel[:, None], st["release"]),
            deadline=jnp.where(ins, dl[:, None], st["deadline"]),
            task=jnp.where(ins, k, st["task"]),
            job=jnp.where(ins, nr[:, None], st["job"]),
            unit=jnp.where(ins, 0, st["unit"]),
            time_left=jnp.where(ins, tab["unit_time"][k, 0].astype(t.dtype),
                                st["time_left"]),
            exited=jnp.where(ins, -1, st["exited"]),
            last_pred=jnp.where(ins, -1, st["last_pred"]),
            mand_time=jnp.where(ins, -1.0, st["mand_time"]).astype(t.dtype),
            margin=jnp.where(ins, 0.0, st["margin"]).astype(t.dtype),
            correct=st["correct"] & ~ins,
            apass=st["apass"] & ~ins,
            m_sched=st["m_sched"] + d_s, m_corr=st["m_corr"] + d_c,
            m_miss=st["m_miss"] + d_m + (khot & dropped[:, None]))
    return st


def expire(st, tab, t, live=False):
    """Drop every queued job whose deadline has come."""
    gone = st["active"] & (t >= st["deadline"])
    d_s, d_c, d_m = _retire(st, tab, gone, live)
    return dict(st, active=st["active"] & ~gone, m_sched=st["m_sched"] + d_s,
                m_corr=st["m_corr"] + d_c, m_miss=st["m_miss"] + d_m)


def choose(st, dev, tab, t, dt, live=False):
    """Priority pick and the capacitor's charge and drain for one step.
    Returns ``(sel, picked, run, new_energy)``."""
    fdt = t.dtype
    K = tab["period"].shape[0]
    U = tab["unit_time"].shape[1]
    tk, u = st["task"], jnp.minimum(st["unit"], U - 1)
    unit_t = tab["unit_time"][tk, u].astype(fdt)
    unit_e = tab["unit_energy"][tk, u].astype(fdt)
    gate_e = jnp.maximum(unit_e / tab["fragments"][tk].astype(fdt),
                         dev["e_man"][:, None])
    drain = unit_e * (jnp.asarray(dt, fdt) / unit_t)
    if live:
        margin = st["margin"]
    else:
        margin = tab["margins"][tk, jnp.maximum(st["last_pred"], 0)].astype(fdt)
    util = jnp.where(st["last_pred"] >= 0, margin, jnp.zeros((), fdt))
    mand = (st["exited"] < 0).astype(fdt)
    lax_ = st["deadline"] - t
    a, e = dev["alpha"][:, None], st["energy"][:, None]
    base = (1.0 - a * lax_) + (1.0 - util)
    zeta = base + mand
    gate = (dev["eta"][:, None] * e >= dev["e_opt"][:, None]).astype(fdt)
    zeta_i = gate * (base + mand) + (1.0 - gate) * mand * base
    zyg = jnp.where(dev["persistent"][:, None], zeta, zeta_i)
    edf = -(lax_ + TIE * st["release"])
    edfm = mand * edf + (1.0 - mand) * NEG
    rank = jnp.mod(tk - st["rr"][:, None], K).astype(fdt)
    rr = -(rank * RR_W + st["release"])
    pol = dev["policy"][:, None]
    score = jnp.where(pol == 0, zyg, jnp.where(pol == 1, edf,
                                               jnp.where(pol == 2, edfm, rr)))
    score = jnp.where(st["active"], score, jnp.asarray(NEG, fdt))
    thr = jnp.where(dev["policy"] == 0, 0.0, 0.5 * NEG).astype(fdt)
    # a started unit runs to its boundary while its job stays queued
    ls = jnp.maximum(st["lock_slot"], 0)
    locked = ((st["lock_slot"] >= 0) & _pick(st["active"], ls)
              & (_pick(st["job"], ls) == st["lock_job"]))
    sel = jnp.where(locked, ls, jnp.argmax(score, 1))
    picked = locked | (score.max(1) > thr)
    slot = jnp.minimum((t / dev["slot_s"]).astype(jnp.int32),
                       dev["events"].shape[1] - 1)
    charge = dev["events"][:, slot] * dev["power_on"] * jnp.asarray(dt, fdt)
    run = picked & (st["energy"] >= _pick(gate_e, sel))
    e_new = (jnp.minimum(st["energy"] + charge, dev["capacity"])
             - run.astype(fdt) * _pick(drain, sel))
    return sel, picked, run, e_new


def execute(st, dev, tab, t, t_end, dt, sel, picked, run, e_new,
            outcome=None):
    """Run ``dt`` of the selected unit; at its boundary apply the utility
    test and retire the job when it is done.  ``outcome`` is the live
    ``(margin, passed, correct)`` of the selected slot's unit; without it
    the replayed tables decide."""
    fdt = t.dtype
    K = tab["period"].shape[0]
    q = st["active"].shape[1]
    U = tab["unit_time"].shape[1]
    oh = jnp.arange(q) == sel[:, None]
    tk, u = st["task"], st["unit"]
    uc = jnp.minimum(u, U - 1)
    tk_sel = _pick(tk, sel)
    frag_t = (tab["unit_time"][tk_sel, _pick(uc, sel)].astype(fdt)
              / tab["fragments"][tk_sel].astype(fdt))
    reboot = run & st["was_off"]
    dtf = jnp.asarray(dt, fdt)
    zero = jnp.zeros((), fdt)
    left = st["time_left"] - jnp.where(run[:, None] & oh, dtf, zero)
    complete = run[:, None] & oh & (left <= jnp.asarray(dt * 1e-3, fdt))
    nu = tab["n_units"][tk]
    done_any = complete.any(1)
    mand = st["exited"] < 0
    last_pred = jnp.where(complete, uc, st["last_pred"])
    left = jnp.where(complete,
                     tab["unit_time"][tk, jnp.minimum(u + 1, U - 1)]
                     .astype(fdt), left)
    if outcome is None:
        passed = tab["passes"][tk, uc]
        margin, correct = st["margin"], st["correct"]
    else:
        m_sel, p_sel, c_sel = outcome
        passed = jnp.broadcast_to(p_sel[:, None], complete.shape)
        margin = jnp.where(complete, m_sel[:, None], st["margin"])
        correct = jnp.where(complete, c_sel[:, None], st["correct"])
    exit_now = complete & dev["imprecise"][:, None] & mand & passed
    exited = jnp.where(exit_now, uc, st["exited"])
    full = complete & (exited < 0) & (u + 1 >= nu)
    exited = jnp.where(full, nu - 1, exited)
    mand_time = jnp.where(exit_now | full, t_end, st["mand_time"])
    done = complete & ((u + 1 >= nu)
                       | (dev["is_edfm"][:, None] & (exited >= 0)))
    d_s, d_c, d_m = _retire(dict(st, last_pred=last_pred, mand_time=mand_time,
                                 correct=correct), tab, done,
                            outcome is not None)
    lock_on = picked & ~done_any
    khot = jnp.arange(K) == tk_sel[:, None]
    opt = done_any & ~_pick(mand, sel)
    return dict(
        st, energy=e_new, was_off=~run & (picked | st["was_off"]),
        rr=jnp.where((dev["policy"] == 3) & done_any, (tk_sel + 1) % K,
                     st["rr"]),
        lock_slot=jnp.where(lock_on, sel, -1),
        lock_job=jnp.where(lock_on, _pick(st["job"], sel), -1),
        active=st["active"] & ~done, unit=jnp.where(complete, u + 1, u),
        time_left=left, exited=exited, last_pred=last_pred,
        mand_time=mand_time, margin=margin, correct=correct,
        m_sched=st["m_sched"] + d_s, m_corr=st["m_corr"] + d_c,
        m_miss=st["m_miss"] + d_m,
        m_units=st["m_units"] + (khot & done_any[:, None]),
        m_opt=st["m_opt"] + (khot & opt[:, None]),
        m_reboots=st["m_reboots"] + (reboot & (st["m_busy"] > 0)),
        m_busy=st["m_busy"] + jnp.where(run, dtf, zero),
        m_idle=st["m_idle"] + jnp.where(picked & ~run, dtf, zero),
        m_wasted=st["m_wasted"] + jnp.where(reboot, 0.5 * frag_t, zero))


def results(st, tab, horizon: float, live=False) -> dict:
    """The run's outcome per device: jobs still queued at the horizon are
    retired, and releases that never happened count as misses."""
    d_s, d_c, d_m = _retire(st, tab, st["active"], live)
    sched, corr = st["m_sched"] + d_s, st["m_corr"] + d_c
    miss = st["m_miss"] + d_m + (tab["n_releases"] - st["next_rel"])
    n = st["energy"].shape[0]
    rel = jnp.broadcast_to(jnp.asarray(tab["n_releases"]), sched.shape)
    return dict(
        released=rel.sum(1), scheduled=sched.sum(1), correct=corr.sum(1),
        deadline_misses=miss.sum(1), units_executed=st["m_units"].sum(1),
        optional_units=st["m_opt"].sum(1), busy_time=st["m_busy"],
        idle_no_energy=st["m_idle"], reboots=st["m_reboots"],
        wasted_reexec=st["m_wasted"],
        sim_time=jnp.full((n,), horizon, st["m_busy"].dtype),
        task_released=rel, task_scheduled=sched, task_correct=corr,
        task_misses=miss, task_units=st["m_units"], task_optional=st["m_opt"])


def as_device(dev: dict, slot_s: float, fdt) -> dict:
    """Per-device rows on the device in the reference's floating type."""
    out = {k: jnp.asarray(v, fdt) if v.dtype == np.float32 else jnp.asarray(v)
           for k, v in dev.items()}
    out["slot_s"] = jnp.asarray(slot_s, fdt)
    return out


def simulate(tasks, devices, horizon: float, queue: int = 3,
             fdt=jnp.float32) -> dict:
    """Replay every device of ``devices`` over the horizon; returns the
    result fields as numpy arrays ``(N,)`` / ``(N, K)``."""
    tab = {k: jnp.asarray(v) for k, v in task_tables(tasks, horizon).items()}
    dt = clock_step(tasks)
    n_steps = int(round(horizon / dt))
    dev = as_device(device_rows(devices, tasks, horizon),
                    devices[0]["harvester"]["slot_s"], fdt)

    @jax.jit
    def run(dev, tab):
        st0 = init_state(dev, tab, dev["eta"].shape[0], queue, fdt)

        def step(st, i):
            t = i.astype(fdt) * jnp.asarray(dt, fdt)
            t_end = (i + 1).astype(fdt) * jnp.asarray(dt, fdt)
            st = admit(st, dev, tab, t)
            st = expire(st, tab, t)
            sel, picked, run_, e_new = choose(st, dev, tab, t, dt)
            return execute(st, dev, tab, t, t_end, dt, sel, picked, run_,
                           e_new), None

        st, _ = lax.scan(step, st0, jnp.arange(n_steps))
        return results(st, tab, horizon)

    return {k: np.asarray(v) for k, v in run(dev, tab).items()}
