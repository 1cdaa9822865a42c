"""Plain reference of live fleet serving with on-device adaptation.

Each device serves its own frames of every task through the scheduler of
:mod:`reference.sched` in live mode: when a unit of the selected job
completes, its real feature is classified by L1 distance against the
device's current bank (top-2 margin ``(d2 - d1) / (d1 + d2)`` over the
unit's selected features), and the outcome drives the utility test.  The
first time a job's margin clears its unit's threshold the device adapts:
the nearest centroid moves to ``(w c + x) / (w + 1)``, its member count
grows by one, and the centroid is propagated through every deeper unit
(``c' = relu(unit(r c)) / r``, paper §4.3).  No import from the program.

Shapes: ``N`` devices, ``K`` tasks, ``J`` jobs per task, ``U`` units of the
deepest task, ``C`` clusters, ``F`` the widest feature, ``S`` selected
features per unit.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from . import cnn, sched

LOG_FIELDS = ("units", "pred", "correct", "margin", "exit_unit", "sched")


def bank_tables(models, banks):
    """Pad every task's per-unit classifiers to ``(K, U, C, F)``."""
    K = len(models)
    dims = [cnn.feature_dims(m) for m in models]
    U = max(len(d) for d in dims)
    C = max(len(b[0]["labels"]) for b in banks)
    F = max(max(d) for d in dims)
    S = len(banks[0][0]["feature_idx"])
    cents = np.zeros((K, U, C, F), np.float32)
    counts = np.ones((K, U, C), np.float32)
    valid = np.zeros((K, U, C), bool)
    clabels = np.zeros((K, U, C), np.int32)
    fidx = np.zeros((K, U, S), np.int32)
    thr = np.zeros((K, U), np.float32)
    for k, bank in enumerate(banks):
        for u, uc in enumerate(bank):
            c, f = uc["centroids"].shape
            cents[k, u, :c, :f] = uc["centroids"]
            counts[k, u, :c] = uc["counts"]
            valid[k, u, :c] = True
            clabels[k, u, :c] = uc["labels"]
            fidx[k, u] = uc["feature_idx"]
            thr[k, u] = uc["threshold"]
    return dict(cents=cents, counts=counts, valid=valid, clabels=clabels,
                fidx=fidx, thr=thr)


def _features(models, params, frames, precision, mdtype):
    """``(N, K, J, U, F)`` features of every device's frames
    ``frames[k]`` ``(N, J, H, W, C)``."""
    dims = [cnn.feature_dims(m) for m in models]
    U = max(len(d) for d in dims)
    F = max(max(d) for d in dims)
    out = []
    for m, p, x in zip(models, params, frames):
        n, j = x.shape[:2]
        fs = cnn.features(m, p, jnp.asarray(x).reshape((n * j,) + x.shape[2:]),
                          precision, mdtype)
        fs = [jnp.pad(f, ((0, 0), (0, F - f.shape[1]))) for f in fs]
        fs += [jnp.zeros_like(fs[0])] * (U - len(fs))
        out.append(jnp.stack(fs, 1).reshape(n, j, U, F))
    return jnp.stack(out, 1)


def serve(models, params, banks, frames, labels, devices, settings,
          precision=lax.Precision.HIGHEST, mdtype=jnp.float32) -> dict:
    """Serve ``frames[k]`` ``(N, J, H, W, C)`` with ``labels[k]`` ``(N, J)``
    on the ``N`` ``devices``.  Returns the per-job log ``(N, K, J)``, the
    final banks ``(N, K, U, C, F)`` and the scheduler's result fields."""
    s = settings
    n_units = [len(cnn.unit_shapes(m)) for m in models]
    J = frames[0].shape[1]
    tasks = [dict(period=s["period"], deadline=s["deadline"],
                  unit_time=[s["unit_time"]] * nu,
                  unit_energy=[s["unit_energy"]] * nu,
                  fragments=s["fragments"], jobs=J) for nu in n_units]
    horizon = s["horizon_s"]
    tab = {k: jnp.asarray(v) for k, v in
           sched.task_tables(tasks, horizon).items()}
    dt = sched.clock_step(tasks)
    n_steps = int(round(horizon / dt))
    dev = sched.as_device(sched.device_rows(devices, tasks, horizon),
                          devices[0]["harvester"]["slot_s"], jnp.float32)
    bt = {k: jnp.asarray(v) for k, v in bank_tables(models, banks).items()}
    feats = _features(models, params, frames, precision, mdtype)
    labels = jnp.stack([jnp.asarray(l) for l in labels], 1)      # (N, K, J)
    N = feats.shape[0]
    cents0 = jnp.broadcast_to(bt["cents"], (N,) + bt["cents"].shape)
    counts0 = jnp.broadcast_to(bt["counts"], (N,) + bt["counts"].shape)
    dims = [cnn.feature_dims(m) for m in models]
    w = jnp.float32(s["adapt_weight"])
    adapt = bool(s.get("adapt", True))

    def adapt_one(cents, counts, x, tk, u, ci, do):
        K, U, C, _ = cents.shape
        hit = (do & (jnp.arange(K)[:, None, None] == tk)
               & (jnp.arange(U)[None, :, None] == u)
               & (jnp.arange(C)[None, None, :] == ci))
        cents = jnp.where(hit[..., None],
                          ((w * cents + x) / (w + 1.0)).astype(cents.dtype),
                          cents)
        counts = counts + hit
        for k, m in enumerate(models):
            for v in range(n_units[k] - 1):
                act = do & (tk == k) & (u <= v)
                r = counts[k, v, ci]
                src = cents[k, v, ci, :dims[k][v]]
                img = cnn.unit_apply(m, params[k], v + 1, (r * src)[None],
                                     precision, mdtype)[0] / r
                old = cents[k, v + 1, ci, :dims[k][v + 1]]
                cents = cents.at[k, v + 1, ci, :dims[k][v + 1]].set(
                    jnp.where(act, img.astype(cents.dtype), old))
        return cents, counts

    @jax.jit
    def run(dev, tab, bt, feats, labels, cents, counts):
        fdt = jnp.float32
        st = sched.init_state(dev, tab, N, s["queue_size"], fdt)
        K, Jl = labels.shape[1:]
        log = dict(units=jnp.zeros((N, K, Jl), jnp.int32),
                   pred=jnp.full((N, K, Jl), -1, jnp.int32),
                   correct=jnp.zeros((N, K, Jl), bool),
                   margin=jnp.zeros((N, K, Jl), fdt),
                   exit_unit=jnp.full((N, K, Jl), -1, jnp.int32),
                   sched=jnp.zeros((N, K, Jl), bool))
        U = feats.shape[3]
        rows = jnp.arange(N)

        def step(carry, i):
            st, log, cents, counts = carry
            t = i.astype(fdt) * jnp.asarray(dt, fdt)
            st = sched.admit(st, dev, tab, t, live=True)
            st = sched.expire(st, tab, t, live=True)
            sel, picked, run_, e_new = sched.choose(st, dev, tab, t, dt,
                                                    live=True)
            pk = lambda a: sched._pick(a, sel)  # noqa: E731
            tk = pk(st["task"])
            u = jnp.minimum(pk(st["unit"]), U - 1)
            job = jnp.clip(pk(st["job"]), 0, Jl - 1)
            complete = run_ & (pk(st["time_left"]) - jnp.asarray(dt, fdt)
                               <= jnp.asarray(dt * 1e-3, fdt))
            exited_pre, apass_pre = pk(st["exited"]), pk(st["apass"])
            ddl = pk(st["deadline"])
            nu = tab["n_units"][tk]
            x = feats[rows, tk, job, u]                          # (N, F)
            idx = bt["fidx"][tk, u]                              # (N, S)
            fsel = jnp.take_along_axis(x, idx, 1)
            c = cents[rows, tk, u]                               # (N, C, F)
            csel = jnp.take_along_axis(c, idx[:, None, :], 2)
            dist = jnp.abs(fsel[:, None, :] - csel).sum(-1).astype(fdt)
            dist = jnp.where(bt["valid"][tk, u], dist, jnp.inf)
            ci = jnp.argmin(dist, 1)
            d1 = dist.min(1)
            d2 = jnp.where(jnp.arange(dist.shape[1]) == ci[:, None], jnp.inf,
                           dist).min(1)
            margin = (d2 - d1) / jnp.maximum(d1 + d2, 1e-9)
            pred = bt["clabels"][tk, u, ci]
            correct = pred == labels[rows, tk, job]
            pass_bank = margin > bt["thr"][tk, u]
            st = sched.execute(st, dev, tab, t, t + jnp.asarray(dt, fdt), dt,
                               sel, picked, run_, e_new,
                               outcome=(margin, pass_bank, correct))
            first = complete & pass_bank & ~apass_pre
            oh = jnp.arange(st["active"].shape[1]) == sel[:, None]
            st = dict(st, apass=st["apass"] | (oh & (complete
                                                     & pass_bank)[:, None]))
            exit_now = complete & dev["imprecise"] & (exited_pre < 0) \
                & pass_bank
            mid = jnp.where(exit_now, u, exited_pre)
            mand_now = exit_now | (complete & (mid < 0) & (u + 1 >= nu))
            sched_now = t + jnp.asarray(dt, fdt) <= ddl
            hit = (complete[:, None, None]
                   & (jnp.arange(K)[None, :, None] == tk[:, None, None])
                   & (jnp.arange(Jl)[None, None, :] == job[:, None, None]))

            def put(old, new, mask=None):
                m = hit if mask is None else hit & mask[:, None, None]
                return jnp.where(m, new[:, None, None], old)

            log = dict(units=put(log["units"], u + 1),
                       pred=put(log["pred"], pred),
                       correct=put(log["correct"], correct),
                       margin=put(log["margin"], margin),
                       exit_unit=put(log["exit_unit"], u, first),
                       sched=put(log["sched"], sched_now, mand_now))
            cents, counts = lax.cond(
                first.any() & adapt,
                lambda a: jax.vmap(adapt_one)(*a),
                lambda a: (a[0], a[1]),
                (cents, counts, x, tk, u, ci, first))
            return (st, log, cents, counts), None

        (st, log, cents, counts), _ = lax.scan(
            step, (st, log, cents, counts), jnp.arange(n_steps))
        return log, cents, counts, sched.results(st, tab, horizon, live=True)

    log, cents, counts, res = run(dev, tab, bt, feats, labels,
                                  cents0.astype(mdtype), counts0)
    out = {f: np.asarray(v) for f, v in log.items()}
    out["centroids"] = np.asarray(cents, np.float32)
    out["counts"] = np.asarray(counts)
    out["result"] = {f: np.asarray(v) for f, v in res.items()}
    return out
