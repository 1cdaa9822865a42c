#!/usr/bin/env python3
"""Chip smoke test: the three execution surfaces, once each, on a TPU.

    python chip_smoke.py               # phases 1-3 on one chip
    python chip_smoke.py --four-chips  # the sharded paths on a 4-chip host

Every phase runs in a process of its own, the only process that touches
JAX (a chip belongs to one process at a time; this parent never imports
JAX).  Each phase checks its own results:

1. ``fleet``: a paper-style sweep grid (4 policies x 4 etas x solar and RF
   harvesters x 512 seeds = 16,384 devices, the task of
   ``examples/fleet_sweep.py``) through ``fleet.simulate_fleet`` with
   ``mode="vmap"`` and ``mode="fused"``.  Every ``FleetResult`` field must
   be bit-identical between the two, and equal to the scalar
   single-device scan (``core.step.simulate_device``) on sampled devices.
2. ``serve``: the two agile CNNs of ``examples/intermittent_serving.py``
   (``configs/paper_cnns.py`` widths, seeded synthetic data) served live by
   ``FleetServeEngine`` over 1,024 devices: ``adapt=True`` (scan), then
   ``adapt=False`` in ``mode="scan"`` and ``mode="fused"``, which must be
   bit-exact, then ``run_stream`` (``adapt=True``) over 2 chunks,
   bit-exact against ``run``.
3. ``anytime``: qwen1.5-0.5b at its published widths (24 layers, d_model
   1024, vocab 151,936, bf16; random weights from a seed) through the path
   behind ``python -m repro.launch.serve --engine anytime``, answering 8
   requests; then the full-depth logits of the first 8 decode steps
   against ``models.transformer.forward``.

``--four-chips`` runs only ``fleet_sharded`` (``simulate_fleet_sharded``
over the 4-chip ``make_fleet_mesh`` vs one chip, bit-identical) and
``serve_sharded`` (``FleetServeEngine.run(mesh=)`` vs unsharded, frozen
bank, bit-exact).

Each phase prints its numbers (work done, compile time, wall time after a
warm-up, peak device bytes) and one ``PHASE {json}`` line.  The last line
of the output is ``{"ok": true, "device": {...}}``, printed only when every
phase passed on a TPU; otherwise the exit code is non-zero.
"""
from __future__ import annotations

import argparse
import json
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
PHASES = ("fleet", "serve", "anytime")
FOUR_CHIP_PHASES = ("fleet_sharded", "serve_sharded")
#: the whole run, compilation included, stays inside 1200 s
BUDGET_S = 1140.0

#: bf16 carries 8 significant bits (unit roundoff 2^-9).  The sequence
#: path and the cached decode path reach the same logits through matmuls
#: and attention reductions of different shapes, so each of the 24 layers
#: may round its residual-stream update differently: an expected relative
#: difference of order 1e-2 in the logits.  A wrong cache slot, position
#: or mask gives differences of order 1, far above this bound.
ANYTIME_LOGITS_RTOL = 5e-2


# --------------------------------------------------------------------------- #
# Child side: one phase, in the process that holds the chip.
# --------------------------------------------------------------------------- #


class _CompileClock:
    """Seconds JAX spent tracing, lowering and compiling, from its own
    monitoring events."""

    def __init__(self):
        from jax import monitoring

        self.s = 0.0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event.startswith("/jax/core/compile/"):
            self.s += duration


def _peak_bytes():
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _timed(fn, clock):
    """Run ``fn`` twice: the first call compiles, the second is the warm
    one.  Returns ``(result, first_call_s, compile_s, warm_s)``."""
    import jax

    c0, t0 = clock.s, time.perf_counter()
    jax.block_until_ready(fn())
    first = time.perf_counter() - t0
    compile_s = clock.s - c0
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, first, compile_s, time.perf_counter() - t0


def _diff_fields(a, b, prefix=""):
    """Names of the NamedTuple fields whose arrays differ in any bit."""
    import numpy as np

    return [prefix + f for f, x, y in zip(a._fields, a, b)
            if not np.array_equal(np.asarray(x), np.asarray(y))]


def _serve_diff(ra, rb, jobs):
    """Fields of two FleetServeResults that differ: per-job logs over the
    first ``jobs`` jobs, the end carry and the fleet aggregates."""
    import numpy as np

    out = [f for f in ("units", "pred", "correct", "margin", "exit_unit",
                       "sched")
           if not np.array_equal(getattr(ra, f)[..., :jobs],
                                 getattr(rb, f)[..., :jobs])]
    out += _diff_fields(ra.carry.dev, rb.carry.dev, "dev.")
    out += _diff_fields(ra.carry.bank, rb.carry.bank, "bank.")
    out += _diff_fields(ra.fleet, rb.fleet, "fleet.")
    return out


def _sweep_grid(n_seeds, horizon):
    from fleet_sweep import make_task

    from repro import fleet
    from repro.core import energy

    return fleet.SweepGrid(
        task=make_task(),
        policies=("zygarde", "edf", "edf-m", "rr"),
        etas=(0.2, 0.5, 0.8, 1.0),
        harvesters=(energy.Harvester("solar", 0.95, 0.95, 0.08),
                    energy.Harvester("rf", 0.85, 0.85, 0.05)),
        seeds=tuple(range(n_seeds)),
        horizon=horizon,
    )


def phase_fleet(clock, n_seeds=512, horizon=40.0, n_ref=8):
    import numpy as np

    import jax

    from repro import fleet
    from repro.core import step as S

    t0 = time.perf_counter()
    cfg, statics, _ = fleet.build(_sweep_grid(n_seeds, horizon))
    D, n = cfg.n_devices, statics.n_steps
    out = {"devices": D, "steps": n, "device_steps": D * n,
           "build_s": time.perf_counter() - t0}
    res = {}
    for mode in ("vmap", "fused"):
        res[mode], first, comp, warm = _timed(
            lambda m=mode: fleet.simulate_fleet(cfg, statics, mode=m), clock)
        out[mode] = {"first_call_s": first, "compile_s": comp,
                     "warm_s": warm, "device_steps_per_s": D * n / warm}
    out["vmap_vs_fused_mismatch"] = _diff_fields(res["vmap"], res["fused"])
    # the scalar single-device scan: the reference the fleet is bit-exact to
    ref_mismatch = []
    for i in np.linspace(0, D - 1, n_ref).astype(int):
        one = S.simulate_device(jax.tree.map(lambda l: l[i], cfg), statics)
        ref_mismatch += [
            f"{f}[{i}]" for f, a, b in zip(one._fields, one, res["vmap"])
            if not np.array_equal(np.asarray(a), np.asarray(b)[i])]
    out["scalar_ref_mismatch"] = ref_mismatch
    r = res["vmap"]
    out["jobs_released"] = int(np.asarray(r.released).sum())
    out["jobs_scheduled"] = int(np.asarray(r.scheduled).sum())
    out["peak_bytes_in_use"] = _peak_bytes()
    ok = (not out["vmap_vs_fused_mismatch"] and not ref_mismatch
          and out["jobs_scheduled"] > 0
          and bool(np.isfinite(np.asarray(r.busy_time)).all()))
    return ok, out


def _serve_models(n_train=384, n_test=128, epochs=3, n_pairs=768):
    """The two visual tasks of ``examples/intermittent_serving.py``."""
    from repro.core.agile import AgileCNN
    from repro.data import make_dataset
    from repro.train import train_agile_cnn

    out = []
    for name, seed in (("cifar100", 0), ("vww", 1)):
        ds = make_dataset(name, n_train=n_train, n_test=n_test, seed=seed)
        t = train_agile_cnn(ds, epochs=epochs, n_pairs=n_pairs, seed=seed)
        out.append((ds, AgileCNN(t.cfg, t.params, t.bank)))
    return out


def _serve_engine(models, n_req, adapt):
    import numpy as np

    from repro.core import energy
    from repro.serve import FleetServeEngine, Request, ServeConfig

    nu = max(m.n_units for _, m in models)
    cfg = ServeConfig(
        policy="zygarde", period=1.0, deadline=2.0, horizon=n_req + 5.0,
        adapt=adapt, unit_time=np.full(nu, 0.22),
        unit_energy=np.full(nu, 7e-3), seed=3)
    eng = FleetServeEngine([m for _, m in models],
                           energy.calibrate_harvester(0.71, 0.35,
                                                      name="solar"),
                           eta=0.71, config=cfg)
    streams = [[Request(ds.x_test[i], int(ds.y_test[i]), release=float(i))
                for i in range(n_req)] for ds, _ in models]
    return eng, streams


def _serve_row(r, first, comp, warm):
    """Counts and times of one served run; ``warm`` is ``None`` when the
    run was not repeated after its compiling call."""
    import numpy as np

    rel = int(np.asarray(r.fleet.released).sum())
    sched = int(np.asarray(r.fleet.scheduled).sum())
    row = {"jobs": r.jobs, "scheduled": sched,
           "deadline_attainment": sched / max(rel, 1),
           "first_call_s": first, "compile_s": comp}
    if warm is not None:
        row.update(warm_s=warm, jobs_per_s=r.jobs / warm)
    return row


def phase_serve(clock, n_devices=1024, n_req=25, n_chunks=2, train=None):
    import jax

    t0 = time.perf_counter()
    models = _serve_models(**(train or {}))
    out = {"devices": n_devices, "requests_per_task": n_req,
           "train_s": time.perf_counter() - t0}
    seeds = list(range(n_devices))
    eng_a, streams = _serve_engine(models, n_req, adapt=True)
    eng, _ = _serve_engine(models, n_req, adapt=False)
    # the adapting bank's propagation convs make this the slowest run:
    # one call, compilation included
    c0, t0 = clock.s, time.perf_counter()
    run_a = jax.block_until_ready(
        eng_a.run(streams, n_devices=n_devices, seeds=seeds))
    out["scan_adapt"] = _serve_row(run_a, time.perf_counter() - t0,
                                   clock.s - c0, None)
    runs = {}
    for mode in ("scan", "fused"):
        runs[mode], first, comp, warm = _timed(
            lambda m=mode: eng.run(streams, n_devices=n_devices, seeds=seeds,
                                   mode=m), clock)
        out[mode] = _serve_row(runs[mode], first, comp, warm)
    out["scan_vs_fused_mismatch"] = _serve_diff(runs["scan"], runs["fused"],
                                                n_req)
    c0, t0 = clock.s, time.perf_counter()
    st = eng_a.run_stream(streams, n_devices=n_devices, seeds=seeds,
                          n_chunks=n_chunks)
    out["stream"] = {"chunks": st.n_chunks, "jobs": st.jobs,
                     "wall_s": time.perf_counter() - t0,
                     "compile_s": clock.s - c0}
    out["stream_vs_run_mismatch"] = _serve_diff(run_a, st, n_req)
    out["peak_bytes_in_use"] = _peak_bytes()
    ok = (not out["scan_vs_fused_mismatch"]
          and not out["stream_vs_run_mismatch"]
          and st.n_chunks == n_chunks
          and all(out[k]["scheduled"] > 0
                  for k in ("scan_adapt", "scan", "fused")))
    return ok, out


def phase_anytime(clock, argv=("--engine", "anytime", "--arch",
                               "qwen1.5-0.5b", "--requests", "8"),
                  n_check=8, rtol=ANYTIME_LOGITS_RTOL):
    import numpy as np

    import jax
    import jax.numpy as jnp

    from repro.launch import serve as serve_cli
    from repro.models import anytime as A
    from repro.models import transformer as T

    t0 = time.perf_counter()
    cfg, params, engine, reqs = serve_cli.build_anytime(
        serve_cli.parse_args(list(argv)))
    jax.block_until_ready(params)
    out = {"arch": cfg.name, "n_layers": cfg.n_layers,
           "d_model": cfg.d_model, "vocab": cfg.vocab, "dtype": cfg.dtype,
           "n_units": cfg.n_units, "requests": len(reqs),
           "scan_steps": engine.scfg.max_steps,
           "init_s": time.perf_counter() - t0}
    res, first, comp, warm = _timed(lambda: engine.run(reqs), clock)
    tokens = int(res.tokens.sum())
    out["serve"] = {"tokens": tokens, "completed": res.completed,
                    "on_time": res.on_time, "mean_depth": res.mean_depth,
                    "first_call_s": first, "compile_s": comp,
                    "warm_s": warm, "tokens_per_s": tokens / warm,
                    "scan_steps_per_s": engine.scfg.max_steps / warm}

    # full-depth logits of the decode path vs the sequence forward
    B, V = 2, cfg.vocab
    toks = jnp.asarray(np.random.default_rng(1).integers(
        0, V, (B, n_check)), jnp.int32)
    ref = jax.jit(lambda p, t: T.forward(cfg, p, {"tokens": t},
                                         remat=False)[0])(params, toks)
    step = jax.jit(lambda p, s, t: A.unit_decode_step(cfg, p, engine.heads,
                                                      s, t))
    st = T.init_decode_state(cfg, B, n_check, cache_len=n_check,
                             stacked=False)
    got = []
    for s in range(n_check):
        ul, st = step(params, st, toks[:, s])
        got.append(ul[-1][..., :V])
    got = np.asarray(jnp.stack(got, axis=1), np.float64)
    ref = np.asarray(ref[..., :V], np.float64)
    rel = (np.linalg.norm(got - ref, axis=-1)
           / np.linalg.norm(ref, axis=-1))
    out["logits_check"] = {
        "positions": n_check, "rtol": rtol,
        "max_rel_l2": float(rel.max()),
        "max_abs": float(np.abs(got - ref).max()),
        "max_abs_ref": float(np.abs(ref).max()),
        "top1_agreement": float((got.argmax(-1) == ref.argmax(-1)).mean())}
    out["peak_bytes_in_use"] = _peak_bytes()
    ok = (tokens > 0 and bool(np.isfinite(got).all())
          and bool(np.isfinite(ref).all()) and float(rel.max()) <= rtol)
    return ok, out


def _shard_devices(leaf):
    """``[(device, shard shape)]`` of a placed array."""
    return [(str(s.device), tuple(s.data.shape))
            for s in leaf.addressable_shards]


def phase_fleet_sharded(clock, n_seeds=512, horizon=10.0):
    import jax

    from repro import fleet
    from repro.launch.mesh import make_fleet_mesh
    from repro.launch.sharding import shard_fleet_config

    cfg, statics, _ = fleet.build(_sweep_grid(n_seeds, horizon))
    D, n = cfg.n_devices, statics.n_steps
    mesh = make_fleet_mesh()
    shards = _shard_devices(shard_fleet_config(mesh, cfg).policy)
    out = {"devices": D, "steps": n, "mesh": dict(mesh.shape),
           "shards": shards}
    one = jax.device_put(cfg, jax.devices()[0])
    r1, first, comp, warm = _timed(
        lambda: fleet.simulate_fleet(one, statics), clock)
    out["one_chip"] = {"first_call_s": first, "compile_s": comp,
                       "warm_s": warm, "device_steps_per_s": D * n / warm}
    r4, first, comp, warm = _timed(
        lambda: fleet.simulate_fleet_sharded(cfg, statics, mesh=mesh), clock)
    out["sharded"] = {"first_call_s": first, "compile_s": comp,
                      "warm_s": warm, "device_steps_per_s": D * n / warm}
    out["sharded_vs_one_mismatch"] = _diff_fields(r1, r4)
    spread = (len({d for d, _ in shards}) == mesh.size
              and all(s[0] == D // mesh.size for _, s in shards))
    out["spread_over_all_devices"] = spread
    return spread and not out["sharded_vs_one_mismatch"], out


def phase_serve_sharded(clock, n_devices=1024, n_req=25):
    from repro.launch.mesh import make_fleet_mesh

    # the comparison is between two placements of the same weights, so the
    # models train briefly here
    models = _serve_models(n_train=128, n_test=n_req, epochs=1, n_pairs=256)
    eng, streams = _serve_engine(models, n_req, adapt=False)
    seeds = list(range(n_devices))
    mesh = make_fleet_mesh()
    r1, first, comp, warm = _timed(
        lambda: eng.run(streams, n_devices=n_devices, seeds=seeds), clock)
    out = {"devices": n_devices, "mesh": dict(mesh.shape),
           "one_chip": _serve_row(r1, first, comp, warm)}
    r4, first, comp, warm = _timed(
        lambda: eng.run(streams, n_devices=n_devices, seeds=seeds,
                        mesh=mesh), clock)
    out["sharded"] = _serve_row(r4, first, comp, warm)
    out["shards"] = _shard_devices(r4.carry.dev.energy)
    out["sharded_vs_one_mismatch"] = _serve_diff(r1, r4, n_req)
    spread = (len({d for d, _ in out["shards"]}) == mesh.size
              and all(s[0] == n_devices // mesh.size
                      for _, s in out["shards"]))
    out["spread_over_all_devices"] = spread
    return spread and not out["sharded_vs_one_mismatch"], out


PHASE_FNS = {"fleet": phase_fleet, "serve": phase_serve,
             "anytime": phase_anytime, "fleet_sharded": phase_fleet_sharded,
             "serve_sharded": phase_serve_sharded}


def _run_child(phase: str) -> int:
    sys.path[:0] = [str(ROOT / "src"), str(ROOT / "examples")]
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if device["platform"] != "tpu":
        print(f"chip smoke: no TPU (JAX found {device})", file=sys.stderr)
        return 2
    if phase in FOUR_CHIP_PHASES and device["count"] != 4:
        print(f"chip smoke: {phase} needs 4 chips, found {device}",
              file=sys.stderr)
        return 2
    enable_compile_cache()
    clock = _CompileClock()
    t0 = time.perf_counter()
    ok, out = PHASE_FNS[phase](clock)
    out["phase_s"] = time.perf_counter() - t0
    for k, v in out.items():
        print(f"{phase}: {k} = {json.dumps(v)}")
    print("PHASE " + json.dumps({"phase": phase, "ok": bool(ok),
                                 "device": device}))
    return 0 if ok else 1


# --------------------------------------------------------------------------- #
# Parent side: never imports JAX.
# --------------------------------------------------------------------------- #


def _run_phase(phase: str, timeout: float):
    """Run one phase in a child; returns its PHASE record or None."""
    try:
        proc = subprocess.run(
            [sys.executable, str(Path(__file__).resolve()), "--phase",
             phase], stdout=subprocess.PIPE, text=True, timeout=timeout,
            cwd=ROOT)
    except subprocess.TimeoutExpired as e:
        print(e.stdout or "", end="")
        print(f"chip smoke: phase {phase} exceeded {timeout:.0f} s",
              file=sys.stderr)
        return None
    record = None
    for line in proc.stdout.splitlines():
        if line.startswith("PHASE "):
            record = json.loads(line[len("PHASE "):])
        else:
            print(line)
    if proc.returncode != 0 or record is None or not record["ok"]:
        print(f"chip smoke: phase {phase} failed (exit {proc.returncode})",
              file=sys.stderr)
        return None
    return record


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Run the fleet simulator, live fleet serving and anytime "
                    "serving once each on a TPU and check their results.")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded fleet and serve paths on a "
                         "4-chip host, against one chip")
    ap.add_argument("--phase", choices=sorted(PHASE_FNS),
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.phase:
        return _run_child(args.phase)
    deadline = time.monotonic() + BUDGET_S
    device = None
    for phase in FOUR_CHIP_PHASES if args.four_chips else PHASES:
        record = _run_phase(phase, deadline - time.monotonic())
        if record is None:
            return 1
        device = record["device"]
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
