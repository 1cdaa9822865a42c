"""Host spans (``repro.telemetry.span``) and the named scopes of the step
core and the serve scan: where they land, what they count, and that they
change no result."""
from __future__ import annotations

import glob
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import fleet
from repro.core import energy
from repro.core.agile import AgileCNN
from repro.serve import FleetServeEngine, Request, ServeConfig
from repro.serve import fleet_engine
from repro.telemetry import span
from repro.telemetry.spans import PREFIX

from _workloads import make_task, random_task_set

N_JOBS, N_DEV = 3, 2


@pytest.fixture(scope="module")
def engine(trained_cnn):
    cfg = ServeConfig(policy="zygarde", period=2.0, deadline=1.5,
                      horizon=N_JOBS * 2.0 + 2.0, adapt=True,
                      start_charged=True, sim_dt=0.05)
    bank = [uc._replace(threshold=jnp.float32(0.02))
            for uc in trained_cnn.bank]
    model = AgileCNN(trained_cnn.cfg, trained_cnn.params, bank)
    return FleetServeEngine([model], energy.Harvester("battery", 1.0, 0.0,
                                                      1.0),
                            eta=1.0, config=cfg)


@pytest.fixture(scope="module")
def requests(mnist_tiny):
    return [[Request(mnist_tiny.x_test[i], int(mnist_tiny.y_test[i]),
                     release=2.0 * i) for i in range(N_JOBS)]]


def _grid(seeds=(0, 1)):
    harv = energy.Harvester("rf", 0.9, 0.8, 0.07)
    return fleet.SweepGrid(task=make_task(n_jobs=6),
                           policies=("zygarde", "edf"), etas=(0.5, 1.0),
                           harvesters=(harv,), seeds=seeds, horizon=8.0)


def _program_spans(trace_dir):
    """``(name, start_ns, end_ns, stats)`` of every ``repro:`` event in the
    profiler trace under ``trace_dir``."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    out.append((ev.name[len(PREFIX):], ev.start_ns,
                                ev.end_ns, dict(ev.stats)))
    return out


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_span_keeps_its_length_and_reraises():
    with span("unit.test", items=3) as sp:
        assert sp.seconds is None
    assert sp.name == "unit.test" and sp.seconds >= 0.0
    with pytest.raises(KeyError):
        with span("unit.raises") as sp:
            raise KeyError("x")
    assert sp.seconds >= 0.0


def test_profiler_trace_holds_nested_spans_with_counters(engine, requests,
                                                         tmp_path):
    grid = _grid()
    engine.run(requests, n_devices=N_DEV)          # compile outside
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        cfg, statics, _ = fleet.build(grid)
        jax.block_until_ready(fleet.simulate_fleet(cfg, statics))
        res = engine.run(requests, n_devices=N_DEV)
    finally:
        jax.profiler.stop_trace()
    spans = _program_spans(tmp_path)
    by = {}
    for s in spans:
        by.setdefault(s[0], []).append(s)
    assert {n: len(v) for n, v in by.items()} == {
        "fleet.build": 1, "fleet.build.configs": 1, "fleet.simulate": 1,
        "fleet.build.stack": 2, "serve.engine.run": 1,
        "serve.build.configs": 1, "serve.build.featurize": 1,
        "serve.build.carry": 1, "serve.scan": 1, "serve.fetch": 1,
        "serve.bank.refresh": 1}

    (build,), (run,) = by["fleet.build"], by["serve.engine.run"]
    assert _inside(by["fleet.build.configs"][0], build)
    stacks = sorted(by["fleet.build.stack"], key=lambda s: s[1])
    assert _inside(stacks[0], build)
    assert _inside(stacks[1], by["serve.build.configs"][0])
    assert by["fleet.simulate"][0][1] >= build[2]
    stages = ["serve.build.configs", "serve.build.featurize",
              "serve.build.carry", "serve.scan", "serve.fetch"]
    for a, b in zip(stages, stages[1:]):
        assert _inside(by[a][0], run)
        assert by[a][0][2] <= by[b][0][1]
    assert _inside(by["serve.fetch"][0], run)
    assert _inside(by["serve.bank.refresh"][0], run)

    # the counters, recorded where the work happens: one shared stream is
    # featurized once; the build makes one configuration per (policy, eta)
    # point of the grid's 2 x 2 x 2 seeds
    n_steps = engine.build(requests, n_devices=N_DEV)[1].n_steps
    assert by["serve.scan"][0][3] == {"steps": n_steps}
    assert by["serve.build.featurize"][0][3] == {"frames": N_JOBS}
    assert by["fleet.build.configs"][0][3] == {"devices": 8, "configs": 4}
    # one bank refresh a first pass, each the first pass of one job
    assert by["serve.bank.refresh"][0][3] == {
        "refreshes": int((res.exit_unit >= 0).sum())}
    assert all(not s[3] for n, v in by.items() for s in v
               if n not in ("serve.scan", "serve.build.featurize",
                            "fleet.build.configs", "serve.bank.refresh"))
    assert res.jobs == N_DEV * N_JOBS


def test_wall_s_is_the_scan_span(engine, requests, monkeypatch):
    opened = []

    def recording(name, **counts):
        sp = span(name, **counts)
        opened.append(sp)
        return sp

    monkeypatch.setattr(fleet_engine, "span", recording)
    res = engine.run(requests, n_devices=N_DEV)
    (scan,) = [sp for sp in opened if sp.name == "serve.scan"]
    assert res.wall_s == scan.seconds
    assert res.jobs_per_sec == res.jobs / scan.seconds


def test_serve_scan_hlo_names_its_stages(engine, requests):
    cfg, statics, tables, carry0, per_dev = engine.build(requests,
                                                         n_devices=N_DEV)
    runner = engine._runner(statics, statics.n_steps, True, False, per_dev)
    lowered = runner.lower(cfg, tables, carry0, jnp.int32(0))
    hlo = lowered.as_text(dialect="hlo", debug_info=True)
    assert hlo.startswith("HloModule jit__scan_steps")
    scopes = {part for name in re.findall(r'op_name="([^"]*)"', hlo)
              for part in name.split("/")}
    assert {"lookup", "classify", "adapt", "admit", "expire", "pick",
            "apply"} <= scopes


def _reachable(hlo: str, root: str) -> set:
    """Names of the HLO computations ``root`` runs, itself included."""
    comps, name = {}, None
    for line in hlo.splitlines():
        if line and not line.startswith(" ") and line.endswith("{"):
            name = line.split()[1 if line.startswith("ENTRY") else 0]
            comps[name] = []
        elif name is not None:
            comps[name].append(line)
    seen, todo = set(), [root]
    while todo:
        c = todo.pop()
        if c in seen:
            continue
        seen.add(c)
        for one, many in re.findall(
                r"(?:to_apply|calls|body|condition)=([\w.\-]+)"
                r"|branch_computations=\{([^}]*)\}", "\n".join(comps[c])):
            todo += [one] if one else [b.strip() for b in many.split(",")]
    return seen


def test_adapting_scan_selects_the_whole_bank_once(engine, requests):
    """The adapting scan gathers the bank's selected columns over the whole
    bank once, before its loop: the loop body gathers only the rows an
    adaptation moved."""
    cfg, statics, tables, carry0, per_dev = engine.build(requests,
                                                         n_devices=N_DEV)
    runner = engine._runner(statics, statics.n_steps, True, False, per_dev)
    hlo = runner.lower(cfg, tables, carry0, jnp.int32(0)).as_text(
        dialect="hlo")
    view = carry0.bank.centroids.shape[:-1] + tables.fidx.shape[-1:]
    whole = re.compile(r"= f32\[%s\]\{[^}]*\} gather\("
                       % ",".join(map(str, view)))
    (body,) = re.findall(r" while\(.*body=([\w.\-]+)", hlo)
    in_loop = _reachable(hlo, body)
    where, name = [], None
    for line in hlo.splitlines():
        if line and not line.startswith(" ") and line.endswith("{"):
            name = line.split()[1 if line.startswith("ENTRY") else 0]
        elif whole.search(line):
            where.append(name)
    assert len(where) == 1 and where[0] not in in_loop


_SOLAR = energy.Harvester("solar", 0.95, 0.95, 0.08)
_RF = energy.Harvester("rf", 0.9, 0.8, 0.07)
_CAPS = (energy.Capacitor(), energy.Capacitor(0.02, 3.3, 1.8))

# grids in which each axis a configuration depends on has more than one
# value, against the edge cases of the seed axis
BUILD_GRIDS = {
    "every_axis": dict(
        task=make_task(n_jobs=6), policies=("zygarde", "edf"),
        etas=(0.5, 1.0), harvesters=(_RF, _SOLAR), capacitors=_CAPS,
        seeds=(3, 4, 5), clock_drifts=(0.0, 1e-3), horizon=8.0),
    "task_set_charged_e_man": dict(
        task=random_task_set(22, 2), policies=("zygarde", "edf-m"),
        etas=(0.2, 0.8), harvesters=(_SOLAR, _RF), capacitors=_CAPS,
        seeds=(7, 8, 9), clock_drifts=(0.0, 2e-3), horizon=8.0,
        start_charged=True, e_man=2e-3),
    "one_seed": dict(
        task=make_task(n_jobs=6), policies=("rr", "edf"), etas=(0.5, 1.0),
        harvesters=(_RF, _SOLAR), seeds=(11,), horizon=8.0),
    "repeated_seed": dict(
        task=make_task(n_jobs=6), policies=("zygarde",), etas=(0.5, 1.0),
        harvesters=(_RF,), seeds=(5, 5, 6), horizon=8.0),
}


@pytest.mark.parametrize("name", list(BUILD_GRIDS))
def test_fleet_build_returns_the_same_arrays(name):
    """``fleet.build`` against the device-by-device construction it
    replaces: one ``device_config`` a grid point, stacked."""
    grid = fleet.SweepGrid(**BUILD_GRIDS[name])
    cfg, statics, meta = fleet.build(grid)
    points = list(grid.points())
    assert len(meta) == len(points) == cfg.n_devices
    ref = [fleet.device_config(
        grid.tasks, pt["harvester"], pt["eta"], pt["capacitor"],
        policy=pt["policy"], horizon=grid.horizon,
        events=fleet.sample_events(pt["harvester"], grid.horizon,
                                   pt["seed"]),
        e_opt_fraction=grid.e_opt_fraction, e_man=grid.e_man,
        start_charged=grid.start_charged, clock_drift=pt["clock_drift"])
        for pt in points]
    for f in cfg._fields:
        want = np.stack([d[f] for d in ref])
        got = np.asarray(getattr(cfg, f))
        assert got.dtype == want.dtype and np.array_equal(got, want), f
    assert [m["seed"] for m in meta] == [pt["seed"] for pt in points]


def test_fleet_build_makes_one_config_per_distinct_point(monkeypatch):
    """Seeds share their point's configuration: ``device_config`` runs
    once per (policy, eta, harvester, capacitor, drift), and ``meta`` keeps
    one row per device in grid order."""
    from repro.fleet import grid as fgrid

    grid = fleet.SweepGrid(**BUILD_GRIDS["every_axis"])
    real, calls = fgrid.device_config, []

    def counting(*args, **kw):
        calls.append(kw["policy"])
        return real(*args, **kw)

    monkeypatch.setattr(fgrid, "device_config", counting)
    cfg, _, meta = fleet.build(grid)
    assert len(calls) == 2 * 2 * 2 * 2 * 2
    assert cfg.n_devices == len(calls) * 3
    assert meta == [dict(policy=pt["policy"], eta=pt["eta"],
                         harvester=pt["harvester"].name, seed=pt["seed"],
                         capacitance_f=pt["capacitor"].capacitance_f,
                         clock_drift=pt["clock_drift"], n_tasks=1)
                    for pt in grid.points()]
