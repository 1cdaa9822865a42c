"""The command end to end on the CPU at each cell's rehearsal size, its
refusals, and the check seeing each fault the timed path can have.

The faults are planted in the program underneath a whole run (set-up,
window, check) in this process: a step that returns its state unchanged,
and an answer altered where it is produced.  The cells run on one chip,
so no exchange between chips can be left out, and neither cell averages
over a batch.
"""
import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT

import run

CELLS = ("sim.multitask", "serve.adapt")


def _rehearse(workload, seed, capsys):
    assert run.main(["--workload", workload, "--seed", str(seed),
                     "--seconds", "0.5", "--rehearse"]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert lines[-1].startswith("REHEARSAL ")
    return json.loads(lines[-1][len("REHEARSAL "):])


@pytest.mark.parametrize("workload", CELLS)
def test_rehearsal_is_correct(workload, capsys):
    out = _rehearse(workload, 2 ** 33 + 5, capsys)
    assert out["correct"], out["checks"]
    assert out["device"]["platform"] == "cpu"
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-3] == "checks"


def _unchanged_fleet_step(monkeypatch):
    from repro.kernels import ops

    monkeypatch.setattr(ops, "fleet_fused_steps",
                        lambda cfg, carry, i0, **kw: carry)


def _altered_fleet_answer(monkeypatch):
    from repro.fleet import simulator

    real = simulator.finalize_fleet

    def altered(cfg, states, statics, live=False):
        res = real(cfg, states, statics, live)
        return res._replace(scheduled=res.scheduled + 1)

    monkeypatch.setattr(simulator, "finalize_fleet", altered)


def _unchanged_serve_step(monkeypatch):
    import jax.numpy as jnp

    from repro.serve import fleet_engine

    real = fleet_engine.serve_step

    def unchanged(cfg, look, dev, log, t, job0, *, statics):
        _, _, (fp, tk, u, job, ci) = real(cfg, look, dev, log, t, job0,
                                          statics=statics)
        return dev, log, (jnp.zeros_like(fp), tk, u, job, ci)

    monkeypatch.setattr(fleet_engine, "serve_step", unchanged)


def _altered_serve_answer(monkeypatch):
    from repro.serve import fleet_engine

    real = fleet_engine._classify_rows

    def altered(look, n_tasks, tk, u, job):
        margin, ci, pred = real(look, n_tasks, tk, u, job)
        return margin, ci, pred + 1

    monkeypatch.setattr(fleet_engine, "_classify_rows", altered)


FAULTS = [("sim.multitask", _unchanged_fleet_step),
          ("sim.multitask", _altered_fleet_answer),
          ("serve.adapt", _unchanged_serve_step),
          ("serve.adapt", _altered_serve_answer)]


@pytest.mark.parametrize("workload,plant", FAULTS,
                         ids=[f"{w}-{p.__name__[1:]}" for w, p in FAULTS])
def test_fault_is_not_correct(workload, plant, monkeypatch, capsys):
    plant(monkeypatch)
    out = _rehearse(workload, 77, capsys)
    assert out["correct"] is False, out["checks"]


def _run_cli(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", "sim.multitask",
         "--seed", "3", "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_a_cpu_without_rehearse():
    p = _run_cli(ROOT)
    assert p.returncode != 0 and p.stdout == ""
    assert "refusing" in p.stderr


def test_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_cli(tmp_path)
    assert p.returncode != 0 and p.stdout == ""
