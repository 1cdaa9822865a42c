"""Launcher drivers (train/serve CLIs) — reduced-scale end-to-end runs."""
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from _subproc import sub_env


def run_module(args, timeout=600):
    out = subprocess.run(
        [sys.executable, "-m"] + args,
        capture_output=True, text=True, timeout=timeout, env=sub_env(),
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_train_driver_reduced():
    out = run_module([
        "repro.launch.train", "--arch", "qwen1.5-0.5b", "--reduced",
        "--steps", "6", "--batch", "4", "--seq", "32", "--log-every", "5",
    ])
    assert "step     0" in out
    assert "done: 6 steps" in out
    # loss is finite and printed
    losses = [float(l.split("loss")[1].split()[0])
              for l in out.splitlines() if "loss" in l]
    assert losses and all(l == l for l in losses)  # not NaN


def test_train_driver_checkpoint(tmp_path):
    out = run_module([
        "repro.launch.train", "--arch", "xlstm-125m", "--reduced",
        "--steps", "4", "--batch", "2", "--seq", "16",
        "--ckpt-every", "4", "--ckpt-path", str(tmp_path / "ck"),
    ])
    assert "checkpoint ->" in out
    assert (tmp_path / "ck_4.npz").exists()


def test_dryrun_cli_single_combo(tmp_path):
    """The dryrun CLI end to end on the smallest (arch, shape)."""
    out_file = tmp_path / "rec.json"
    run_module([
        "repro.launch.dryrun", "--arch", "xlstm-125m",
        "--shape", "decode_32k", "--out", str(out_file),
    ], timeout=900)
    import json

    rec = json.loads(out_file.read_text())
    assert rec["status"] == "ok"
    assert rec["n_devices"] == 256
    assert rec["roofline"]["dominant"] in ("compute", "memory", "collective")


_CACHE_SUB = """
import jax
import jax.numpy as jnp

from repro.launch.compile_cache import DEFAULT_DIR, enable_compile_cache

jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
print("CACHE", enable_compile_cache())
print("DEFAULT", DEFAULT_DIR)
jax.jit(lambda x: jnp.cumsum(x * 3.0 - 1.0))(jnp.arange({n}.0)).block_until_ready()
"""


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_location(tmp_path, from_env):
    """The CLIs' compile cache lands in ``JAX_COMPILATION_CACHE_DIR`` when
    it is set, else in one fixed directory inside the checkout."""
    env = sub_env()
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cc")
    code = textwrap.dedent(_CACHE_SUB).format(n=37 + from_env)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300, env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = dict(l.split(" ", 1) for l in out.stdout.splitlines())
    default = Path(lines["DEFAULT"])
    assert default == Path(__file__).resolve().parents[1] / ".jax_compile_cache"
    if from_env:
        assert lines["CACHE"] == str(tmp_path / "cc")
        assert any((tmp_path / "cc").iterdir())
    else:
        assert lines["CACHE"] == str(default)
        assert any(default.iterdir())
