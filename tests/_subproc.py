"""Shared helper for tests that spawn python subprocesses.

Subprocesses don't inherit pytest's ``pythonpath`` ini setting, so the
repo's ``src`` dir must be placed on PYTHONPATH explicitly for
``python -m repro...`` / ``python -c "import repro..."`` children to work
when the package is not pip-installed.
"""
from __future__ import annotations

import os
from pathlib import Path

_SRC = str(Path(__file__).resolve().parents[1] / "src")


def sub_env(host_devices: int | None = None) -> dict:
    """The child's environment; ``host_devices`` gives its CPU backend that
    many placeholder devices (``XLA_FLAGS`` must be set before jax starts,
    so it travels in the environment, not in the child's code)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = _SRC + os.pathsep + env.get("PYTHONPATH", "")
    if host_devices is not None:
        env["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={host_devices}")
    return env
