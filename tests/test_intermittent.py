"""Intermittent execution substrate: the SONIC-style contract —
run-with-power-failures == run-without, bit-exactly."""
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

import jax.numpy as jnp

from repro.core import energy
from repro.core.intermittent import (
    FRAMStore,
    Fragment,
    fragment_unit,
    run_intermittent,
)

PERSISTENT = energy.Harvester("battery", 1.0, 0.0, 10.0)


def counter_fragments(n=8, time_s=0.05, energy_j=2e-3):
    """n fragments, each appends its index and updates a running hash."""
    frags = []
    for i in range(n):
        def fn(state, i=i):
            return {
                "seq": state["seq"] + [i],
                "acc": state["acc"] * 31 + i,
                "arr": state["arr"] + jnp.float32(i),
            }
        frags.append(Fragment(fn, time_s, energy_j, f"f{i}"))
    return frags


def init_state():
    return {"seq": [], "acc": 7, "arr": jnp.zeros((4,), jnp.float32)}


def test_persistent_run_completes():
    frags = counter_fragments()
    out, stats = run_intermittent(frags, init_state(), PERSISTENT)
    assert out["seq"] == list(range(8))
    assert stats.reboots == 0
    assert stats.fragments_run == 8
    assert stats.off_time == 0.0


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_failure_run_bit_exact(seed):
    """The central idempotence contract: intermittent result == persistent."""
    frags = counter_fragments(n=10, energy_j=4e-2)
    ref, _ = run_intermittent(frags, init_state(), PERSISTENT)
    weak = energy.Harvester("weak", 0.7, 0.7, 0.06)
    cap = energy.Capacitor(capacitance_f=0.02)
    out, stats = run_intermittent(
        frags, init_state(), weak, cap, seed=seed, max_wall=1e4
    )
    assert out["seq"] == ref["seq"]
    assert out["acc"] == ref["acc"]
    np.testing.assert_array_equal(np.asarray(out["arr"]),
                                  np.asarray(ref["arr"]))
    assert stats.fragments_run == 10


def test_snapshot_restores_from_fram():
    fram = FRAMStore()
    frags = counter_fragments(n=6, energy_j=3e-2)
    weak = energy.Harvester("weak", 0.6, 0.6, 0.05)
    out, stats = run_intermittent(
        frags, init_state(), weak, energy.Capacitor(capacitance_f=0.02),
        fram=fram, seed=1, max_wall=1e4,
    )
    assert fram.commits >= stats.fragments_run + 1  # init + per-fragment
    assert out["seq"] == list(range(6))


def test_fragment_unit_splits_costs():
    calls = []
    frags = fragment_unit(lambda s: calls.append(1) or s + 1, 4, 0.4, 8e-3)
    assert len(frags) == 4
    assert sum(f.time_s for f in frags) == pytest.approx(0.4)
    assert sum(f.energy_j for f in frags) == pytest.approx(8e-3)
    out, _ = run_intermittent(frags, 0, PERSISTENT)
    assert out == 1 and calls == [1]  # unit function applied exactly once


@given(st.integers(0, 500), st.floats(0.55, 0.95), st.floats(0.02, 0.2))
@settings(max_examples=15, deadline=None)
def test_idempotence_property(seed, p_stay, power):
    frags = counter_fragments(n=6, energy_j=2.5e-2)
    ref, _ = run_intermittent(frags, init_state(), PERSISTENT)
    harv = energy.Harvester("h", p_stay, p_stay, power)
    out, stats = run_intermittent(
        frags, init_state(), harv, energy.Capacitor(capacitance_f=0.02),
        seed=seed, max_wall=2e4,
    )
    if stats.fragments_run == 6:  # completed within the wall-clock budget
        assert out["seq"] == ref["seq"]
        assert out["acc"] == ref["acc"]
    assert stats.busy_time <= stats.wall_time + 1e-9
