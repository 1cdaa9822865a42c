"""The controls come out as not correct, and the program as correct, on
the cells' own code paths at their rehearsal sizes.

The control is the plain reference, put in the program's place and
computed at the next precision below the configuration's: bfloat16 for
the fleet sweep's float32, three bfloat16 passes (``high``) for the
served CNNs' float32 at ``highest``.  The reference computes ``high``'s
three passes itself, so the control reads the same on any backend;
``python3 perfbench/readings.py`` reads it on a TPU at the cell's own
size.
"""
import io
from types import SimpleNamespace

import jax.numpy as jnp

from conftest import BENCH

import readings
import run


def _limits(workload):
    _, _, traffic, _, _, _ = run.resolve(workload, True)
    return traffic["checks"]


def _fails(numbers, limits):
    return any(v > limits[k] for k, v in numbers.items() if k in limits)


def test_sim_control_fails_and_program_holds():
    rows = readings.readings("sim.multitask", [2 ** 31 + 9, 10], 1, True,
                             out=io.StringIO())
    lim = _limits("sim.multitask")
    assert _fails(rows[0]["control"], lim)
    assert not any(_fails(r["program"], lim) for r in rows)


def _serve_state(seed):
    cell, config, traffic, params, _, _ = run.resolve("serve.adapt", True)
    surface = run.load_module(BENCH / "surfaces" / "fleet_serve.py",
                              "perfbench_surface_fleet_serve")
    ctx = SimpleNamespace(seed=seed, config=config, traffic=traffic,
                          params=params, span=run.Spans(False), extra={},
                          counters={}, rehearse=True)
    st = surface.State(ctx)
    for i in range(int(params["check_calls"])):
        surface.call(st, i)
    return surface, st


def test_serve_bfloat16_control_fails():
    surface, st = _serve_state(12)
    ref = surface.reference(st)
    ctl = surface.reference(st, "highest", jnp.bfloat16)
    kept = [dict(r, log={f: ctl[f][n] for f in surface.ref_serve.LOG_FIELDS},
                 centroids=ctl["centroids"][n],
                 result={f: ctl["result"][f][n]
                         for f in surface.sched.RESULT_FIELDS})
            for n, r in enumerate(st.kept)]
    lim = _limits("serve.adapt")
    assert _fails(surface.compare(st, kept, ref), lim)
    assert not _fails(surface.compare(st, st.kept, ref), lim)


def test_serve_high_control_fails():
    surface, st = _serve_state(13)
    assert _fails(surface.numbers(st, control=True), _limits("serve.adapt"))


def test_three_pass_product_lies_between_bfloat16_and_float32():
    """The emulated ``high`` product: far closer to the exact product than
    one bfloat16 pass, and not exact."""
    import numpy as np

    import jax
    from jax import lax

    from reference import cnn

    ka, kb = jax.random.split(jax.random.PRNGKey(0))
    a = jax.random.normal(ka, (64, 256), jnp.float32)
    b = jax.random.normal(kb, (256, 32), jnp.float32)
    exact = np.asarray(a, np.float64) @ np.asarray(b, np.float64)

    def dot(x, y, pr):
        return jnp.dot(x, y, precision=pr, preferred_element_type=jnp.float32)

    def err(y):
        return np.abs(np.asarray(y, np.float64) - exact).max()

    three = err(cnn._product(dot, a, b, cnn.BF16_3X))
    one = err(dot(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                  lax.Precision.HIGHEST))
    full = err(dot(a, b, lax.Precision.HIGHEST))
    assert full < three < one / 100
