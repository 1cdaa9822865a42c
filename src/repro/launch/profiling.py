"""Profiling harness: compile/steady split, trace capture, roofline join.

The benchmark lane previously timed jitted entry points with one warm call
and a wall clock — conflating compilation, dispatch, and device time, and
leaving nothing to attribute a regression to.  This module is the shared
measurement core used by :mod:`benchmarks.common` and the ``--profile``
flag on ``benchmarks/run.py``:

* :func:`measure` — AOT-lowers the function (``jit -> lower -> compile``)
  so compile time is measured *separately* from steady-state, then times
  repeated executions with ``jax.block_until_ready`` around every call
  (async dispatch otherwise lets device work leak between timestamps).
* :func:`trace` — a ``jax.profiler`` trace context writing a TensorBoard-
  loadable trace directory; a profiler that cannot start raises.
* :func:`roofline_join` — joins a measured steady-state time against the
  loop-aware HLO cost model (:mod:`repro.launch.hlo_cost`) and the device
  roofline (:func:`repro.launch.hlo_stats.roofline_terms`, with the peaks
  of the device that ran it): modeled FLOPs / bytes, the bound term, and
  measured-vs-bound ratio.
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field
from typing import Optional

import numpy as np

import jax

from .hlo_cost import HloCostModel
from .hlo_stats import collective_stats, roofline_terms


@dataclass
class Measurement:
    """One profiled entry point: compile vs steady-state, plus the optional
    roofline join (``roofline`` stays None unless requested)."""

    label: str
    compile_s: float             # lower+compile wall time (one-off)
    steady_s: float              # median per-call, fully blocked
    steady_min_s: float
    steady_max_s: float
    repeats: int
    roofline: Optional[dict] = None
    extra: dict = field(default_factory=dict)

    def as_row(self) -> dict:
        """Flat JSON/CSV-friendly view for ``benchmarks.common.emit``."""
        row = dict(
            label=self.label,
            compile_s=round(self.compile_s, 4),
            steady_s=round(self.steady_s, 6),
            steady_min_s=round(self.steady_min_s, 6),
            steady_max_s=round(self.steady_max_s, 6),
            repeats=self.repeats,
        )
        if self.roofline is not None:
            row.update({f"roofline_{k}": v for k, v in self.roofline.items()})
        row.update(self.extra)
        return row


def _block(x):
    jax.block_until_ready(x)
    return x


def measure(fn, *args, label: str = "fn", repeats: int = 10,
            warmup: int = 2, static_argnames=(), **kwargs) -> Measurement:
    """Profile one jittable callable: AOT compile split from steady-state.

    ``fn`` is wrapped in ``jax.jit`` (pass ``static_argnames`` for hashable
    statics) and lowered/compiled once under a timer; the compiled
    executable is then run ``warmup`` throwaway + ``repeats`` timed calls,
    each wrapped in ``block_until_ready`` so async dispatch cannot smear
    device work across timestamps.  Keyword args are forwarded to the
    traced call (static ones participate in lowering).
    """
    jitted = jax.jit(fn, static_argnames=tuple(static_argnames))
    t0 = time.perf_counter()
    compiled = jitted.lower(*args, **kwargs).compile()
    compile_s = time.perf_counter() - t0

    dyn_kwargs = {k: v for k, v in kwargs.items()
                  if k not in set(static_argnames)}
    for _ in range(warmup):
        _block(compiled(*args, **dyn_kwargs))
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        _block(compiled(*args, **dyn_kwargs))
        times.append(time.perf_counter() - t0)
    meas = Measurement(
        label=label,
        compile_s=compile_s,
        steady_s=float(np.median(times)),
        steady_min_s=float(np.min(times)),
        steady_max_s=float(np.max(times)),
        repeats=repeats,
    )
    meas.extra["_compiled"] = compiled     # for roofline_join; stripped below
    return meas


def roofline_join(meas: Measurement, n_devices: int = 1) -> Measurement:
    """Attach the HLO-cost roofline attribution to a :func:`measure` result.

    Re-derives loop-aware FLOPs/bytes from the compiled module's
    post-optimization HLO (XLA's own ``cost_analysis`` counts scan bodies
    once — useless for a 400-step ``lax.scan``), computes the roofline
    bound, and records ``measured / bound`` — how far the measured
    steady-state sits above the model's best case.
    """
    compiled = meas.extra.pop("_compiled", None)
    if compiled is None:
        return meas
    hlo = compiled.as_text()
    cost = HloCostModel(hlo, n_devices).entry_cost()
    ici = collective_stats(hlo, n_devices).ici_bytes
    terms = roofline_terms(flops=cost.flops, bytes_accessed=cost.bytes,
                           ici_bytes=ici,
                           device_kind=jax.devices()[0].device_kind)
    bound = terms["bound_s"]
    meas.roofline = dict(
        flops=cost.flops,
        bytes=cost.bytes,
        ici_bytes=ici,
        bound_s=round(bound, 9),
        dominant=terms["dominant"],
        measured_over_bound=(round(meas.steady_s / bound, 2)
                             if bound > 0 else None),
    )
    return meas


def profile_call(fn, *args, label: str = "fn", repeats: int = 10,
                 warmup: int = 2, static_argnames=(), n_devices: int = 1,
                 **kwargs) -> Measurement:
    """:func:`measure` + :func:`roofline_join` in one call (the shape the
    bench modules use under ``--profile``)."""
    meas = measure(fn, *args, label=label, repeats=repeats, warmup=warmup,
                   static_argnames=static_argnames, **kwargs)
    meas = roofline_join(meas, n_devices=n_devices)
    meas.extra.pop("_compiled", None)
    return meas


@contextlib.contextmanager
def trace(log_dir, enabled: bool = True):
    """``jax.profiler`` trace context (TensorBoard / Perfetto loadable).

    ``enabled=False`` makes it a clean no-op so call sites can thread a
    ``--profile`` flag straight through; a profiler that fails to start
    raises — a run asked to trace never silently runs untraced.
    """
    if not enabled:
        yield None
        return
    jax.profiler.start_trace(str(log_dir))
    try:
        yield str(log_dir)
    finally:
        jax.profiler.stop_trace()
