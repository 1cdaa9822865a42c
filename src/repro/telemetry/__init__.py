"""Jit-safe observability for the fleet, serve, and adaptation paths.

The measurement substrate every perf PR measures itself against:

* :class:`Telemetry` — the per-device pytree of counters, extrema, an
  exit-depth histogram and a fixed-size event ring buffer, carried
  alongside :class:`repro.core.step.DeviceCarry` through the scan
  frontends.  Enabling it is numerics-neutral (events are derived from
  carry deltas); disabling it (``telemetry=None``, the default everywhere)
  compiles every instrumented branch out of the hot path entirely.
* :class:`TelemetryConfig` — hashable static config; pass it to
  ``fleet.simulate_fleet`` / ``fleet.run_segments`` /
  ``FleetServeEngine.run`` as ``telemetry=``.
* :func:`summarize` / :class:`TelemetrySummary` — host-side per-segment
  reduction, the structured replacement for ad-hoc carry diffing in
  :class:`repro.adapt.online.OnlineAdapter`.
* :class:`TelemetryLogger` / :func:`read_jsonl` — structured JSONL event
  streams, rendered by ``python -m repro.telemetry.report``.
* :func:`span` — host spans (``repro:<name>`` ``TraceAnnotation``s with
  counters) at the layer boundaries of the fleet build and live serving,
  on the profiler's clock; each keeps its own host-clock ``.seconds``.

Usage::

    tcfg = TelemetryConfig(ring_size=512)
    res, carry, tel = fleet.run_segments(cfg, statics, n_segments=8,
                                         telemetry=tcfg)
    summary = summarize(tel, statics.horizon)
    summary.miss_rate, summary.exit_hist, summary.energy_min
"""
from .export import (  # noqa: F401
    TelemetryLogger,
    TelemetrySummary,
    read_jsonl,
    summarize,
)
from .spans import span  # noqa: F401
from .state import (  # noqa: F401
    EVENT_KINDS,
    EVENT_NAMES,
    Telemetry,
    TelemetryConfig,
    init_fleet_telemetry,
    init_telemetry,
    record_anytime_step,
    record_knob_updates,
    record_step,
)
