#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 perfbench/run.py --workload sim.multitask --seed 7 --seconds 10 --trace 0

Everything that belongs to a cell is found by name from ``BENCHMARK.json``:
the configuration file it names, the traffic mix
``perfbench/traffic/<traffic>.json``, the surface runner the mix names
(``perfbench/surfaces/<surface>.py``) and one reader per metric
(``perfbench/metrics/<metric>.py``).  A later cell, mix or metric is new
files and a manifest entry.

The run: set-up (inputs, weights, warm-up of every shape the cell uses),
then calls back to back until ``--seconds`` have passed, counting whole
calls; then the check of what the window produced against the plain
reference.  ``--trace 1`` records a profiler trace of the window and
reports the cell's per-layer metrics instead of its end-to-end ones.

The last line on standard output is the result as one JSON object.  The
run refuses to report from anything but a TPU; ``--rehearse`` drives the
same path on the CPU at the mix's tiny rehearsal sizes and prints a
``REHEARSAL`` line, never a result.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_compile_cache"
TRACE_DIR = ROOT / ".perfbench_trace"


def load_module(path: Path, name: str):
    if not path.is_file():
        raise FileNotFoundError(f"missing {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve(workload: str, rehearse: bool):
    """The cell's manifest entries, files and the metrics it reports."""
    man = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in man["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: "
                       f"{sorted(cells)}")
    cell = cells[workload]
    conf = {c["name"]: c for c in man["configs"]}[cell["config"]]
    config = json.loads((ROOT / conf["file"]).read_text())
    traffic = json.loads(
        (BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    params = dict(traffic["params"])
    if rehearse:
        params.update(traffic["rehearsal"])

    def mine(m):
        return workload in m.get("workloads", [workload])

    e2e = [m for m in man["end_to_end"] if mine(m)]
    per_layer = [m for m in man["per_layer"] if mine(m)]
    return cell, config, traffic, params, e2e, per_layer


class Spans:
    """The harness's own spans around each call into the program: kept on
    the host clock, and written into the profiler trace when it runs."""

    def __init__(self, traced: bool):
        self.traced = traced
        self.rows = []

    @contextlib.contextmanager
    def __call__(self, name: str):
        ann = contextlib.nullcontext()
        if self.traced:
            import jax

            ann = jax.profiler.TraceAnnotation(f"bench:{name}")
        with ann:
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.rows.append((name, t0, time.perf_counter()))


def enable_compile_cache():
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    where the environment sets it, else a fixed directory in the checkout
    (the path is part of the cache key).  Every program is cached."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", metavar="FILE",
                    help="with --trace 1: also write the trace's device "
                         "operations and harness spans to FILE (.jsonl.gz)")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU only: drive the cell at its tiny rehearsal "
                         "sizes and print a REHEARSAL line, no result")
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print("perfbench: the program (src/repro) is not in this checkout",
              file=sys.stderr)
        return 2
    cell, config, traffic, params, e2e, per_layer = resolve(
        args.workload, args.rehearse)
    surface = load_module(BENCH / "surfaces" / f"{traffic['surface']}.py",
                          f"perfbench_surface_{traffic['surface']}")

    if not args.rehearse:
        enable_compile_cache()
    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if args.rehearse != (device["platform"] == "cpu") or (
            not args.rehearse and device["platform"] != "tpu"):
        print(f"perfbench: refusing to run on {device} "
              f"(rehearse={args.rehearse})", file=sys.stderr)
        return 2
    if device["count"] < cell["chips"]:
        print(f"perfbench: {args.workload} needs {cell['chips']} chips, "
              f"found {device['count']}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(BENCH))

    spans = Spans(traced=bool(args.trace))
    ctx = SimpleNamespace(seed=args.seed, config=config, traffic=traffic,
                          params=params, device=device, devices=devs,
                          rehearse=args.rehearse, span=spans, extra={},
                          counters={})
    state = surface.setup(ctx)
    jax.effects_barrier()
    ctx.setup_s = time.perf_counter() - T0

    if args.trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        # No Python tracer: it times every Python call and slows host code
        # such as the fleet build several-fold; the harness's spans are
        # TraceAnnotations, which the host tracer keeps.
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    calls = 0
    with spans("window"):
        t_start = time.perf_counter()
        call_s = []
        while True:
            t_call = time.perf_counter()
            got = surface.call(state, calls)
            call_s.append(time.perf_counter() - t_call)
            calls += 1
            for k, v in got.items():
                ctx.counters[k] = ctx.counters.get(k, 0) + v
            if time.perf_counter() - t_start >= args.seconds:
                break
        ctx.window_s = time.perf_counter() - t_start
    ctx.calls = calls
    if args.trace:
        jax.profiler.stop_trace()
    used = devs[:cell["chips"]]
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in used)

    t_check = time.perf_counter()
    checks = surface.check(state)
    print(f"perfbench: setup {ctx.setup_s:.1f} s, window {ctx.window_s:.1f} s"
          f" over {calls} calls, check {time.perf_counter() - t_check:.1f} s;"
          f" calls (s): {' '.join(f'{c:.3f}' for c in call_s)}",
          file=sys.stderr)
    del state
    ctx.trace = None
    if args.trace:
        from lib import trace as trace_lib

        events = trace_lib.load(str(TRACE_DIR))
        if args.keep_trace:
            trace_lib.save(events, args.keep_trace)
        ctx.trace = trace_lib.reduce(events)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    ctx.spans = spans.rows

    from lib import peaks as peaks_lib

    ctx.peaks = (peaks_lib.peaks(device["kind"]) if not args.rehearse
                 else None)
    metrics = {}
    for m in (per_layer if args.trace else e2e):
        reader = load_module(BENCH / "metrics" / f"{m['name']}.py",
                             "perfbench_metric_" + m["name"].replace(".", "_"))
        value = reader.read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    correct = all(c["value"] <= c["limit"] for c in checks)
    dev_out = dict(device, count=len(used), memory_peak_bytes=peak)
    out = {"correct": correct,
           "attempted": int(ctx.counters.get("attempted", 0)),
           "failed": int(ctx.counters.get("failed", 0)),
           "metrics": metrics, "device": dev_out}
    if ctx.trace is not None:
        from lib import trace as trace_lib

        dev_out.update(busy_s=ctx.trace.busy_s, window_s=ctx.trace.window_s)
        out["breakdown"] = trace_lib.breakdown(ctx.trace)
    out["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                     for c in checks}
    for c in checks:
        print(f"check {c['name']} = {c['value']!r} (limit {c['limit']!r}) "
              f"{'ok' if c['value'] <= c['limit'] else 'FAIL'}",
              file=sys.stderr)
    sys.stderr.flush()
    if args.rehearse:
        out["calls"], out["window_s"] = calls, ctx.window_s
        print("REHEARSAL " + json.dumps(out))
        return 0
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
