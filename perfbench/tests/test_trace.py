"""The trace-to-metrics reduction, on hand-made events laid out as a TPU
v5e trace is: a host plane with the harness's ``bench:`` annotations, the
device's ``XLA Ops`` and ``XLA Modules`` lines, and a plane that is not a
TensorCore."""
import pytest

from lib import trace

DEV = "/device:TPU:0"
HOST = "/host:CPU"


def ev(plane, line, name, s, e, module=""):
    return trace.Event(plane, line, name, float(s), float(e), module)


def sample_events():
    return [
        ev(HOST, "python", "bench:window", 0, 1000),
        ev(HOST, "python", "bench:build", 0, 300),
        ev(HOST, "python", "bench:simulate", 300, 900),
        # device ops: two overlap, one straddles the window's end
        ev(DEV, "XLA Ops", "fusion.1", 350, 500, "jit_a"),
        ev(DEV, "XLA Ops", "custom-call.2", 450, 700, "jit_b"),
        ev(DEV, "XLA Ops", "fusion.1", 950, 1100, "jit_a"),
        ev(DEV, "XLA Modules", "jit_a", 0, 5000),      # not an op line
        ev("/device:TPU:0 SparseCore", "XLA Ops", "x", 0, 1000),
    ]


def test_union_merges_overlaps():
    assert trace.union([(5, 6), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 6)]


def test_reduce_busy_ops_and_gaps():
    red = trace.reduce(sample_events())
    assert red.window_s == pytest.approx(1000e-9)
    # busy: [350, 700] and [950, 1000] inside the window
    assert red.busy_s == pytest.approx(400e-9)
    assert red.chips == 1
    assert red.op_s == pytest.approx({"fusion.1": 200e-9,
                                      "custom-call.2": 250e-9})
    assert red.module_s == pytest.approx({"jit_a": 200e-9, "jit_b": 250e-9})
    # gaps [0,350] in build (midpoint 175), [700,950] in simulate
    assert red.gaps == [("build", pytest.approx(350e-9)),
                        ("simulate", pytest.approx(250e-9))]


@pytest.mark.parametrize("raw,name", [
    ("%fleet_fused_steps.1 = (f32[128,1,128]{2,1,0:T(1,128)}) "
     "custom-call(s32[1]{0:T(128)} %bitcast.45), "
     "custom_call_target=\"tpu_custom_call\"", "fleet_fused_steps.1"),
    ("%while.12 = (s32[]{:T(128)}) while(%tuple.546), "
     "condition=%region_48, body=%region_0", "while.12"),
    ("fusion.3", "fusion.3")])
def test_op_name_is_the_hlo_instruction(raw, name):
    """A TPU trace names an operation by its whole HLO text."""
    assert trace.op_name(raw) == name


def test_ops_without_a_module_stat_take_the_module_that_holds_them():
    evs = [ev(DEV, "XLA Modules", "jit__scan_steps(3)", 100, 500),
           ev(DEV, "XLA Modules", "jit_other(4)", 600, 700),
           ev(DEV, "XLA Ops", "while.1", 110, 480),
           ev(DEV, "XLA Ops", "fusion.2", 610, 650),
           ev(DEV, "XLA Ops", "copy.3", 520, 530),
           ev(DEV, "XLA Ops", "fusion.4", 620, 640, "jit_named")]
    got = {e.name: e.module for e in trace._modules_by_span(evs)
           if e.line == trace.OPS_LINE}
    assert got == {"while.1": "jit__scan_steps(3)", "fusion.2": "jit_other(4)",
                   "copy.3": "", "fusion.4": "jit_named"}


def test_gap_outside_every_span_is_between_calls():
    evs = [ev(HOST, "python", "bench:window", 0, 100),
           ev(HOST, "python", "bench:build", 60, 100),
           ev(DEV, "XLA Ops", "f", 40, 60)]
    assert trace.reduce(evs).gaps[0] == ("between_calls", pytest.approx(40e-9))


def test_breakdown_is_capped_and_sorted():
    evs = [ev(HOST, "python", "bench:window", 0, 10_000)] + [
        ev(DEV, "XLA Ops", f"op{i}", 100 * i, 100 * i + i + 1)
        for i in range(20)]
    out = trace.breakdown(trace.reduce(evs))
    assert [n for n, _ in out["device_ops"]] == [f"op{i}"
                                                 for i in range(19, 9, -1)]
    assert len(out["idle_gaps"]) == 10
    assert out["idle_gaps"][0][1] >= out["idle_gaps"][-1][1]


def test_reduce_refuses_a_trace_without_device_ops():
    with pytest.raises(ValueError):
        trace.reduce([ev(HOST, "python", "bench:window", 0, 10)])


def test_load_reads_the_harness_spans_of_a_recorded_trace(tmp_path):
    """A trace recorded here (on the CPU, which has no TPU plane): the
    loader finds the ``bench:`` annotations, nested as they were made."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench:window"):
        with jax.profiler.TraceAnnotation("bench:build"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    spans = {e.name: e for e in trace.load(str(tmp_path))
             if e.name.startswith("bench:")}
    assert set(spans) == {"bench:window", "bench:build"}
    w, b = spans["bench:window"], spans["bench:build"]
    assert w.start_ns <= b.start_ns < b.end_ns <= w.end_ns


def test_saved_events_read_back(tmp_path):
    evs = sample_events()
    path = str(tmp_path / "events.jsonl.gz")
    trace.save(evs, path)
    assert trace.read_saved(path) == evs


@pytest.mark.parametrize("reader,table", [
    ("fleet_kernel_us_per_step", "op_s"),
    ("serve_scan_us_per_step", "module_s")])
def test_trace_reader_raises_where_its_events_are_missing(reader, table):
    """In a traced run a device-time reader that matches no event raises,
    so a renamed kernel or executable cannot drop its metric unnoticed;
    without a trace it reports nothing."""
    from types import SimpleNamespace

    import run

    mod = run.load_module(run.BENCH / "metrics" / f"{reader}.py", reader)
    red = trace.reduce(sample_events())
    ctx = SimpleNamespace(trace=red, calls=2, extra={"steps_per_call": 10})
    with pytest.raises(LookupError):
        mod.read(ctx)
    key = ({"fleet_kernel_us_per_step": "fleet_fused_steps.1",
            "serve_scan_us_per_step": "jit__unknown(16632723599032985019)"}
           [reader])
    times = dict(getattr(red, table), **{key: 4e-6})
    ctx.trace = red._replace(**{table: times})
    assert mod.read(ctx) == pytest.approx(1e6 * 4e-6 / 20)
    assert mod.read(SimpleNamespace(trace=None)) is None


DATA = __import__("pathlib").Path(__file__).parent / "data"


def _recorded_ctx(name, steps_per_call):
    """A traced run's context around a trace recorded on a TPU v5e by
    ``run.py --trace 1 --keep-trace`` (one call in the window)."""
    from types import SimpleNamespace

    red = trace.reduce(trace.read_saved(str(DATA / name)))
    return SimpleNamespace(trace=red, calls=1,
                           extra={"steps_per_call": steps_per_call})


def test_recorded_sweep_trace_reduces_to_its_kernel_and_build_gap():
    """``sim.multitask``: the fused fleet kernel is named by its HLO
    instruction, and the device waits through the host build."""
    import run

    ctx = _recorded_ctx("sim_multitask_v5e.jsonl.gz", 1600)
    red = ctx.trace
    assert red.chips == 1 and 0 < red.busy_s < red.window_s
    top = trace.breakdown(red)
    assert top["device_ops"][0][0] == "fleet_fused_steps.1"
    assert top["idle_gaps"][0][0] == "build"
    mod = run.load_module(run.BENCH / "metrics" / "fleet_kernel_us_per_step.py",
                          "fleet_kernel_us_per_step")
    us = mod.read(ctx)
    assert 0 < us * 1600 * 1e-6 <= red.busy_s


def test_recorded_serve_trace_times_the_unnamed_scan_executable():
    """``serve.adapt`` (operations under 2 ms dropped to keep the file
    small): the scan's executable is ``jit__unknown``, and its time is the
    union of its nested operations, within the window."""
    import run

    ctx = _recorded_ctx("serve_adapt_v5e_ops_over_2ms.jsonl.gz", 545)
    red = ctx.trace
    scan = [v for k, v in red.module_s.items()
            if k.startswith("jit__unknown(")]
    assert len(scan) == 1 and 0 < scan[0] <= red.busy_s <= red.window_s
    assert sum(v for k, v in red.op_s.items()) > red.window_s  # nested ops
    mod = run.load_module(run.BENCH / "metrics" / "serve_scan_us_per_step.py",
                          "serve_scan_us_per_step")
    assert mod.read(ctx) == pytest.approx(1e6 * scan[0] / 545)
