"""Loop-aware HLO cost model vs closed-form counts (single CPU device)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.hlo_cost import HloCostModel, analyze_hlo
from repro.launch.hlo_stats import DRYRUN_TARGET, peaks, roofline_terms


def compile_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def test_single_matmul_flops_exact():
    M, K, N = 64, 128, 32

    def f(x, w):
        return x @ w

    txt = compile_text(
        f,
        jax.ShapeDtypeStruct((M, K), jnp.float32),
        jax.ShapeDtypeStruct((K, N), jnp.float32),
    )
    cost = HloCostModel(txt).entry_cost()
    assert cost.dot_flops == pytest.approx(2 * M * K * N, rel=1e-6)


def test_scan_multiplies_body_flops():
    """The whole point of the loop-aware model: a scanned matmul counts
    trip_count x body FLOPs (XLA's own cost_analysis counts it once)."""
    M, K, T = 32, 64, 10

    def f(x, w):
        def body(x, _):
            return jnp.tanh(x @ w), None

        y, _ = jax.lax.scan(body, x, None, length=T)
        return y

    txt = compile_text(
        f,
        jax.ShapeDtypeStruct((M, K), jnp.float32),
        jax.ShapeDtypeStruct((K, K), jnp.float32),
    )
    cost = HloCostModel(txt).entry_cost()
    want = 2 * M * K * K * T
    assert cost.dot_flops == pytest.approx(want, rel=1e-6)
    # elementwise tanh adds < 5% on top of the dots here
    assert cost.flops < want * 1.1


def test_nested_scan_trip_product():
    def f(x, w):
        def inner(x, _):
            return x @ w, None

        def outer(x, _):
            y, _ = jax.lax.scan(inner, x, None, length=3)
            return y, None

        y, _ = jax.lax.scan(outer, x, None, length=5)
        return y

    txt = compile_text(
        f,
        jax.ShapeDtypeStruct((8, 16), jnp.float32),
        jax.ShapeDtypeStruct((16, 16), jnp.float32),
    )
    cost = HloCostModel(txt).entry_cost()
    assert cost.dot_flops == pytest.approx(2 * 8 * 16 * 16 * 15, rel=1e-6)


def test_batched_dot_general():
    B, M, K, N = 4, 16, 32, 8

    def f(x, w):
        return jnp.einsum("bmk,bkn->bmn", x, w)

    txt = compile_text(
        f,
        jax.ShapeDtypeStruct((B, M, K), jnp.float32),
        jax.ShapeDtypeStruct((B, K, N), jnp.float32),
    )
    cost = HloCostModel(txt).entry_cost()
    assert cost.dot_flops == pytest.approx(2 * B * M * K * N, rel=1e-6)


def test_bytes_scale_with_scan_trips():
    def mk(T):
        def f(x, w):
            def body(x, _):
                return jnp.tanh(x @ w), None

            y, _ = jax.lax.scan(body, x, None, length=T)
            return y
        return f

    sds = (jax.ShapeDtypeStruct((64, 64), jnp.float32),) * 2
    b1 = HloCostModel(compile_text(mk(2), *sds)).entry_cost().bytes
    b2 = HloCostModel(compile_text(mk(20), *sds)).entry_cost().bytes
    assert b2 > 5 * b1


def test_elementwise_flops_counted():
    def f(x):
        return jnp.tanh(x) * 2.0 + 1.0

    txt = compile_text(f, jax.ShapeDtypeStruct((128, 128), jnp.float32))
    cost = HloCostModel(txt).entry_cost()
    assert cost.dot_flops == 0
    assert cost.flops >= 128 * 128  # at least one pass over the data


def test_analyze_hlo_dict_keys():
    txt = compile_text(
        lambda x: x + 1.0, jax.ShapeDtypeStruct((4, 4), jnp.float32)
    )
    d = analyze_hlo(txt)
    for k in ("flops", "dot_flops", "bytes", "ici_bytes", "coll_counts"):
        assert k in d
    assert d["ici_bytes"] == 0.0  # single device: no collectives


def test_roofline_peaks_keyed_by_device_kind():
    """The roofline divides by the peaks of the device kind it is given;
    a kind with no published row raises instead of borrowing another's."""
    pk = peaks(DRYRUN_TARGET)
    terms = roofline_terms(flops=pk.flops, bytes_accessed=pk.hbm_bw / 2,
                           ici_bytes=0.0, device_kind=DRYRUN_TARGET)
    assert terms["compute_s"] == pytest.approx(1.0)
    assert terms["memory_s"] == pytest.approx(0.5)
    with pytest.raises(ValueError, match="no published peaks"):
        roofline_terms(flops=1.0, bytes_accessed=1.0, ici_bytes=0.0,
                       device_kind="cpu")
