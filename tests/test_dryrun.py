"""Mesh lowering / dry-run machinery.

Real mesh tests need >1 device, which requires setting XLA_FLAGS before jax
initialises — so they run in subprocesses with a small forced device count
(the full 512-device sweep is exercised by ``python -m repro.launch.dryrun``
and recorded in EXPERIMENTS.md).  Spec-inference tests run in-process.
"""
import json
import subprocess
import sys
import textwrap

import pytest

from _subproc import sub_env

SUB = """
import json
import jax

from repro.configs import get_config
from repro.launch.lowering import analyze, lower_step
from repro.launch.mesh import make_mesh

mesh = make_mesh((2, 4), ("data", "model"))
cfg = get_config("{arch}").reduced()
res = lower_step(cfg, "{shape}", mesh)
rec = analyze(res)
print("RESULT" + json.dumps({{
    "flops": rec["hlo_flops_per_device"],
    "bytes": rec["hlo_bytes_per_device"],
    "ici": rec["collectives"]["ici_bytes"],
    "dominant": rec["roofline"]["dominant"],
    "mem": rec["memory"]["temp_size_in_bytes"],
}}))
"""


def run_sub(arch, shape):
    code = SUB.format(arch=arch, shape=shape)
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=900, env=sub_env(host_devices=8),
    )
    assert out.returncode == 0, out.stderr[-3000:]
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULT")][-1]
    return json.loads(line[len("RESULT"):])


@pytest.mark.slow
@pytest.mark.parametrize("arch,shape", [
    ("qwen1.5-0.5b", "train_4k"),
    ("dbrx-132b", "train_4k"),          # MoE: expert sharding + all-to-all
    ("recurrentgemma-9b", "decode_32k"),  # hybrid decode state
    ("xlstm-125m", "long_500k"),        # native long-context decode
])
def test_lowering_compiles_on_8dev_mesh(arch, shape):
    rec = run_sub(arch, shape)
    assert rec["flops"] > 0
    assert rec["bytes"] > 0
    assert rec["dominant"] in ("compute", "memory", "collective")


@pytest.mark.slow
def test_long_500k_skip_is_honoured():
    code = SUB.format(arch="seamless-m4t-medium", shape="long_500k")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=900, env=sub_env(host_devices=8),
    )
    assert out.returncode != 0
    assert "ShapeSkip" in out.stderr or "skips long_500k" in out.stderr


# ----------------------------------------------------------------------- #
# Spec inference (no devices needed).
# ----------------------------------------------------------------------- #


def test_param_specs_divisible():
    """Every sharded dim must be divisible by its mesh axes (the contract
    sanitize_dim enforces) — checked over all architectures on an abstract
    16x16 mesh."""
    import jax
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from repro.configs import ASSIGNED_ARCHS, get_config
    from repro.launch.mesh import make_abstract_mesh
    from repro.launch.sharding import param_specs
    from repro.models import transformer as T

    mesh = make_abstract_mesh((16, 16), ("data", "model"))
    sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
    for arch in ASSIGNED_ARCHS:
        cfg = get_config(arch)
        shapes = jax.eval_shape(
            lambda cfg=cfg: T.init_params(cfg, jax.random.key(0))
        )
        specs = param_specs(mesh, shapes)
        leaves = jax.tree.leaves(shapes)
        spec_leaves = jax.tree.leaves(
            specs, is_leaf=lambda s: isinstance(s, P)
        )
        assert len(leaves) == len(spec_leaves)
        n_sharded = 0
        for leaf, spec in zip(leaves, spec_leaves):
            for dim, axes in zip(leaf.shape, spec):
                if axes is None:
                    continue
                n_sharded += 1
                axes = (axes,) if isinstance(axes, str) else axes
                total = int(np.prod([sizes[a] for a in axes]))
                assert dim % total == 0, (arch, leaf.shape, spec)
        assert n_sharded > 0  # the model is actually distributed


def test_state_specs_shard_cache():
    import jax

    from repro.configs import get_config
    from repro.launch.mesh import make_abstract_mesh
    from repro.launch.sharding import state_specs
    from repro.models import transformer as T

    mesh = make_abstract_mesh((16, 16), ("data", "model"))
    # glm4: kv=2 not divisible by 16 -> the cache LENGTH must shard
    cfg = get_config("glm4-9b")
    state = jax.eval_shape(lambda: T.init_decode_state(cfg, 128, 32768))
    specs = state_specs(mesh, state)
    k_spec = specs["stack"][0]["k"]
    assert "model" in str(k_spec)
    assert "data" in str(k_spec)
    # stablelm: kv=32 divisible -> heads shard, cache length replicated
    cfg2 = get_config("stablelm-3b")
    state2 = jax.eval_shape(lambda: T.init_decode_state(cfg2, 128, 32768))
    k2 = state2["stack"][0]["k"]
    spec2 = state_specs(mesh, state2)["stack"][0]["k"]
    # (n_scan, B, C, KV, hd): KV position carries the model axis
    assert spec2[3] == "model", spec2
    assert k2.shape[3] == 32


def test_batch_specs_batch_axis():
    import jax
    import jax.numpy as jnp

    from repro.launch.mesh import make_abstract_mesh
    from repro.launch.sharding import batch_specs

    mesh = make_abstract_mesh((2, 16, 16), ("pod", "data", "model"))
    batch = {"tokens": jax.ShapeDtypeStruct((256, 4096), jnp.int32)}
    spec = batch_specs(mesh, batch)["tokens"]
    assert spec[0] == ("pod", "data")
    # batch=1 (long_500k) falls back to replication
    one = {"tokens": jax.ShapeDtypeStruct((1, 8), jnp.int32)}
    assert batch_specs(mesh, one)["tokens"][0] is None
