"""The main-path Pallas kernels compile for a TPU v5e at their real sizes.

Nothing here runs on a chip: the TPU compiler installed beside JAX
compiles for a described ``v5e:2x2`` topology, so what Mosaic refuses (a
layout, a shape cast, the scoped VMEM limit) fails here, at no chip time.
The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro import fleet
from repro.core import energy
from repro.core.scheduler import JobProfile, SimConfig, TaskSpec
from repro.fleet.state import ServeBank, ServeCarry, ServeLog
from repro.kernels.centroid_update import centroid_update
from repro.kernels.fleet_step import fleet_fused_steps, serve_fused_steps
from repro.kernels.l1_topk2 import l1_topk2
from repro.serve.fleet_engine import ServeLookup

D = 1024
# the two agile-CNN tasks of examples/intermittent_serving.py: 5 units,
# 5 clusters per unit classifier, 150 selected features, 8192-wide unit
# features (+1 zero pad column), 25 requests per task
K, U, C, S, F, J = 2, 5, 5, 150, 8193, 25


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to a persistent cache but
    # cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _on(sharding, tree, rows=None):
    """ShapeDtypeStructs of ``tree`` on ``sharding``; ``rows`` replaces
    the leading (device) axis."""
    return jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(
            l.shape if rows is None else (rows,) + l.shape[1:], l.dtype,
            sharding=sharding), tree)


def _fleet(n_tasks, n_units, n_jobs, horizon):
    """A one-device config of ``n_tasks`` tasks, each ``n_units`` units and
    ``n_jobs`` jobs, and its t=0 carry."""
    prof = JobProfile(np.linspace(0.05, 0.5, n_units),
                      np.arange(n_units) >= 1, np.ones(n_units, bool))
    tasks = [TaskSpec(task_id=k, period=1.0, deadline=2.0,
                      unit_time=np.full(n_units, 0.22),
                      unit_energy=np.full(n_units, 7e-3),
                      profiles=[prof] * n_jobs) for k in range(n_tasks)]
    cfg, statics = fleet.from_sim_config(
        tasks, energy.Harvester("solar", 0.95, 0.95, 0.08), 0.71,
        sim=SimConfig(policy="zygarde", horizon=horizon))
    return cfg, statics, fleet.init_fleet(cfg, statics)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def test_fleet_fused_steps_compiles(one_chip):
    # the task of examples/fleet_sweep.py: 4 units, 40 jobs
    cfg, statics, carry = _fleet(1, 4, 40, 40.0)
    compiled = _compile(
        lambda p, c, i: fleet_fused_steps(p, c, i, statics=statics,
                                          n_steps=statics.n_steps),
        _on(one_chip, cfg, D), _on(one_chip, carry, D),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip))
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the kernel's own name, which a device trace finds it by
    assert "%fleet_fused_steps" in text


@pytest.mark.parametrize("per_device_bank", [True, False])
def test_serve_fused_steps_compiles(one_chip, per_device_bank):
    cfg, statics, dev = _fleet(K, U, J, J + 5.0)
    lead = (D,) if per_device_bank else ()
    log = ServeLog(units=np.zeros((D, K, J), np.int32),
                   pred=np.zeros((D, K, J), np.int32),
                   correct=np.zeros((D, K, J), bool),
                   margin=np.zeros((D, K, J), np.float32),
                   exit_unit=np.zeros((D, K, J), np.int32),
                   sched=np.zeros((D, K, J), bool))
    bank = ServeBank(
        centroids=jax.ShapeDtypeStruct(lead + (K, U, C, F), jnp.float32),
        counts=jax.ShapeDtypeStruct(lead + (K, U, C), jnp.float32))
    carry = ServeCarry(dev=_on(one_chip, dev, D), bank=_on(one_chip, bank),
                       log=_on(one_chip, log))
    look = ServeLookup(
        feat_rows=np.zeros((K * J * U, S), np.float32),
        cent_rows=np.zeros(lead + (K * U * C, S), np.float32),
        labels=np.zeros((K * J,), np.int32),
        clabels=np.zeros((K * U * C,), np.int32),
        thr=np.zeros((K * U,), np.float32))
    compiled = _compile(
        lambda p, c, lk, i, j: serve_fused_steps(
            p, c, lk, i, j, statics=statics, n_steps=statics.n_steps,
            shared_bank=not per_device_bank),
        _on(one_chip, cfg, D), carry, _on(one_chip, look),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((K,), jnp.int32, sharding=one_chip))
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "%serve_fused_steps" in text


@pytest.mark.parametrize("rows", [4096, 1000])
def test_l1_topk2_compiles(one_chip, rows):
    compiled = _compile(
        l1_topk2, jax.ShapeDtypeStruct((rows, 128), jnp.float32,
                                       sharding=one_chip),
        jax.ShapeDtypeStruct((16, 128), jnp.float32, sharding=one_chip))
    assert "tpu_custom_call" in compiled.as_text()


def test_centroid_update_compiles(one_chip):
    compiled = _compile(
        lambda c, x, a: centroid_update(c, x, a, 32.0),
        jax.ShapeDtypeStruct((16, 512), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((4096, 512), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((4096,), jnp.int32, sharding=one_chip))
    assert "tpu_custom_call" in compiled.as_text()
