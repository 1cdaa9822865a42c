"""AOT lowering of train / prefill / decode steps onto a mesh.

Shared by the multi-pod dry-run (``repro.launch.dryrun``), the roofline
benchmark, and the mesh-lowering tests (which use tiny meshes on CPU).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Optional

import jax
from jax.sharding import Mesh

from repro.configs import INPUT_SHAPES, ModelConfig
from repro.models import transformer as tfm
from repro.models.common import logical_axis_rules
from repro.train import trainer
from repro.train.optimizer import adamw_init

from . import sharding as shd
from .hlo_cost import HloCostModel
from .hlo_stats import (
    DRYRUN_TARGET,
    cost_analysis_dict,
    memory_analysis_dict,
    model_flops,
    roofline_terms,
)
from .inputs import LoweringSpec, input_specs
from .mesh import logical_rules


@dataclass
class LoweringResult:
    lowered: Any
    compiled: Any
    spec: LoweringSpec
    mesh: Mesh


def _params_shapes(cfg: ModelConfig):
    return jax.eval_shape(
        functools.partial(tfm.init_params, cfg), jax.random.key(0)
    )


def lower_step(
    cfg: ModelConfig,
    shape_name: str,
    mesh: Mesh,
    *,
    compile: bool = True,
    donate: bool = True,
) -> LoweringResult:
    """Lower (and optionally compile) the step the input shape dictates."""
    spec = input_specs(cfg, INPUT_SHAPES[shape_name])
    rules = logical_rules(mesh)
    named = functools.partial(shd.named, mesh)

    with mesh, logical_axis_rules(mesh, rules):
        params_s = _params_shapes(cfg)
        psp = shd.param_specs(mesh, params_s)

        if spec.step_kind == "train":
            (batch_s,) = spec.args
            opt_s = jax.eval_shape(adamw_init, params_s)
            osp = shd.param_specs(mesh, opt_s)
            bsp = shd.batch_specs(mesh, batch_s)
            step = trainer.make_train_step(cfg, window=spec.window)
            jitted = jax.jit(
                step,
                in_shardings=(named(psp), named(osp), named(bsp)),
                out_shardings=(named(psp), named(osp), None),
                donate_argnums=(0, 1) if donate else (),
            )
            lowered = jitted.lower(params_s, opt_s, batch_s)

        elif spec.step_kind == "prefill":
            (batch_s,) = spec.args
            bsp = shd.batch_specs(mesh, batch_s)

            def pf(params, batch):
                return tfm.prefill(cfg, params, batch, window=spec.window)

            logits_s, state_s = jax.eval_shape(pf, params_s, batch_s)
            lsp = shd.logits_spec(mesh, *logits_s.shape, ndim=2)
            ssp = shd.state_specs(mesh, state_s)
            jitted = jax.jit(
                pf,
                in_shardings=(named(psp), named(bsp)),
                out_shardings=(named(lsp), named(ssp)),
            )
            lowered = jitted.lower(params_s, batch_s)

        else:  # decode
            state_s, token_s = spec.args
            ssp = shd.state_specs(mesh, state_s)
            tsp = shd.batch_specs(mesh, token_s)

            def ds(params, state, token):
                return tfm.decode_step(
                    cfg, params, state, token, window=spec.window,
                    unroll=True,
                )

            logits_s, _ = jax.eval_shape(ds, params_s, state_s, token_s)
            lsp = shd.logits_spec(mesh, *logits_s.shape, ndim=2)
            jitted = jax.jit(
                ds,
                in_shardings=(named(psp), named(ssp), named(tsp)),
                out_shardings=(named(lsp), named(ssp)),
                donate_argnums=(1,) if donate else (),
            )
            lowered = jitted.lower(params_s, state_s, token_s)

        compiled = lowered.compile() if compile else None
    return LoweringResult(lowered, compiled, spec, mesh)


def analyze(result: LoweringResult) -> dict:
    """Dry-run record: memory/cost analysis + collective + roofline terms.

    FLOPs / bytes / collective traffic come from the loop-aware HLO cost
    model (:mod:`repro.launch.hlo_cost`) — XLA's ``cost_analysis()`` counts
    scan bodies once and is reported alongside for reference only.
    """
    compiled = result.compiled
    spec = result.spec
    n_dev = result.mesh.size
    mem = memory_analysis_dict(compiled)
    xla_cost = cost_analysis_dict(compiled)
    cost = HloCostModel(compiled.as_text(), n_dev).entry_cost()
    terms = roofline_terms(
        flops=cost.flops, bytes_accessed=cost.bytes,
        ici_bytes=cost.ici_bytes, device_kind=DRYRUN_TARGET,
    )
    mflops = model_flops(
        spec.cfg, spec.step_kind, spec.shape.global_batch, spec.shape.seq_len
    )
    mflops_dev = mflops / n_dev
    return {
        "arch": spec.cfg.name,
        "shape": spec.shape.name,
        "step_kind": spec.step_kind,
        "window": spec.window,
        "mesh": list(result.mesh.devices.shape),
        "mesh_axes": list(result.mesh.axis_names),
        "n_devices": n_dev,
        "memory": mem,
        "hlo_flops_per_device": cost.flops,
        "hlo_dot_flops_per_device": cost.dot_flops,
        "hlo_bytes_per_device": cost.bytes,
        "collectives": {
            "ici_bytes": cost.ici_bytes,
            "counts": cost.coll_counts,
            "by_kind_bytes": cost.coll_bytes,
        },
        "xla_cost_analysis": {
            k: xla_cost[k] for k in ("flops", "bytes accessed")
            if k in xla_cost
        },
        "roofline": terms,
        "model_flops_total": mflops,
        "model_flops_per_device": mflops_dev,
        "useful_flops_ratio": (
            (mflops_dev / cost.flops) if cost.flops else 0.0
        ),
        "params_total": spec.cfg.param_count(),
        "params_active": spec.cfg.active_param_count(),
    }
