"""Production training driver.

Runs the LM ``train_step`` for an assigned architecture on whatever devices
exist: the production meshes on TPU pods, the 1-device host mesh on CPU
(``--reduced`` for the smoke-scale variant).  Parameters are initialised
*sharded* (jit with out_shardings so no host copy of a 100B+ model is ever
materialised), data comes from the deterministic synthetic LM stream, and
checkpoints are written every ``--ckpt-every`` steps.

    PYTHONPATH=src python -m repro.launch.train --arch xlstm-125m \
        --reduced --steps 50 --batch 8 --seq 128
"""
from __future__ import annotations

import argparse
import functools
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.data import make_lm_tokens
from repro.models import transformer as tfm
from repro.models.common import logical_axis_rules
from repro.train import make_train_step, save_checkpoint
from repro.train.optimizer import adamw_init

from . import sharding as shd
from .compile_cache import enable_compile_cache
from .mesh import logical_rules, make_host_mesh, make_production_mesh


def build_mesh(kind: str):
    if kind == "host":
        return make_host_mesh()
    return make_production_mesh(multi_pod=(kind == "multi-pod"))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mesh", choices=("host", "single-pod", "multi-pod"),
                    default="host")
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--ckpt-path", default="experiments/ckpt/train")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    mesh = build_mesh(args.mesh)
    rules = logical_rules(mesh)
    named = functools.partial(shd.named, mesh)

    with mesh, logical_axis_rules(mesh, rules):
        params_shapes = jax.eval_shape(
            functools.partial(tfm.init_params, cfg), jax.random.key(args.seed)
        )
        psp = shd.param_specs(mesh, params_shapes)
        osp = shd.param_specs(
            mesh, jax.eval_shape(adamw_init, params_shapes)
        )
        init = jax.jit(
            functools.partial(tfm.init_params, cfg),
            out_shardings=named(psp),
        )
        params = init(jax.random.key(args.seed))
        opt = jax.jit(adamw_init, out_shardings=named(osp))(params)
        n_params = sum(x.size for x in jax.tree.leaves(params))
        print(f"arch={cfg.name} params={n_params / 1e6:.1f}M "
              f"mesh={dict(mesh.shape)}")

        step_fn = jax.jit(
            make_train_step(cfg, lr=args.lr),
            in_shardings=(named(psp), named(osp), None),
            out_shardings=(named(psp), named(osp), None),
            donate_argnums=(0, 1),
        )

        tokens = make_lm_tokens(
            cfg.vocab, args.seq, args.batch * args.steps, seed=args.seed
        )
        frontend = None
        if cfg.is_encoder_decoder or cfg.n_frontend_tokens:
            nf = (cfg.n_enc_tokens if cfg.is_encoder_decoder
                  else cfg.n_frontend_tokens)
            frontend = np.random.default_rng(args.seed).normal(
                size=(args.batch, nf, cfg.d_model)
            ).astype(np.float32)

        t0 = time.time()
        for step in range(args.steps):
            lo = step * args.batch
            batch = {"tokens": jnp.asarray(tokens[lo:lo + args.batch])}
            if frontend is not None:
                batch["frontend"] = jnp.asarray(frontend)
            params, opt, metrics = step_fn(params, opt, batch)
            if step % args.log_every == 0 or step == args.steps - 1:
                loss = float(metrics["loss"])
                dt = time.time() - t0
                tok_s = args.batch * args.seq * (step + 1) / max(dt, 1e-9)
                print(f"step {step:5d}  loss {loss:7.4f}  "
                      f"aux {float(metrics['aux']):.4f}  "
                      f"tokens/s {tok_s:,.0f}")
            if args.ckpt_every and (step + 1) % args.ckpt_every == 0:
                save_checkpoint(f"{args.ckpt_path}_{step + 1}.npz", params)
                print(f"checkpoint -> {args.ckpt_path}_{step + 1}.npz")
        print(f"done: {args.steps} steps in {time.time() - t0:.1f}s")


if __name__ == "__main__":
    main()
