import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Multi-pod dry-run (assignment deliverable e).

Lowers + compiles the step every (architecture x input-shape) pair dictates
— ``train_step`` for train_4k, ``prefill`` for prefill_32k, ``serve_step``
(one token against a seq_len KV cache) for decode_32k / long_500k — on the
production meshes:

    single-pod : 16 x 16           ("data", "model")        = 256 chips
    multi-pod  : 2 x 16 x 16       ("pod", "data", "model") = 512 chips

and records memory_analysis / cost_analysis / collective schedule and the
three roofline terms into a JSON record per combination (EXPERIMENTS.md
§Dry-run and §Roofline read these).

The two lines above MUST stay first: they install 512 placeholder host
devices before jax locks the device count.  Do not set XLA_FLAGS globally —
smoke tests and benchmarks must see the single real CPU device.

Usage:
    python -m repro.launch.dryrun --arch dbrx-132b --shape train_4k
    python -m repro.launch.dryrun --arch dbrx-132b --shape train_4k --multi-pod
    python -m repro.launch.dryrun --all --out-dir experiments/dryrun
"""
import argparse
import json
import subprocess
import sys
import time
import traceback
from pathlib import Path


def run_one(arch: str, shape: str, multi_pod: bool) -> dict:
    # imports deferred so --all subprocesses re-init jax themselves
    from repro.configs import get_config
    from repro.launch.inputs import ShapeSkip
    from repro.launch.lowering import analyze, lower_step
    from repro.launch.mesh import make_production_mesh

    cfg = get_config(arch)
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    try:
        result = lower_step(cfg, shape, mesh)
    except ShapeSkip as e:
        return {
            "arch": arch, "shape": shape,
            "mesh": list(mesh.devices.shape), "status": "skip",
            "reason": str(e),
        }
    record = analyze(result)
    record["status"] = "ok"
    record["compile_s"] = round(time.time() - t0, 1)
    return record


def combo_list():
    from repro.configs import ASSIGNED_ARCHS, INPUT_SHAPES

    return [(a, s) for a in ASSIGNED_ARCHS for s in INPUT_SHAPES]


def sweep(out_dir: Path, multi_pod: bool, jobs: int, archs=None,
          shapes=None) -> int:
    """Run every combination in subprocesses (isolation + parallelism)."""
    out_dir.mkdir(parents=True, exist_ok=True)
    combos = [
        (a, s) for a, s in combo_list()
        if (archs is None or a in archs) and (shapes is None or s in shapes)
    ]
    pending = list(combos)
    running: list[tuple] = []
    failures = 0
    while pending or running:
        while pending and len(running) < jobs:
            arch, shape = pending.pop(0)
            tag = f"{arch}__{shape}" + ("__multipod" if multi_pod else "")
            out = out_dir / f"{tag}.json"
            if out.exists():
                print(f"[skip-existing] {tag}")
                continue
            cmd = [
                sys.executable, "-m", "repro.launch.dryrun",
                "--arch", arch, "--shape", shape, "--out", str(out),
            ]
            if multi_pod:
                cmd.append("--multi-pod")
            # the children only lower on placeholder host meshes: keep them
            # off any accelerator, which one process at a time may hold
            proc = subprocess.Popen(
                cmd, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True, env={**os.environ, "JAX_PLATFORMS": "cpu"},
            )
            running.append((proc, tag, out, time.time()))
        done = [r for r in running if r[0].poll() is not None]
        for proc, tag, out, t0 in done:
            running.remove((proc, tag, out, t0))
            dt = time.time() - t0
            if proc.returncode == 0 and out.exists():
                rec = json.loads(out.read_text())
                r = rec.get("roofline", {})
                print(
                    f"[{rec['status']:>4}] {tag} ({dt:.0f}s) "
                    f"dom={r.get('dominant', '-')}"
                )
            else:
                failures += 1
                log = proc.stdout.read() if proc.stdout else ""
                (out_dir / f"{tag}.err").write_text(log)
                print(f"[FAIL] {tag} ({dt:.0f}s) -> {out_dir / (tag + '.err')}")
        time.sleep(1.0)
    return failures


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--archs", nargs="*", help="subset filter for --all")
    ap.add_argument("--shapes", nargs="*", help="subset filter for --all")
    ap.add_argument("--jobs", type=int, default=2)
    ap.add_argument("--out", help="JSON output path (single combo)")
    ap.add_argument("--out-dir", default="experiments/dryrun")
    args = ap.parse_args()

    if args.all:
        n_fail = sweep(
            Path(args.out_dir), args.multi_pod, args.jobs,
            archs=args.archs, shapes=args.shapes,
        )
        sys.exit(1 if n_fail else 0)

    if not args.arch or not args.shape:
        ap.error("--arch and --shape required (or --all)")
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    try:
        record = run_one(args.arch, args.shape, args.multi_pod)
    except Exception:
        traceback.print_exc()
        sys.exit(1)
    text = json.dumps(record, indent=2)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(text)
    print(text)


if __name__ == "__main__":
    main()
