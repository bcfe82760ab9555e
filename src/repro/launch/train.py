"""Production training launcher.

Assembles mesh → sharding rules/profile → model → train_step → data pipeline
→ checkpoint/coded-parity cadence, and runs. On a real TPU slice the mesh
comes from jax.devices(); in this container pass ``--devices N`` smoke sizes
or use examples/train_lm.py for the single-host path.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b \
        --mesh 4x2 --batch 8 --seq 256 --steps 20 --smoke
"""

from __future__ import annotations

import argparse
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs import get, smoke_config
from repro.dist.sharding import named_sharding
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.launch.profiles import BASELINE, OPT, rules_for
from repro.configs.base import ShapeSpec
from repro.models import build_model, batch_dims
from repro.train import (
    CodedStateGuard,
    OptConfig,
    SyntheticLM,
    init_state,
    latest_step,
    make_train_step,
    param_shardings,
    restore_checkpoint,
    save_checkpoint,
)
from repro.train.train_loop import _tree_shard, opt_state_shardings


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--mesh", default="1x1", help="DATAxMODEL, e.g. 16x16")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--smoke", action="store_true", help="reduced config")
    ap.add_argument("--profile", default="opt", choices=["baseline", "opt"])
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--coded-every", type=int, default=25)
    ap.add_argument("--coded-k", type=int, default=8)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = smoke_config(args.arch) if args.smoke else get(args.arch)
    d, m = (int(x) for x in args.mesh.split("x"))
    if d * m > len(jax.devices()):
        raise SystemExit(
            f"mesh {args.mesh} needs {d * m} devices, have {len(jax.devices())}"
        )
    mesh = make_mesh((d, m), ("data", "model"))
    shape = ShapeSpec("cli", "train", args.seq, args.batch)
    profile = OPT if args.profile == "opt" else BASELINE
    rules = rules_for(cfg, shape, profile)
    model = build_model(cfg)

    ocfg = OptConfig(lr=args.lr, warmup_steps=max(args.steps // 10, 1), total_steps=args.steps)
    ps = param_shardings(model, mesh, rules)
    params = jax.jit(model.init, out_shardings=ps)(jax.random.key(0))
    opt_state = init_state(ocfg, params)
    start = 0
    if args.ckpt and latest_step(args.ckpt) is not None:
        like = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
            {"params": params, "opt": opt_state},
        )
        state, start = restore_checkpoint(args.ckpt, like)
        params, opt_state = state["params"], state["opt"]
        print(f"restored checkpoint at step {start}")

    step_fn = jax.jit(
        make_train_step(model, ocfg, mesh=mesh, rules=rules),
        in_shardings=(ps, opt_state_shardings(ocfg, model, mesh, rules), None),
        out_shardings=(ps, opt_state_shardings(ocfg, model, mesh, rules), None),
    )
    ds = SyntheticLM(cfg)
    guard = CodedStateGuard(K=args.coded_k)
    t0 = time.time()
    for s in range(start, args.steps):
        batch = {k: jnp.asarray(v) for k, v in ds.batch(s, args.batch, args.seq).items()}
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        if s % 10 == 0 or s == args.steps - 1:
            print(
                f"step {s:5d} loss {float(metrics['loss']):.4f} "
                f"gnorm {float(metrics['grad_norm']):.3f}"
            )
        if args.coded_every and s and s % args.coded_every == 0:
            guard.snapshot({"params": params, "opt": opt_state}, s)
        if args.ckpt and s and s % args.ckpt_every == 0:
            save_checkpoint(args.ckpt, {"params": params, "opt": opt_state}, s)
    if args.ckpt:
        save_checkpoint(args.ckpt, {"params": params, "opt": opt_state}, args.steps)
    dt = time.time() - t0
    print(f"done: {args.steps - start} steps in {dt:.1f}s")


if __name__ == "__main__":
    main()
