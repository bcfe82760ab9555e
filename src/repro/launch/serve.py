"""Production serving launcher: mesh-placed params + serving engine.

Continuous batching by default (compiled bucketed prefill + slot
scheduler); ``--engine fixed`` falls back to the fixed-batch loop (also
the automatic fallback for model kinds without one-pass prefill:
recurrent, encoder-decoder, VLM).

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b --smoke \
        --prompts "1,2,3;4,5" --max-new 16

``--coded K,R`` makes the run straggler-tolerant: the decode-path state
is LCC-encoded to N = K + R simulated hosts every chunk
(``serve.coded.CodedServeGuard``) and ``--kill TICK:HOST`` (repeatable)
injects host faults mid-trace — in-flight requests are recovered from
any K surviving shards, not dropped:

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b --smoke \
        --prompts "1,2,3;4,5" --coded 3,2 --kill 2:0 --kill 6:4
"""

from __future__ import annotations

import argparse
import time

import jax

from repro.configs import get, smoke_config
from repro.configs.base import ShapeSpec
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.launch.profiles import BASELINE, rules_for
from repro.models import build_model
from repro.serve import (
    CodedServeGuard,
    ContinuousEngine,
    Engine,
    FaultInjector,
    Request,
)
from repro.train import latest_step, param_shardings, restore_checkpoint


def build_serving(cfg, mesh, max_len: int, *, ckpt=None):
    """``(model, params, rules)`` for serving ``cfg`` on ``mesh``: sharding
    rules for a ``max_len`` decode, params initialised from key 0 straight
    into their shardings, then restored from ``ckpt`` when it holds a step."""
    rules = rules_for(cfg, ShapeSpec("cli", "decode", max_len, 1), BASELINE)
    model = build_model(cfg)
    ps = param_shardings(model, mesh, rules)
    params = jax.jit(model.init, out_shardings=ps)(jax.random.key(0))
    if ckpt and latest_step(ckpt) is not None:
        like = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
        params, _ = restore_checkpoint(ckpt, like, shardings=ps)
    return model, params, rules


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--mesh", default="1x1")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--prompts", default="1,2,3;7,8")
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--engine", choices=["continuous", "fixed"], default="continuous")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument(
        "--coded", default=None, metavar="K,R",
        help="LCC-protect the decode state: K data + R parity shards "
        "over N=K+R simulated hosts (continuous engine only)",
    )
    ap.add_argument(
        "--kill", action="append", default=[], metavar="TICK:HOST",
        help="inject a host fault after decode tick TICK (repeatable; "
        "needs --coded)",
    )
    args = ap.parse_args()
    if args.kill and args.coded is None:
        ap.error("--kill requires --coded K,R")

    enable_compile_cache()
    cfg = smoke_config(args.arch) if args.smoke else get(args.arch)
    d, m = (int(x) for x in args.mesh.split("x"))
    mesh = make_mesh((d, m), ("data", "model"))
    model, params, rules = build_serving(cfg, mesh, args.max_len, ckpt=args.ckpt)

    prompts = [[int(t) for t in p.split(",") if t] for p in args.prompts.split(";")]
    use_continuous = args.engine == "continuous" and model.supports_prefill
    if args.engine == "continuous" and not use_continuous:
        print(f"{cfg.name}: no one-pass prefill; falling back to fixed-batch")

    if args.coded is not None and not use_continuous:
        raise SystemExit("--coded needs the continuous engine")

    if use_continuous:
        guard = None
        if args.coded is not None:
            K, R = (int(x) for x in args.coded.split(","))
            kills = tuple(
                tuple(int(x) for x in k.split(":")) for k in args.kill
            )
            guard = CodedServeGuard(
                K=K, R=R,
                injector=FaultInjector(kills=kills) if kills else None,
            )
        eng = ContinuousEngine(
            model, params, n_slots=args.slots, max_len=args.max_len,
            max_new_tokens=args.max_new, mesh=mesh, rules=rules,
        )
        reqs = [
            Request(id=f"cli-{i}", prompt=p, max_new_tokens=args.max_new)
            for i, p in enumerate(prompts)
        ]
        rep = eng.serve(reqs, guard=guard)
        print(
            f"{rep.decode_steps} decode steps, {len(rep.results)} reqs, "
            f"{rep.tokens_per_s:.1f} tok/s, ttft p99 {rep.ttft_ms['p99']:.1f} ms, "
            f"{rep.prefill_compiles} prefill graphs"
        )
        if rep.coded is not None:
            c = rep.coded
            print(
                f"coded K={c['K']} R={c['R']}: {c['injected_faults']} faults "
                f"injected, {c['recoveries']} hosts recovered from, "
                f"{c['requests_recovered']} in-flight requests recovered, "
                f"recovery p99 {c['recovery_us']['p99']:.0f} us"
            )
        for r in rep.results:
            print(f"{r.id}: {r.tokens}")
    else:
        eng = Engine(model, params, max_len=args.max_len, mesh=mesh, rules=rules)
        t0 = time.time()
        res = eng.generate(prompts, max_new_tokens=args.max_new)
        dt = time.time() - t0
        print(f"{res.steps} decode steps, {len(prompts)} seqs, {dt:.2f}s")
        for i, row in enumerate(res.tokens):
            print(f"seq {i}: {row[: res.lengths[i]].tolist()}")


if __name__ == "__main__":
    main()
