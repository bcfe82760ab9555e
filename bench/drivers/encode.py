"""Encode driver: the coded-state LCC encode as a deployment of its own.

Each of the N chips holds its packet, already sharded over the mesh axis
(rows K..N-1 are the zero padding, as ``lcc_pad`` leaves them). One caller
runs the program's mesh encode (``lcc_encode_collective`` →
``ps_encode_jit`` → ``ir_encode_jit``, kernels resolved from the mesh) back
to back, each call ending in ``block_until_ready``, for ``--seconds``.

The packet is one shard of the coded serving pool's decode state as
``shard_state_limbs`` cuts it, sized from the model's shapes; its words are
16-bit limbs drawn on the device from the seed.

The check: the outputs of calls drawn from the seed (reservoir sampling over
the window's calls) and of the last call, against the reference encode.
"""

from __future__ import annotations

import time

import numpy as np

from bench import counts, weights as weight_gen
from bench.harness import BENCH, Check, HarnessError, Outcome, load_json, reference_module, say


def packet_words(config: dict) -> int:
    pay = config["payload"]
    model = load_json(BENCH / "configs" / f"{pay['state_of']}.json")
    total = counts.pool_state_limbs(model, pay["slots"], pay["max_len"], pay["max_new_tokens"])
    return counts.shard_words(total, int(config["K"]))


def run(ctx) -> Outcome:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.coded.lagrange_compute import build_lcc, lcc_encode_collective
    from repro.launch.mesh import make_mesh

    from bench.drivers.serve import check_code

    config, seed = ctx.config, ctx.seed
    K, R, N = int(config["K"]), int(config["R"]), int(config["N"])
    S = int(config.get("packet_words") or packet_words(config))
    if len(jax.devices()) < N:
        raise HarnessError(f"the encode needs {N} devices, found {len(jax.devices())}")
    plan = build_lcc(K, p=int(config["p"]), q=int(config["q"]), R=R)
    check_code(config, plan)
    mesh = make_mesh((N,), ("enc",))
    rows = NamedSharding(mesh, P("enc"))
    fn = lcc_encode_collective(mesh, "enc", plan)

    limb = 1 << int(config["payload"]["limb_bits"])

    def make_packets(key):
        x = jax.random.randint(key, (N, S), 0, limb, jnp.int32).astype(jnp.uint32)
        return jnp.where(jnp.arange(N)[:, None] < K, x, jnp.uint32(0))

    x = jax.jit(make_packets, out_shardings=rows)(weight_gen.key_for(seed))
    jax.block_until_ready(fn(x))  # compile, then one warm call
    jax.block_until_ready(fn(x))

    rng = np.random.default_rng([seed, 4])
    n_keep = int(ctx.traffic["check"]["sampled_calls"])
    kept: list = []  # (call index, output)
    calls = 0
    with ctx.window():
        t0 = time.perf_counter()
        while True:
            out = fn(x)
            out.block_until_ready()
            calls += 1
            if len(kept) < n_keep:
                kept.append((calls, out))
            else:
                j = int(rng.integers(0, calls))
                if j < n_keep:
                    kept[j] = (calls, out)
            elapsed = time.perf_counter() - t0
            if elapsed >= ctx.seconds:
                break
    last = out
    ctx.record_memory(jax.devices()[:N])
    packet_bytes = 4 * S
    e2e = {"encode_gbps": N * packet_bytes * calls / elapsed / 1e9}
    say(f"{calls} encodes of {N} x {S} words in {elapsed:.3f} s")

    t_check = time.perf_counter()
    ref = reference_module(config)
    xs = np.asarray(x)
    want = ref.encode(xs, config)
    checked = {i: o for i, o in kept}
    checked[calls] = last
    wrong = 0
    for i, o in checked.items():
        wrong += ref.words_wrong(np.asarray(o), want)
    say(f"checked calls {sorted(checked)} against the reference in "
        f"{time.perf_counter() - t_check:.1f} s")
    ps = counts.prepare_shoot_counts(N, int(config["p"]))
    say(f"plan: {ps['rounds']} comm rounds, {ps['ppermutes']} ppermutes, "
        f"{ps['local_ops']} LocalOp; {ps['wire_words_per_word'] * packet_bytes} bytes "
        "on the wire per device per call")
    facts = {"calls": calls, "calls_traced": calls if ctx.trace else 0,
             "packet_words": S, "devices": N, **ps}
    return Outcome(attempted=calls, failed=0, e2e=e2e,
                   checks={"encode_words_wrong": Check(float(wrong),
                                                       ctx.limit("encode_words_wrong"))},
                   facts=facts)
