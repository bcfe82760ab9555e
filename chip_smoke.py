#!/usr/bin/env python3
"""Bring-up check: the system's main path on a TPU, in one process.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # a four-chip host: the cross-chip path only

One chip runs four phases and stops at the first that fails:

1. device      — ``jax.devices()[0]`` must be a TPU; anything else exits 2.
2. kernels     — ``gf_matmul`` (K=16 Vandermonde over M31 on a 2^22-word
                 payload) and ``butterfly_mac`` (radix 2 and 3), compiled
                 (``interpret=False``), bit-exact against the host oracles;
                 each compiled program must hold a ``tpu_custom_call``.
3. serving     — qwen3-1.7b at its published widths, random weights from a
                 seed, built as ``launch/serve.py`` builds it, serves 16
                 seeded requests (prompts of 16–256 tokens, 32 greedy new
                 tokens) on 8 slots × ``max_len`` 4096. For two requests,
                 the last-position prefill logits must match a plain
                 ``model.forward`` of the same prompt, all 32 served tokens
                 must be greedy under ``model.forward``, and the cache rows
                 the engine wrote for them must match a one-pass
                 ``model.prefill_into_cache`` (see ``LOGIT_TOL``).
4. coded       — the same model on 4 slots × ``max_len`` 1024: the unguarded
                 run's tokens and cache rows checked as above, then
                 ``CodedServeGuard(K=3, R=1)`` with one host killed
                 mid-decode: tokens identical to the unguarded run.

``--four-chips`` runs only what exists across chips, and what it is compared
with: ``ps_encode_jit`` on a 4-wide mesh and ``hierarchical_encode_jit`` on
2×2 (kernels chosen from the mesh, so the compiled Pallas kernels), bit-exact
against ``encode_oracle``, permute-only HLO, outputs on all 4 devices; then
coded serving with the LCC encode over a 4-wide host axis, against the
unguarded run on one chip.

Times, compile counts and memory are printed as bring-up readings, not as
benchmark numbers. The last line of stdout, printed only when every phase
passed, is ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

import numpy as np

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")

ARCH = "qwen3-1.7b"
SERVE_SLOTS, SERVE_MAX_LEN = 8, 4096
CODED_SLOTS, CODED_MAX_LEN = 4, 1024
N_REQUESTS, N_CODED_REQUESTS, NEW_TOKENS = 16, 8, 32
PROMPT_LENS = (16, 256)  # inclusive, inside DEFAULT_BUCKETS
#: the coded run's prefill buckets: one graph, so the 4-chip call compiles once
CODED_BUCKETS = (256,)
CODED_K, CODED_R = 3, 1
KILL = (9, 1)  # (decode tick, host): detected at the chunk sync after tick 12
ENCODE_WORDS = 1 << 22  # u32 words: the whole kernel payload / each device's
SEED = 0
#: bf16 agreement with the reference passes: relative L2 error of prefill
#: logits and of each layer's cache rows, and largest logit error relative to
#: the largest logit — also how far a served token's reference logit may
#: trail the reference argmax. One bf16 rounding
#: is 2^-8 relative; the programs round in different orders through 28
#: layers, so a few ulps are allowed, and a near-tie may go either way. A
#: wrong row, position or cache gives errors of order 1.
LOGIT_TOL = {"rel_l2": 2.0**-5, "max_rel": 2.0**-4}
#: the reference forward's one sequence length: the longest prompt and its new
#: tokens, right-padded (attention is causal: padding changes no earlier logit)
REF_LEN = PROMPT_LENS[1] + NEW_TOKENS


class SmokeFailure(AssertionError):
    pass


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    say(f"  ok: {what}")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def kernels_phase() -> None:
    """gf_matmul and butterfly_mac, compiled, against the host oracles."""
    import jax.numpy as jnp

    from repro.core.field import M31, NTT, Field, shoup_precompute
    from repro.core.matrices import distinct_points, random_vector, vandermonde
    from repro.core.prepare_shoot import encode_oracle
    from repro.kernels.butterfly.ops import butterfly_mac, butterfly_mac_reference
    from repro.kernels.gf_matmul.ops import gf_matmul

    f, K, words = Field(M31), 16, ENCODE_WORDS
    G = vandermonde(f, distinct_points(f, K, seed=SEED))
    x = random_vector(f, (K, words // K), seed=SEED + 1)
    a = jnp.asarray(G.T.astype(np.uint32))  # out = x @ G  ⇔  G^T · x
    b = jnp.asarray(x.astype(np.uint32))
    _compiled_kernel(gf_matmul, (a, b), dict(q=M31),
                     f"gf_matmul {K}x{K} @ {K}x{words // K}")
    t0 = time.perf_counter()
    out = np.asarray(gf_matmul(a, b, q=M31), dtype=np.uint64)
    say(f"  gf_matmul run {time.perf_counter() - t0:.3f} s (with the copy back)")
    check(np.array_equal(out, encode_oracle(x, G, M31)),
          f"gf_matmul K={K} Vandermonde over M31, {words} words: "
          "bit-exact vs encode_oracle")

    rng = np.random.default_rng(SEED)
    B, P = 256, 8192
    for radix in (2, 3):
        parts = rng.integers(0, NTT, size=(radix, B, P), dtype=np.uint32)
        tw = rng.integers(0, NTT, size=(B, radix), dtype=np.uint32)
        args = (jnp.asarray(parts), jnp.asarray(tw),
                jnp.asarray(np.asarray(shoup_precompute(tw, NTT))))
        _compiled_kernel(butterfly_mac, args, dict(q=NTT),
                         f"butterfly_mac radix {radix} {B}x{P}")
        out = np.asarray(butterfly_mac(*args, q=NTT))
        ref = np.asarray(butterfly_mac_reference(*args, q=NTT))
        check(np.array_equal(out, ref),
              f"butterfly_mac radix {radix} {B}x{P}: bit-exact vs "
              "butterfly_mac_reference")


def _compiled_kernel(fn, args, kw, name):
    t0 = time.perf_counter()
    text = fn.lower(*args, **kw).compile().as_text()
    say(f"  {name}: compiled in {time.perf_counter() - t0:.2f} s")
    check("tpu_custom_call" in text, f"{name}: compiled HLO holds tpu_custom_call")


def make_requests(n: int, vocab: int):
    from repro.serve import Request

    rng = np.random.default_rng(SEED)
    lo, hi = PROMPT_LENS
    lens = rng.integers(lo, hi + 1, size=n)
    return [
        Request(id=f"smoke-{i}",
                prompt=rng.integers(0, vocab, size=int(L)).tolist(),
                max_new_tokens=NEW_TOKENS)
        for i, L in enumerate(lens)
    ]


def build(cfg):
    """``launch.serve.build_serving`` on a one-device mesh."""
    import jax

    from repro.launch.mesh import make_mesh
    from repro.launch.serve import build_serving

    mesh = make_mesh((1, 1), ("data", "model"))
    t0 = time.perf_counter()
    model, params, rules = build_serving(cfg, mesh, SERVE_MAX_LEN)
    jax.block_until_ready(params)
    n = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    say(f"  {cfg.name}: {n} params initialised in {time.perf_counter() - t0:.1f} s")
    say_peak_memory()
    return model, params, mesh, rules


def say_peak_memory() -> None:
    """Device 0's peak since the process started (bring-up reading)."""
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    say(f"  peak_bytes_in_use {stats.get('peak_bytes_in_use')} of "
        f"bytes_limit {stats.get('bytes_limit')} on device 0")


def serving_phase(cfg, model, params, mesh, rules) -> None:
    from repro.serve import ContinuousEngine

    reqs = make_requests(N_REQUESTS, cfg.vocab_size)
    eng = ContinuousEngine(model, params, n_slots=SERVE_SLOTS,
                           max_len=SERVE_MAX_LEN, max_new_tokens=NEW_TOKENS,
                           mesh=mesh, rules=rules)
    longest = sorted(reqs, key=lambda r: len(r.prompt))[-2:]
    probe = CacheProbe(longest)
    t0 = time.perf_counter()
    rep = eng.serve(reqs, greedy=True, guard=probe)
    say(f"  served {len(rep.results)} requests on {SERVE_SLOTS} slots x "
        f"{SERVE_MAX_LEN} in {time.perf_counter() - t0:.1f} s wall, compiles "
        f"included; {rep.decode_steps} decode ticks, {rep.prefill_compiles} "
        "prefill graphs")
    check(len(rep.results) == N_REQUESTS
          and all(r.gen_len == NEW_TOKENS for r in rep.results)
          and all(0 <= t < cfg.vocab_size for r in rep.results for t in r.tokens),
          f"{N_REQUESTS} requests x {NEW_TOKENS} tokens, all inside the vocabulary")
    say_peak_memory()
    watched = [r for r in rep.results if r.id in probe.ids]
    check_prefill_logits(model, params, mesh, rules, watched)
    check_greedy_tokens(model, params, watched)
    check_cache_rows(model, params, watched, probe)


def reference_logits(model, params, tokens) -> np.ndarray:
    """Float32 logits over the vocabulary of a plain ``model.forward`` at
    every position of ``tokens`` (one sequence, right-padded to REF_LEN)."""
    import jax.numpy as jnp

    seq = np.zeros((1, REF_LEN), np.int32)
    seq[0, :len(tokens)] = tokens
    lg = _forward(model)(params, jnp.asarray(seq))
    return np.asarray(lg[0, :len(tokens), :model.cfg.vocab_size], np.float32)


@functools.cache
def _forward(model):
    import jax

    return jax.jit(lambda p, t: model.forward(p, {"tokens": t})[0])


def check_prefill_logits(model, params, mesh, rules, results) -> None:
    """The serving prefill's last-position logits against a plain forward."""
    import jax
    import jax.numpy as jnp

    from repro.serve.scheduler import DEFAULT_BUCKETS, bucket_for
    from repro.train.train_loop import make_prefill_step

    V = model.cfg.vocab_size
    prefill = jax.jit(make_prefill_step(model, mesh, rules, into_cache=True))
    for r in results:
        plen = r.prompt_len
        bucket = bucket_for(plen, DEFAULT_BUCKETS)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :plen] = r.tokens[:plen]
        got, _ = prefill(params, model.init_cache(1, bucket), jnp.asarray(toks),
                         jnp.int32(0), jnp.int32(plen))
        got = np.asarray(got[0, :V], np.float64)
        ref = reference_logits(model, params, r.tokens[:plen])[-1].astype(np.float64)
        err = got - ref
        rel_l2 = float(np.linalg.norm(err) / np.linalg.norm(ref))
        max_rel = float(np.abs(err).max() / np.abs(ref).max())
        say(f"  {r.id} (prompt {plen}): rel L2 {rel_l2:.3e}, max |err| / max "
            f"|logit| {max_rel:.3e}, max |logit| {np.abs(ref).max():.3f}")
        check(rel_l2 <= LOGIT_TOL["rel_l2"] and max_rel <= LOGIT_TOL["max_rel"],
              f"{r.id}: prefill logits match model.forward within {LOGIT_TOL}")


def check_greedy_tokens(model, params, results) -> None:
    """The engine's served tokens against greedy decoding by ``model.forward``.

    The prompt and the served tokens, refed in one pass, give the reference
    logits before every served token. Each must be the reference argmax, or
    trail it by at most ``LOGIT_TOL["max_rel"]`` of the largest logit (a bf16
    near-tie: the decode tick rounds in another order than the full
    forward). A wrong cache row, slot or position serves tokens far below it.
    """
    tol = LOGIT_TOL["max_rel"]
    for r in results:
        plen, served = r.prompt_len, np.asarray(r.tokens[r.prompt_len:])
        lg = reference_logits(model, params, r.tokens)[plen - 1:-1]
        steps = np.arange(len(served))
        behind = (lg.max(axis=1) - lg[steps, served]) / np.abs(lg).max(axis=1)
        exact = int((lg.argmax(axis=1) == served).sum())
        say(f"  {r.id} (prompt {plen}): {exact} of {len(served)} served tokens "
            f"are the reference argmax; largest shortfall {behind.max():.3e} "
            "of max |logit|")
        check(len(served) == NEW_TOKENS and behind.max() <= tol,
              f"{r.id}: all {NEW_TOKENS} served tokens greedy under "
              f"model.forward (near-ties within {tol} of max |logit|)")


class CacheProbe:
    """A serve ``guard`` that only watches. At every decode-chunk start the
    engine hands it the live cache and state; for each slot serving one of
    the watched requests it keeps the slot's position and its decode-cache
    rows ``[0, REF_LEN)``, so the rows the engine's own prefill and decode
    programs wrote can be checked afterwards. A slot is told by its prompt
    length (``pos - gen_count + 1``), so the watched lengths must be unique."""

    def __init__(self, reqs):
        self.ids = {r.id for r in reqs}
        self._by_plen = {len(r.prompt): r.id for r in reqs}
        if len(self._by_plen) != len(reqs):
            raise ValueError("watched requests need distinct prompt lengths")
        self.rows = {}  # id -> (pos, body cache rows of its slot)

    def attach(self, metrics, tracer) -> None:
        pass

    def snapshot(self, cache, state, tick: int) -> None:
        import jax

        pos, count = np.asarray(state["pos"]), np.asarray(state["gen_count"])
        for s in np.flatnonzero(np.asarray(state["active"])):
            rid = self._by_plen.get(int(pos[s] - count[s] + 1))
            if rid is not None:
                self.rows[rid] = (int(pos[s]), jax.tree.map(
                    lambda c: np.asarray(c[:, s, :REF_LEN]), cache["body"]))

    def poll(self, now_tick: int) -> list:
        return []

    def stats(self) -> None:
        return None


def check_cache_rows(model, params, results, probe) -> None:
    """The decode-cache rows of a watched slot against the rows a one-pass
    ``model.prefill_into_cache`` of the prompt and served tokens writes.

    Rows below the prompt length came from the engine's prefill program,
    the rest from its decode ticks: per layer, each part's relative L2 error
    must stay within ``LOGIT_TOL["rel_l2"]``. Past the first layer a row
    depends on attention over every earlier row, so a wrong slot, position
    or cache read shows here even where the greedy tokens (random tied
    weights mostly repeat the last token) cannot show it."""
    import jax
    import jax.numpy as jnp

    tol = LOGIT_TOL["rel_l2"]
    ref_fn = jax.jit(lambda p, t: model.prefill_into_cache(
        p, model.init_cache(1, REF_LEN), t, 0)[1]["body"])
    for r in results:
        check(r.id in probe.rows, f"{r.id}: its slot's cache rows were recorded")
        pos, got = probe.rows[r.id]
        plen = r.prompt_len
        seq = np.zeros((1, REF_LEN), np.int32)
        seq[0, :len(r.tokens)] = r.tokens
        ref = jax.tree.map(lambda c: np.asarray(c[:, 0], np.float32),
                           ref_fn(params, jnp.asarray(seq)))
        worst = {"prefill": 0.0, "decode": 0.0}
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
            g = g.astype(np.float32)
            for part, lo, hi in (("prefill", 0, plen), ("decode", plen, pos)):
                err = np.linalg.norm((g[:, lo:hi] - w[:, lo:hi]).reshape(len(g), -1), axis=1)
                norm = np.linalg.norm(w[:, lo:hi].reshape(len(w), -1), axis=1)
                worst[part] = max(worst[part], float((err / norm).max()))
        say(f"  {r.id} (prompt {plen}): cache rows [0, {pos}) of its slot, worst "
            f"per-layer rel L2: prefill rows {worst['prefill']:.3e}, decode rows "
            f"{worst['decode']:.3e}")
        check(pos > plen and max(worst.values()) <= tol,
              f"{r.id}: the engine's cache rows match prefill_into_cache within "
              f"rel L2 {tol}")


def coded_phase(cfg, model, params, mesh, rules, host_mesh=None) -> None:
    """Unguarded run, checked against model.forward, then the same requests
    under the guard with one kill."""
    from repro.serve import CodedServeGuard, ContinuousEngine, FaultInjector

    reqs = make_requests(N_CODED_REQUESTS, cfg.vocab_size)
    eng = ContinuousEngine(model, params, n_slots=CODED_SLOTS,
                           max_len=CODED_MAX_LEN, buckets=CODED_BUCKETS,
                           max_new_tokens=NEW_TOKENS, mesh=mesh, rules=rules)
    probe = CacheProbe(sorted(reqs, key=lambda r: len(r.prompt))[-2:])
    t0 = time.perf_counter()
    base = eng.serve(reqs, greedy=True, guard=probe)
    say(f"  unguarded: {len(base.results)} requests on {CODED_SLOTS} slots x "
        f"{CODED_MAX_LEN} in {time.perf_counter() - t0:.1f} s wall, compiles "
        "included")
    watched = [r for r in base.results if r.id in probe.ids]
    check_greedy_tokens(model, params, watched)
    check_cache_rows(model, params, watched, probe)
    kw = {}
    if host_mesh is not None:
        kw = dict(mesh=host_mesh, axis=host_mesh.axis_names[0])
    guard = CodedServeGuard(K=CODED_K, R=CODED_R,
                            injector=FaultInjector(kills=(KILL,)), **kw)
    t0 = time.perf_counter()
    rep = eng.serve(reqs, greedy=True, guard=guard)
    c = rep.coded
    where = (f"over {len(host_mesh.devices.flat)} devices" if host_mesh is not None
             else "on one device")
    say(f"  guarded (encode {where}): {time.perf_counter() - t0:.1f} s wall, "
        f"{c['snapshots']} snapshots, recovery p50 {c['recovery_us']['p50']:.0f} us")
    say_peak_memory()
    check(c["injected_faults"] == 1 and c["recoveries"] == 1,
          f"host {KILL[1]} killed after tick {KILL[0]} and recovered from "
          f"{CODED_K} of {CODED_K + CODED_R} shards")
    check([r.tokens for r in rep.results] == [r.tokens for r in base.results],
          "guarded tokens identical to the unguarded run")


def cross_chip_encode_phase() -> None:
    """ps_encode_jit on 4 and hierarchical_encode_jit on 2x2, the lowering
    chosen from the mesh: a ``tpu_custom_call`` in the HLO shows it resolved
    to the Pallas kernels."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.field import M31, Field
    from repro.core.matrices import random_matrix, random_vector
    from repro.core.prepare_shoot import encode_oracle
    from repro.dist import hierarchical_encode_jit, ps_encode_jit
    from repro.launch.mesh import make_mesh

    f, K, words = Field(M31), 4, ENCODE_WORDS
    A = np.asarray(random_matrix(f, K, seed=SEED))
    x = random_vector(f, (K, words), seed=SEED + 1)
    want = encode_oracle(x, A, M31)
    flat = make_mesh((4,), ("enc",))
    grid = make_mesh((2, 2), ("inter", "intra"))
    cases = [
        ("ps_encode_jit 4", flat, ("enc",),
         lambda: ps_encode_jit(flat, "enc", A)[0]),
        ("hierarchical_encode_jit 2x2", grid, ("inter", "intra"),
         lambda: hierarchical_encode_jit(grid, "inter", "intra", A)[0]),
    ]
    for name, mesh, axes, make in cases:
        fn = make()
        xs = jax.device_put(jnp.asarray(x.astype(np.uint32)),
                            NamedSharding(mesh, P(axes)))
        t0 = time.perf_counter()
        text = fn.lower(xs).compile().as_text()
        say(f"  {name}: compiled in {time.perf_counter() - t0:.1f} s")
        check("collective-permute" in text and "tpu_custom_call" in text
              and "all-gather" not in text,
              f"{name}: HLO holds collective-permute and tpu_custom_call, "
              "no all-gather")
        out = fn(xs)
        check(len(out.sharding.device_set) == 4,
              f"{name}: output spans {len(out.sharding.device_set)} devices")
        check(np.array_equal(np.asarray(out, dtype=np.uint64), want),
              f"{name}: {words} words per device, bit-exact vs encode_oracle")


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------


def run_phase(name, fn, log) -> None:
    say(f"phase {name}")
    t0 = time.perf_counter()
    fn()
    say(f"phase {name}: PASS in {time.perf_counter() - t0:.1f} s wall "
        f"(bring-up reading); compiles so far: {log.summary()}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the cross-chip path on a four-chip host")
    args = ap.parse_args(argv)
    want = 4 if args.four_chips else 1

    import jax

    devs = jax.devices()
    d0 = devs[0]
    device = {"platform": d0.platform, "kind": d0.device_kind, "count": len(devs)}
    say(f"devices: {device}")
    if d0.platform != "tpu":
        say(f"FAIL: needs a TPU, found platform {d0.platform!r} ({d0.device_kind})")
        return 2
    if len(devs) < want:
        say(f"FAIL: --four-chips needs 4 devices, found {len(devs)}")
        return 2
    sys.path.insert(0, SRC)
    try:
        from repro.configs import get
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        say(f"FAIL: the repository's src/ must sit next to this script ({e})")
        return 2

    log = enable_compile_cache()
    cfg = get(ARCH)
    state = {}

    def setup():
        state["model"] = build(cfg)

    if args.four_chips:
        from repro.launch.mesh import make_mesh

        phases = [
            ("cross-chip encode", cross_chip_encode_phase),
            ("build model", setup),
            ("coded serving over 4 chips", lambda: coded_phase(
                cfg, *state["model"], host_mesh=make_mesh((4,), ("hosts",)))),
        ]
    else:
        phases = [
            ("encode kernels", kernels_phase),
            ("build model", setup),
            ("serving", lambda: serving_phase(cfg, *state["model"])),
            ("coded serving", lambda: coded_phase(cfg, *state["model"])),
        ]
    for name, fn in phases:
        try:
            run_phase(name, fn, log)
        except Exception as e:  # the boundary: report the phase, exit non-zero
            import traceback

            traceback.print_exc()
            say(f"phase {name}: FAIL ({type(e).__name__}: {e})")
            return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
