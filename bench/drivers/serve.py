"""Serving driver: a dense decoder through the program's normal serving path
(``ContinuousEngine`` with the sharding rules and mesh ``launch/serve.py``
builds), fed by the mix's generated requests.

Every request is due at once (a ``backlog``), more than the window can
drain. The window closes at the first chunk sync after ``--seconds``: the
watching guard raises there, and the served tokens so far are read back
from the engine's own state. With a ``guard`` the pool runs under
``CodedServeGuard`` and one host is killed a fraction into the window (a
time-keyed injector).

The program's ``launch/serve.build_serving`` initialises its weights from
key 0 with ``model.init``; this driver builds the same model, rules and
shardings and puts its own seeded weights in their place.
"""

from __future__ import annotations

import time

import numpy as np

from bench import traffic as traffic_gen, weights as weight_gen
from bench.harness import (BENCH, Check, HarnessError, Outcome, load_json,
                           reference_module, say)

#: published config key -> program ModelConfig attribute
PROGRAM_KEYS = {
    "num_hidden_layers": "n_layers", "hidden_size": "d_model",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim", "intermediate_size": "d_ff",
    "vocab_size": "vocab_size", "rope_theta": "rope_theta",
    "tie_word_embeddings": "tie_embeddings",
}


#: chunks a warm-up serve may run (its requests need a few)
WARM_CHUNKS = 64


class WindowClosed(Exception):
    """Raised by the watching guard at the chunk sync that closes the window."""


class TimedKill:
    """A fault schedule keyed by time: host ``host`` dies at the first chunk
    sync once ``clock() >= at_s``. Quacks like the program's
    ``FaultInjector`` (``due`` and ``injected``)."""

    def __init__(self, at_s: float, host: int, clock):
        self.at_s, self.host, self.clock = at_s, host, clock
        self.fired_at: float | None = None
        self.armed = False  # the window's guard arms it when the window opens

    def due(self, now_tick: int):
        if self.armed and self.fired_at is None and self.clock() >= self.at_s:
            self.fired_at = self.clock()
            return [(now_tick - 1, self.host)]
        return []

    @property
    def injected(self) -> int:
        return 0 if self.fired_at is None else 1


class WindowGuard:
    """The guard the engine is handed. It forwards every call to the real
    ``CodedServeGuard`` (or watches alone, ``inner=None``), times
    ``snapshot`` and ``recover`` on the host clock, and closes the window:
    at the first chunk sync at or past ``close_s`` it raises
    :class:`WindowClosed`. It fetches no device array."""

    def __init__(self, inner, close_s: float, spans: list, kill: TimedKill | None = None,
                 max_chunks: int | None = None):
        self.inner, self.close_s, self.spans, self.kill = inner, close_s, spans, kill
        self.max_chunks, self.chunks = max_chunks, 0
        self.t0: float | None = None
        self.closed_at: float | None = None
        self.captured = None  # (data shards, coded shards) at a recovery

    def clock(self) -> float:
        return time.perf_counter() - self.t0

    def attach(self, metrics, tracer) -> None:
        self.t0 = time.perf_counter()
        if self.kill is not None:
            self.kill.armed = True
        if self.inner is not None:
            self.inner.attach(metrics, tracer)

    def snapshot(self, cache, state, tick: int) -> None:
        import jax

        if self.inner is None:
            return
        with jax.profiler.TraceAnnotation("bench.snapshot"):
            t = time.perf_counter()
            self.inner.snapshot(cache, state, tick)
            self.spans.append(("bench.snapshot", time.perf_counter() - t, {"tick": tick}))

    def poll(self, now_tick: int) -> list:
        dead = self.inner.poll(now_tick) if self.inner is not None else []
        self.chunks += 1
        over = self.max_chunks is not None and self.chunks >= self.max_chunks
        if not dead and (over or self.clock() >= self.close_s):
            self.closed_at = self.clock()
            raise WindowClosed()
        return dead

    def recover(self, dead, requests_in_flight: int = 0):
        import jax

        group = self.inner.group
        coded = {j: v for j, v in group._mem.items()}  # host copies the hosts held
        reconstruct = group.reconstruct
        seen = {}

        def watched():
            seen["X"] = reconstruct()
            return seen["X"]

        group.reconstruct = watched
        try:
            with jax.profiler.TraceAnnotation("bench.recover"):
                t = time.perf_counter()
                out = self.inner.recover(dead, requests_in_flight)
                self.spans.append(("bench.recover", time.perf_counter() - t, {}))
        finally:
            del group.reconstruct
        self.captured = (seen.get("X"), coded)
        return out

    def stats(self):
        return self.inner.stats() if self.inner is not None else None


def _serve_frame(exc) -> dict:
    """The locals of ``ContinuousEngine.serve`` where the window closed."""
    tb = exc.__traceback__
    while tb is not None:
        if tb.tb_frame.f_code.co_name == "serve" and "results" in tb.tb_frame.f_locals:
            return tb.tb_frame.f_locals
        tb = tb.tb_next
    raise HarnessError("the window closed outside ContinuousEngine.serve")


def check_program_config(config: dict, pcfg) -> None:
    for key, attr in PROGRAM_KEYS.items():
        want, got = config[key], getattr(pcfg, attr)
        if (float(want) if isinstance(want, (int, float)) and not isinstance(want, bool)
                else want) != got:
            raise HarnessError(
                f"{config['name']}: {key} is {want} here, the program runs {attr}={got}")
    if pcfg.qk_norm != (config["model_type"] == "qwen3"):
        raise HarnessError(f"{config['name']}: q/k norms differ from the program's")


def check_code(code: dict, plan) -> None:
    """The program's LCC plan must use the configuration's points."""
    if ([int(w) for w in plan.omega_points] != code["omega_points"]
            or [int(a) for a in plan.alpha_points] != code["alpha_points"]
            or int(plan.q) != code["q"]):
        raise HarnessError(f"{code['name']}: the program's LCC points differ from "
                           "the configuration's")


def build(config: dict, traffic: dict, seed: int, tracer=None):
    """(model, params, engine) for the mix's pool, weights from ``seed``."""
    import jax

    from repro.configs import get
    from repro.configs.base import ShapeSpec
    from repro.launch.mesh import make_mesh
    from repro.launch.profiles import BASELINE, rules_for
    from repro.models import build_model
    from repro.serve import ContinuousEngine
    from repro.train import param_shardings

    pcfg = get(config["program_config"])
    for key, val in config.get("program_overrides", {}).items():
        pcfg = pcfg.replace(**{key: val})
    check_program_config(config, pcfg)
    pool = traffic["pool"]
    mesh = make_mesh((1, 1), ("data", "model"))
    rules = rules_for(pcfg, ShapeSpec("bench", "decode", pool["max_len"], 1), BASELINE)
    model = build_model(pcfg)
    shapes, _ = model.param_specs()
    params = weight_gen.make(shapes, param_shardings(model, mesh, rules), seed)
    jax.block_until_ready(params)
    eng = ContinuousEngine(
        model, params, n_slots=pool["slots"], max_len=pool["max_len"],
        buckets=tuple(pool["buckets"]), max_new_tokens=traffic["output"]["max"],
        mesh=mesh, rules=rules, tracer=tracer)
    return model, params, eng


def to_requests(reqs):
    from repro.serve import Request

    return [Request(id=r.id, prompt=r.prompt.tolist(), max_new_tokens=r.max_new_tokens,
                    arrival_s=r.arrival_s) for r in reqs]


def used_buckets(reqs, buckets) -> list[int]:
    from repro.serve.scheduler import bucket_for

    return sorted({bucket_for(len(r.prompt), buckets) for r in reqs})


def warm(eng, reqs, traffic, guard, code, vocab: int) -> None:
    """Compile every program the window will run: the decode tick, and a
    prefill per bucket the traffic uses, both as a serve's first prefill
    (fresh state) and as a later one (state the programs returned, with other
    shardings); under a guard also the snapshot encode and the programs a
    recovery runs."""
    from repro.serve import Request

    pool = traffic["pool"]
    rng = np.random.default_rng(0)
    buckets = used_buckets(reqs, pool["buckets"])

    def req(i, b):
        return Request(id=f"warm-{i}", prompt=rng.integers(1, vocab, size=b).tolist(),
                       max_new_tokens=2 * pool["sync_every"])

    for first in buckets:
        batch = [req(0, first)] + [req(i + 1, b) for i, b in enumerate(buckets)]
        # a warm serve ends after WARM_CHUNKS chunks even if a broken program
        # never finishes its requests: the window then shows the fault
        wg = WindowGuard(guard, float("inf"), [], max_chunks=WARM_CHUNKS)
        try:
            eng.serve(batch, greedy=traffic["greedy"], sync_every=pool["sync_every"],
                      guard=wg)
        except WindowClosed as e:
            e.__traceback__ = None
    if guard is not None:
        import jax
        import jax.numpy as jnp

        from repro.coded.rs_checkpoint import shard_state_limbs, unshard_state_limbs

        # a recovery rebuilds the state from host shards, so the replayed
        # chunk's first tick sees arguments placed otherwise: compile it too
        shards, meta = shard_state_limbs(eng.init_state(), code["K"])
        X = jnp.asarray(np.zeros(shards.shape, np.uint32))
        cache, state = unshard_state_limbs(X, meta)
        tick = eng._tick_for(traffic["greedy"])
        jax.block_until_ready(tick(eng.params, cache, state, jnp.int32(-1), jnp.float32(1.0)))


def _sample(rng, candidates, must, budget: int):
    """Requests to check: those in ``must``, the longest, then a seeded draw
    until ``budget`` served tokens are covered."""
    by_id = {c["id"]: c for c in candidates}
    chosen = [by_id[i] for i in must if i in by_id]
    longest = max(candidates, key=lambda c: len(c["prompt"]) + len(c["served"]))
    if longest not in chosen:
        chosen.append(longest)
    rest = [c for c in candidates if c not in chosen]
    for i in rng.permutation(len(rest)):
        if sum(len(c["served"]) for c in chosen) >= budget:
            break
        chosen.append(rest[int(i)])
    return chosen


def run(ctx) -> Outcome:
    return measure(ctx, prepare(ctx.config, ctx.traffic, ctx.seed, ctx.trace))


def prepare(config: dict, traffic: dict, seed: int, trace: bool = False) -> dict:
    """The engine and its weights; ``measure`` warms it on first use."""
    tracer = None
    if trace:
        from bench.spans import AnnotatingTracer

        tracer = AnnotatingTracer()
    model, params, eng = build(config, traffic, seed, tracer)
    return {"eng": eng, "params": params, "tracer": tracer, "warmed": False}


def reseed(prep: dict, config: dict, seed: int) -> None:
    """New weights from ``seed`` for an engine already warmed (the control's
    readings over many seeds in one process)."""
    import jax

    eng = prep["eng"]
    shapes = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), eng.params)
    shardings = jax.tree.map(lambda a: a.sharding, eng.params)
    eng.params = prep["params"] = None
    eng.params = prep["params"] = weight_gen.make(shapes, shardings, seed)


def measure(ctx, prep: dict, controls: tuple = (), max_chunks: int | None = None) -> Outcome:
    """Warm (once), run the window, check. ``controls`` names reference
    precisions whose readings over the same tokens go into the facts.
    ``max_chunks`` (CPU tests) also closes the window after that many chunks,
    so that what a test reads does not depend on the machine's speed."""
    import jax

    from repro.serve import CodedServeGuard

    config, traffic, seed = ctx.config, ctx.traffic, ctx.seed
    pool, guard_cfg = traffic["pool"], traffic.get("guard")
    eng, params, tracer = prep["eng"], prep["params"], prep["tracer"]
    vocab = int(config["vocab_size"])
    gen = traffic_gen.generate(traffic, seed, vocab)
    reqs = to_requests(gen)

    spans: list = []
    inner, kill, code = None, None, None
    wg = WindowGuard(None, ctx.seconds, spans, max_chunks=max_chunks)
    if guard_cfg is not None:
        # one guard for the warm-up and the window, so its encode program is
        # the one compiled; the kill is armed when the window opens
        code = load_json(BENCH / "configs" / f"{guard_cfg['code']}.json")
        host = int(np.random.default_rng([seed, 2]).integers(0, code["N"]))
        kill = TimedKill(ctx.seconds * traffic["kill"]["at_window_fraction"], host, wg.clock)
        inner = CodedServeGuard(K=code["K"], R=code["R"], p=code["p"], q=code["q"],
                                injector=kill)
        check_code(code, inner.plan)
        wg.inner, wg.kill = inner, kill
    if not prep["warmed"]:
        warm(eng, reqs, traffic, inner, code, vocab)
        prep["warmed"] = True
    if tracer is not None:
        tracer.spans.clear()
    with ctx.window():
        try:
            eng.serve(reqs, greedy=traffic["greedy"], sync_every=pool["sync_every"], guard=wg)
        except WindowClosed as e:
            loc = _serve_frame(e)
            finished = dict(loc["results"])
            state = loc["state"]
            meta = dict(loc["meta"])
            del loc
            e.__traceback__ = None
        else:
            raise HarnessError("the backlog drained before the window closed: deepen it")
    ctx.record_memory(jax.devices()[:1])
    window_s = wg.closed_at
    gen_buf = np.asarray(state["gen_buf"])
    gen_count = np.asarray(state["gen_count"])
    in_flight = [{"id": req.id, "prompt": list(req.prompt),
                  "served": gen_buf[s, :int(gen_count[s])].tolist(), "ttft_s": ttft}
                 for s, (req, ttft) in meta.items()]
    del state
    if not controls:
        prep.clear()
        del eng

    prompts = {r.id: r.prompt for r in reqs}
    done = [{"id": r.id, "prompt": prompts[r.id], "served": r.tokens[r.prompt_len:],
             "ttft_s": r.ttft_s, "e2e_s": r.e2e_s} for r in finished.values()]
    attempted = len(done) + len(in_flight)
    tokens = sum(len(c["served"]) for c in done + in_flight)
    e2e = {"tokens_per_s": tokens / window_s}
    checks: dict = {}
    # where the window went, so that a slow run can be put down to its cause
    snaps = sorted(d for n, d, _ in spans if n == "bench.snapshot")
    recovered = sum(d for n, d, _ in spans if n == "bench.recover")
    say(f"window: {len(done)} requests finished, {len(in_flight)} in flight, {tokens} "
        f"tokens, closed at {window_s:.3f} s; {len(snaps)} snapshots"
        + (f" (p50 {snaps[len(snaps) // 2] * 1e3:.3f} ms, max {snaps[-1] * 1e3:.3f} ms, "
           f"total {sum(snaps):.3f} s)" if snaps else "")
        + (f", recovery_us {inner.recovery_us}" if inner is not None else "")
        + f"; {window_s - sum(snaps) - recovered:.3f} s in ticks, prefills and the host loop")

    # -- the check: served tokens under the float32 reference ---------------
    t_check = time.perf_counter()
    ref = reference_module(config)
    must = []
    if kill is not None and kill.fired_at is not None:
        at = kill.fired_at
        must = [c["id"] for c in done if c["ttft_s"] < at < c["e2e_s"]]
        must += [c["id"] for c in in_flight if c["ttft_s"] < at]
    cands = [c for c in done + in_flight if c["served"]]
    sample = _sample(np.random.default_rng([seed, 3]), cands, must,
                     traffic["check"]["served_tokens"])
    worst = 0.0
    n_tok = 0
    control_worst = {m: 0.0 for m in controls}
    for c in sample:
        gaps = ref.served_gaps(params, config, c["prompt"], c["served"],
                               traffic["check"]["ref_len"])
        worst = max(worst, float(gaps.max()))
        n_tok += len(gaps)
        for m in controls:
            cg = ref.served_gaps(params, config, c["prompt"], c["served"],
                                 traffic["check"]["ref_len"], control=m)
            control_worst[m] = max(control_worst[m], float(cg.max()))
    checks["max_logit_shortfall"] = Check(worst, ctx.limit("max_logit_shortfall"))
    say(f"checked {n_tok} served tokens of {len(sample)} requests "
        f"({len(must)} in flight at the kill) in {time.perf_counter() - t_check:.1f} s")
    # a decode that makes no progress finishes nothing
    checks["no_request_finished"] = Check(float(not done), ctx.limit("no_request_finished"))
    if guard_cfg is not None:
        fired = kill.injected
        checks["kill_missing"] = Check(float(1 - fired), ctx.limit("kill_missing"))
        checks["kill_unrecovered"] = Check(float(fired - inner.recoveries),
                                           ctx.limit("kill_unrecovered"))
        if fired:
            # a recovery that went round ``group.reconstruct`` leaves nothing to
            # compare: that reads as wrong, so the check cannot vanish unseen
            X, coded = wg.captured or (None, None)
            wrong = 1 if X is None else _recovery_words_wrong(X, coded, code)
            checks["recovered_words_wrong"] = Check(float(wrong),
                                                    ctx.limit("recovered_words_wrong"))

    facts = {
        "served": [(len(c["prompt"]), len(c["served"])) for c in done + in_flight],
        "checked_tokens": n_tok,
        **{f"control_{m}": v for m, v in control_worst.items()},
    }
    if inner is not None:
        facts["recovery_us"] = list(inner.recovery_us)
    if tracer is not None:
        spans.extend((s.name, s.dur_us / 1e6, s.attrs) for s in tracer.spans)
    return Outcome(attempted=attempted, failed=0, e2e=e2e, checks=checks,
                   facts=facts, spans=spans)


def _recovery_words_wrong(X, coded: dict, lcc: dict) -> int:
    """Words where the bench's own encode of the reconstructed data differs
    from a coded shard a host held when the kill came (the killed host's
    included): zero iff the recovery gave back exactly what was encoded."""
    from bench.references import lcc_encode

    K, N = lcc["K"], lcc["N"]
    x = np.zeros((N,) + X.shape[1:], np.uint32)
    x[:K] = X
    want = lcc_encode.encode(x.reshape(N, -1), lcc)
    return sum(lcc_encode.words_wrong(np.asarray(v).reshape(-1), want[j])
               for j, v in coded.items())

