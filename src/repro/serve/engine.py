"""Serving engines: fixed-batch (legacy) and continuous-batching.

Two tiers share the model's compiled graphs:

* :class:`Engine` — the original fixed-capacity batch: prompts are
  right-padded and refed token-by-token through the single compiled
  decode step, then new tokens are sampled until max length or EOS. One
  long prompt or one slow finisher stalls the whole batch; it stays as
  the measured baseline and the encoder-decoder/recurrent fallback.

* :class:`ContinuousEngine` — the maxtext-style continuous-batching
  tier. A **separate compiled prefill graph**
  (``train.train_loop.make_prefill_step(into_cache=True)`` →
  ``models.model.Model.prefill_into_cache``) writes a whole prompt's
  K/V into one cache slot in a single forward pass and returns the first
  sampled token; prompts are right-padded to a length **bucket** so the
  number of prefill compilations is bounded by the bucket set (counted
  in ``serve.prefill_compiles``). A :class:`~repro.serve.scheduler.
  SlotScheduler` keeps a fixed pool of decode slots fed from a FIFO
  arrival queue — when a slot hits EOS or its token budget it is retired
  and the next queued request is prefilled into that slot **mid-decode**,
  without draining the batch. The decode step threads per-slot position
  counters and an active-slot mask entirely on device; the host syncs
  only every ``sync_every`` ticks (one bool-mask fetch), so retired
  slots cost no per-token sampling syncs.

Observability (``repro.obs``): ``serve.steps`` / ``serve.generate_ms`` /
``serve.tokens_per_s`` (generated-tokens-only in BOTH engines) /
``serve.eos_syncs_saved`` on the fixed path; ``serve.prefill_compiles``
/ ``serve.decode_steps`` / ``serve.ttft_ms`` / ``serve.e2e_ms`` /
``serve.slot_occupancy`` on the continuous path, and for an MoE model
``models.moe.held_pairs``: the (token, expert) pairs its held experts
computed, counted on the device by the tick and fetched with the chunk's
active mask. Passing ``tracer=``
feeds the ``serve.step_us`` / ``serve.prefill_us`` /
``serve.decode_chunk_us`` latency histograms and, on the continuous path,
puts every phase of a serve-loop iteration under a top-level span:
``serve.admit`` (``admitted``; one ``serve.prefill`` child per request),
the guard's ``serve.snapshot``, ``serve.decode_chunk`` (``cache_rows``, the
occupied slots' positions summed at the chunk's start; ``moe_pairs`` and
``moe_pairs_max`` for an MoE model; ``replay=1`` for the chunk replayed
after a recovery), ``serve.poll``, the guard's
``serve.recovery``, ``serve.harvest`` (``finished``), and
``serve.wait`` while no slot is busy and the next arrival is due later.
The fixed path's step spans force a device sync per step (opt-in); the
continuous path's spans sync nowhere the untraced loop does not.
"""

from __future__ import annotations

import time
import zlib
from dataclasses import dataclass

import numpy as np

import jax
import jax.numpy as jnp

from repro.models.model import Model
from repro.obs.trace import optional_span
from repro.train.train_loop import make_decode_step, make_prefill_step

from .scheduler import (
    DEFAULT_BUCKETS,
    Request,
    RequestResult,
    SlotScheduler,
    bucket_for,
)


def _request_seed(req: Request) -> int:
    """The request's sampling-stream seed: explicit ``req.seed`` or a
    stable hash of its id — never a function of batch composition."""
    if req.seed is not None:
        return int(req.seed)
    return zlib.crc32(req.id.encode()) & 0x7FFFFFFF


def _percentiles_ms(samples_s: list[float]) -> dict:
    if not samples_s:
        return {"p50": 0.0, "p99": 0.0}
    ms = np.asarray(samples_s) * 1e3
    return {"p50": float(np.percentile(ms, 50)), "p99": float(np.percentile(ms, 99))}


@dataclass
class GenerationResult:
    tokens: np.ndarray  # (B, total)
    steps: int
    #: per-sequence prompt + generated length, trimmed at the first EOS in
    #: the generated region (the EOS token itself counts)
    lengths: np.ndarray  # (B,)
    prompt_lens: np.ndarray  # (B,)


class Engine:
    """Fixed-batch engine (baseline + encdec/recurrent fallback)."""

    def __init__(
        self,
        model: Model,
        params,
        max_len: int = 256,
        mesh=None,
        rules=None,
        tracer=None,
        metrics=None,
    ):
        self.model = model
        self.params = params
        self.max_len = max_len
        self._step = jax.jit(make_decode_step(model, mesh, rules))
        self._tracer = tracer
        self._metrics = metrics

    def _registry(self):
        if self._metrics is not None:
            return self._metrics
        from repro.obs.metrics import get_registry

        return get_registry()

    def generate(
        self,
        prompts: list[list[int]],
        max_new_tokens: int = 32,
        eos_id: int | None = None,
        greedy: bool = True,
        seed: int = 0,
        eos_check_every: int = 8,
    ) -> GenerationResult:
        B = len(prompts)
        cfg = self.model.cfg
        plen = np.array([len(p) for p in prompts])
        total = int(plen.max()) + max_new_tokens
        assert total <= self.max_len
        toks = np.zeros((B, total), dtype=np.int32)
        for b, p in enumerate(prompts):
            toks[b, : len(p)] = p
        cache = self.model.init_cache(B, self.max_len)
        if self.model.is_encdec:
            # stub frames: zeros (real system: audio frontend output)
            cache = dict(cache)
            cache["enc_out"] = jnp.zeros(
                (B, cfg.encdec.n_frames, cfg.d_model), self.model.dtype
            )
        toks_j = jnp.asarray(toks)
        key = jax.random.key(seed)
        reg = self._registry()
        tracer = self._tracer
        steps = 0
        last_t = 0
        t_start = time.perf_counter()
        for t in range(total - 1):
            cur = toks_j[:, t : t + 1]
            pos = jnp.full((B,), t, jnp.int32)
            if tracer is not None:
                with tracer.span("serve.step", step=steps, pos=t, batch=B) as sp:
                    logits, cache = self._step(self.params, cache, cur, pos)
                    jax.block_until_ready(logits)
                reg.histogram("serve.step_us").observe(sp.dur_us)
            else:
                logits, cache = self._step(self.params, cache, cur, pos)
            steps += 1
            last_t = t
            lg = logits[:, 0, : cfg.vocab_size]
            if greedy:
                nxt = jnp.argmax(lg, axis=-1).astype(jnp.int32)
            else:
                key, sk = jax.random.split(key)
                nxt = jax.random.categorical(sk, lg).astype(jnp.int32)
            # only overwrite positions beyond each prompt
            write = (t + 1) >= jnp.asarray(plen)
            new_col = jnp.where(write, nxt, toks_j[:, t + 1])
            toks_j = toks_j.at[:, t + 1].set(new_col)
            if eos_id is not None:
                # the all-sequences-done check is a device→host sync; batch
                # it every eos_check_every steps (and on the last step) so
                # the decode loop stays asynchronous in between
                due = steps % max(eos_check_every, 1) == 0 or t == total - 2
                if due:
                    if bool(jnp.all(jnp.any(toks_j == eos_id, axis=1))):
                        break
                else:
                    reg.counter("serve.eos_syncs_saved").inc()
        wall_s = time.perf_counter() - t_start
        toks_np = np.asarray(toks_j)
        # generated-tokens-only accounting: columns 0..last_t+1 are filled;
        # a sequence's generated region is [plen, last_t+2), EOS-trimmed
        filled = last_t + 2
        gen = np.clip(filled - plen, 0, max_new_tokens)
        if eos_id is not None:
            for b in range(B):
                region = toks_np[b, plen[b] : plen[b] + gen[b]]
                hits = np.nonzero(region == eos_id)[0]
                if hits.size:
                    gen[b] = hits[0] + 1
        reg.counter("serve.steps").inc(steps)
        reg.gauge("serve.generate_ms").set(wall_s * 1e3)
        if wall_s > 0:
            reg.gauge("serve.tokens_per_s").set(float(gen.sum()) / wall_s)
        return GenerationResult(
            tokens=toks_np,
            steps=steps,
            lengths=plen + gen,
            prompt_lens=plen,
        )


@dataclass
class ServeReport:
    """Outcome of one :meth:`ContinuousEngine.serve` run: per-request
    results (arrival order) + the latency/throughput aggregates."""

    results: list[RequestResult]
    wall_s: float
    tokens_per_s: float  # generated tokens only
    ttft_ms: dict  # {"p50", "p99"}
    e2e_ms: dict  # {"p50", "p99"}
    slot_occupancy: float  # mean occupied-slot fraction over decode ticks
    prefill_compiles: int  # engine-lifetime compiled prefill graph count
    decode_steps: int
    #: guard.stats() when the run was coded (K/R, injected_faults,
    #: recoveries, requests_recovered, recovery_us percentiles)
    coded: dict | None = None

    @property
    def recoveries(self) -> int:
        return int(self.coded["recoveries"]) if self.coded else 0

    @property
    def requests_recovered(self) -> int:
        return int(self.coded["requests_recovered"]) if self.coded else 0

    def to_record(self) -> dict:
        """JSON-ready row of the aggregates."""
        rec = {
            "tokens_per_s": self.tokens_per_s,
            "ttft_ms": dict(self.ttft_ms),
            "e2e_ms": dict(self.e2e_ms),
            "slot_occupancy": self.slot_occupancy,
            "prefill_compiles": self.prefill_compiles,
            "decode_steps": self.decode_steps,
            "n_requests": len(self.results),
            "wall_s": self.wall_s,
        }
        if self.coded is not None:
            rec["coded"] = dict(self.coded)
        return rec


class ContinuousEngine:
    """Continuous-batching engine: compiled prefill graph per length
    bucket + slot-scheduled decode with mid-stream insertion."""

    def __init__(
        self,
        model: Model,
        params,
        n_slots: int = 4,
        max_len: int = 256,
        buckets=None,
        max_new_tokens: int = 32,
        mesh=None,
        rules=None,
        tracer=None,
        metrics=None,
    ):
        if not model.supports_prefill:
            raise NotImplementedError(
                f"{model.cfg.name}: one-pass prefill needs per-position cache "
                "rows (recurrent/encdec/VLM models serve via the fixed-batch "
                "Engine)"
            )
        if buckets is None:
            buckets = tuple(b for b in DEFAULT_BUCKETS if b <= max_len) or (max_len,)
        if max(buckets) > max_len:
            raise ValueError(f"bucket {max(buckets)} exceeds max_len {max_len}")
        self.model = model
        self.params = params
        self.n_slots = n_slots
        self.max_len = max_len
        self.buckets = tuple(sorted(int(b) for b in buckets))
        self.max_new_tokens = max_new_tokens
        self._mesh, self._rules = mesh, rules
        self._tracer = tracer
        self._metrics = metrics
        self._prefill_jits: dict = {}  # (bucket, greedy) -> jitted graph
        self._tick_jits: dict = {}  # greedy -> jitted decode tick
        #: an expert model's ticks count the pairs its held experts compute
        self._moe_held = model.cfg.moe.held if model.cfg.moe is not None else 0

    # -- observability ------------------------------------------------------
    def _registry(self):
        if self._metrics is not None:
            return self._metrics
        from repro.obs.metrics import get_registry

        return get_registry()

    def _span(self, name: str, **attrs):
        """A span of the engine's tracer; a no-op context without one."""
        return optional_span(self._tracer, name, **attrs)

    @property
    def prefill_compiles(self) -> int:
        """Compiled prefill graphs over this engine's lifetime — bounded by
        len(buckets) per sampling mode by construction."""
        return len(self._prefill_jits)

    # -- compiled graphs ----------------------------------------------------
    def _tick_for(self, greedy: bool):
        tick = self._tick_jits.get(greedy)
        if tick is None:
            tick = self._make_tick(greedy)
            self._tick_jits[greedy] = tick
        return tick

    def _make_tick(self, greedy: bool):
        decode = make_decode_step(self.model, self._mesh, self._rules, with_pairs=True)
        V = self.model.cfg.vocab_size
        G = self.max_new_tokens

        def tick(params, cache, state, eos_id, temperature):
            logits, cache, pairs = decode(
                params, cache, state["last_tok"][:, None], state["pos"]
            )
            lg = logits[:, 0, :V]
            if greedy:
                nxt = jnp.argmax(lg, axis=-1).astype(jnp.int32)
            else:
                # per-slot streams: token i of a request is sampled with
                # fold_in(request_key, i) — independent of batch
                # composition, so slot-scheduled == one-at-a-time
                keys = jax.random.wrap_key_data(state["rng"])
                tok_keys = jax.vmap(jax.random.fold_in)(keys, state["gen_count"])
                nxt = jax.vmap(jax.random.categorical)(
                    tok_keys, lg / temperature
                ).astype(jnp.int32)
            active = state["active"]
            nxt = jnp.where(active, nxt, state["last_tok"])
            gc = state["gen_count"]
            # masked append: retired slots write nothing, cost no host sync
            write = (jnp.arange(G)[None, :] == gc[:, None]) & active[:, None]
            gen_buf = jnp.where(write, nxt[:, None], state["gen_buf"])
            gc = gc + active.astype(jnp.int32)
            pos = state["pos"] + active.astype(jnp.int32)
            hit_eos = active & (eos_id >= 0) & (nxt == eos_id)
            active = active & ~hit_eos & (gc < state["max_gen"])
            new = {
                "last_tok": nxt,
                "pos": pos,
                "active": active,
                "gen_buf": gen_buf,
                "gen_count": gc,
                "max_gen": state["max_gen"],
                "rng": state["rng"],
            }
            if pairs is not None:  # every slot's token went through the experts
                new["moe_pairs"] = state["moe_pairs"] + pairs
            return cache, new

        return jax.jit(tick, donate_argnums=(1, 2))

    def _prefill_for(self, bucket: int, greedy: bool):
        key = (bucket, greedy)
        pf = self._prefill_jits.get(key)
        if pf is None:
            pf = self._make_prefill(greedy)
            self._prefill_jits[key] = pf
            self._registry().counter("serve.prefill_compiles").inc()
        return pf

    def _make_prefill(self, greedy: bool):
        raw = make_prefill_step(self.model, self._mesh, self._rules, into_cache=True)
        V = self.model.cfg.vocab_size
        G = self.max_new_tokens

        def prefill(
            params, cache, state, tokens, slot, plen, req_max, eos_id,
            rng_kd, temperature,
        ):
            last, cache = raw(params, cache, tokens, slot, plen)
            lg = last[0, :V]
            if greedy:
                t0 = jnp.argmax(lg).astype(jnp.int32)
            else:
                # token 0 of this request's stream (see _make_tick)
                k0 = jax.random.fold_in(jax.random.wrap_key_data(rng_kd), 0)
                t0 = jax.random.categorical(k0, lg / temperature).astype(jnp.int32)
            done = ((eos_id >= 0) & (t0 == eos_id)) | (req_max <= 1)
            row = jnp.zeros((G,), jnp.int32).at[0].set(t0)
            state = {
                **state,
                "last_tok": state["last_tok"].at[slot].set(t0),
                "pos": state["pos"].at[slot].set(plen),
                "active": state["active"].at[slot].set(~done),
                "gen_buf": state["gen_buf"].at[slot].set(row),
                "gen_count": state["gen_count"].at[slot].set(1),
                "max_gen": state["max_gen"].at[slot].set(req_max),
                "rng": state["rng"].at[slot].set(rng_kd),
            }
            return cache, state

        return jax.jit(prefill, donate_argnums=(1, 2))

    # -- serve loop ---------------------------------------------------------
    def init_state(self):
        """``(cache, state)`` with every slot free: what a serve starts
        from, and the argument schema of the decode tick and prefill."""
        S, G = self.n_slots, self.max_new_tokens
        cache = self.model.init_cache(S, self.max_len)
        state = {
            "last_tok": jnp.zeros((S,), jnp.int32),
            "pos": jnp.zeros((S,), jnp.int32),
            "active": jnp.zeros((S,), jnp.bool_),
            "gen_buf": jnp.zeros((S, G), jnp.int32),
            "gen_count": jnp.zeros((S,), jnp.int32),
            "max_gen": jnp.zeros((S,), jnp.int32),
            "rng": jnp.zeros((S, 2), jnp.uint32),
        }
        if self._moe_held:
            # pairs each held expert computed in the ticks so far
            state["moe_pairs"] = jnp.zeros((self._moe_held,), jnp.int32)
        return cache, state

    def _validate(self, req: Request) -> None:
        plen = len(req.prompt)
        bucket_for(plen, self.buckets)  # raises if no bucket covers it
        if req.max_new_tokens < 1 or req.max_new_tokens > self.max_new_tokens:
            raise ValueError(
                f"{req.id}: max_new_tokens {req.max_new_tokens} outside "
                f"[1, {self.max_new_tokens}]"
            )
        if plen + req.max_new_tokens > self.max_len:
            raise ValueError(
                f"{req.id}: prompt {plen} + budget {req.max_new_tokens} "
                f"exceeds max_len {self.max_len}"
            )

    def serve(
        self,
        requests: list[Request],
        greedy: bool = True,
        eos_id: int | None = None,
        seed: int = 0,
        sync_every: int = 4,
        temperature: float = 1.0,
        guard=None,
    ) -> ServeReport:
        """Run a trace of requests to completion; returns a ServeReport with
        per-request results in arrival order.

        ``sync_every`` is the decode-chunk length between host syncs: one
        bool-mask fetch per chunk detects retirements (a finished slot may
        run up to ``sync_every - 1`` masked ticks before harvest — the
        latency/throughput knob).

        ``guard`` (a :class:`repro.serve.coded.CodedServeGuard`) makes the
        run straggler-tolerant: the decode-path state is LCC-encoded to
        N = K + R coded hosts before every chunk, host faults are polled
        at the chunk sync, and a lost host triggers exact reconstruction
        from any K survivors + a deterministic chunk replay — in-flight
        requests are recovered, not dropped, and the token streams stay
        bit-identical to an unfailed run.
        """
        if not greedy and temperature <= 0:
            raise ValueError(f"sampling needs temperature > 0, got {temperature}")
        reg = self._registry()
        tracer = self._tracer
        ordered = sorted(requests, key=lambda r: (r.arrival_s, r.id))
        sched = SlotScheduler(self.n_slots)
        for r in ordered:
            self._validate(r)
            sched.submit(r)
        S = self.n_slots
        cache, state = self.init_state()
        base_key = jax.random.key(seed)
        temp = jnp.float32(temperature)
        eos = jnp.int32(-1 if eos_id is None else eos_id)
        tick = self._tick_for(greedy)
        if guard is not None:
            guard.attach(reg, tracer)
        meta: dict[int, tuple[Request, float]] = {}  # slot -> (req, ttft_s)
        admitted_at: dict[int, int] = {}  # slot -> decode_steps at its prefill
        results: dict[str, RequestResult] = {}
        pairs_seen = np.zeros((self._moe_held,), np.int64)
        ticks_active = ticks_total = decode_steps = 0
        t0 = time.perf_counter()

        def now() -> float:
            return time.perf_counter() - t0

        def run_chunk(cache, state):
            for _ in range(sync_every):
                cache, state = tick(self.params, cache, state, eos, temp)
            if self._moe_held:
                # the expert counters come back with the chunk's one sync
                active, pairs = jax.device_get((state["active"], state["moe_pairs"]))
                return cache, state, active, pairs
            return cache, state, np.asarray(state["active"]), None

        def note_pairs(sp, pairs_now):
            """The chunk's pairs on held experts: a span attribute and a counter."""
            if pairs_now is None:
                return
            chunk = pairs_now - pairs_seen
            reg.counter("models.moe.held_pairs").inc(int(chunk.sum()))
            if sp is not None:
                sp.attrs["moe_pairs"] = int(chunk.sum())
                sp.attrs["moe_pairs_max"] = int(chunk.max())

        while sched.has_work:
            # 1. refill free slots with every arrived request (mid-decode
            #    insertion: the rest of the batch is untouched)
            with self._span("serve.admit") as admit_sp:
                admitted = 0
                while (a := sched.next_assignment(now())) is not None:
                    slot, req = a
                    plen = len(req.prompt)
                    bucket = bucket_for(plen, self.buckets)
                    pf = self._prefill_for(bucket, greedy)
                    toks = np.zeros((1, bucket), np.int32)
                    toks[0, :plen] = req.prompt
                    rng_kd = jax.random.key_data(
                        jax.random.fold_in(base_key, _request_seed(req))
                    )
                    with self._span(
                        "serve.prefill", slot=slot, bucket=bucket, plen=plen
                    ) as sp:
                        cache, state = pf(
                            self.params, cache, state, jnp.asarray(toks),
                            jnp.int32(slot), jnp.int32(plen),
                            jnp.int32(req.max_new_tokens), eos, rng_kd, temp,
                        )
                        # first token is materialized here — that's TTFT
                        jax.block_until_ready(state["last_tok"])
                    if sp is not None:
                        reg.histogram("serve.prefill_us").observe(sp.dur_us)
                    ttft = now() - req.arrival_s
                    meta[slot] = (req, ttft)
                    admitted_at[slot] = decode_steps
                    reg.histogram("serve.ttft_ms").observe(ttft * 1e3)
                    admitted += 1
                if admit_sp is not None:
                    admit_sp.attrs["admitted"] = admitted
            occ = sched.occupied
            if not occ:
                nxt_arr = sched.next_arrival_s()
                if nxt_arr is None:
                    break  # queue drained, all slots retired
                wait = nxt_arr - now()
                if wait > 0:
                    with self._span("serve.wait"):
                        time.sleep(wait)
                continue
            # 2. one decode chunk: sync_every fully-async ticks, then a
            #    single host sync on the active mask to detect retirements.
            #    Under a guard the chunk-start state was LCC-encoded first,
            #    so a host lost mid-chunk costs one reconstruct + replay.
            if guard is not None:
                guard.snapshot(cache, state, tick=decode_steps)
            # cache rows the occupied slots hold at the chunk's start: each
            # slot has run every tick since its prefill wrote its prompt
            rows = sum(len(meta[s][0].prompt) + decode_steps - admitted_at[s] for s in occ)
            with self._span(
                "serve.decode_chunk", ticks=sync_every, occupied=len(occ), cache_rows=rows
            ) as sp:
                cache, state, active_now, pairs_now = run_chunk(cache, state)
                note_pairs(sp, pairs_now)
            if sp is not None:
                reg.histogram("serve.decode_chunk_us").observe(sp.dur_us)
            decode_steps += sync_every
            ticks_active += len(occ) * sync_every
            ticks_total += S * sync_every
            if guard is not None:
                with self._span("serve.poll"):
                    dead = guard.poll(decode_steps)
                if dead:
                    # exact chunk-start state from any K survivors, then a
                    # deterministic replay (the PRNG lives in the state) —
                    # the replayed tokens are bit-identical
                    cache, state = guard.recover(
                        dead, requests_in_flight=len(occ)
                    )
                    with self._span(
                        "serve.decode_chunk", ticks=sync_every,
                        occupied=len(occ), cache_rows=rows, replay=1,
                    ) as sp:
                        cache, state, active_now, pairs_now = run_chunk(cache, state)
                        note_pairs(sp, pairs_now)
            if pairs_now is not None:
                pairs_seen = pairs_now
            # 3. harvest + retire finished slots (they refill next iteration)
            finished = [s for s in occ if not active_now[s]]
            with self._span("serve.harvest", finished=len(finished)):
                if finished:
                    gen_counts = np.asarray(state["gen_count"])
                    gen_buf = np.asarray(state["gen_buf"])
                    for s in finished:
                        req, ttft = meta.pop(s)
                        del admitted_at[s]
                        sched.retire(s)
                        g = int(gen_counts[s])
                        e2e = now() - req.arrival_s
                        results[req.id] = RequestResult(
                            id=req.id,
                            tokens=list(req.prompt) + gen_buf[s, :g].tolist(),
                            prompt_len=len(req.prompt),
                            gen_len=g,
                            ttft_s=ttft,
                            e2e_s=e2e,
                        )
                        reg.histogram("serve.e2e_ms").observe(e2e * 1e3)
        wall_s = now()
        out = [results[r.id] for r in ordered]
        gen_total = sum(r.gen_len for r in out)
        occupancy = (ticks_active / ticks_total) if ticks_total else 0.0
        tokens_per_s = (gen_total / wall_s) if wall_s > 0 else 0.0
        reg.counter("serve.decode_steps").inc(decode_steps)
        reg.gauge("serve.slot_occupancy").set(occupancy)
        reg.gauge("serve.tokens_per_s").set(tokens_per_s)
        return ServeReport(
            results=out,
            wall_s=wall_s,
            tokens_per_s=tokens_per_s,
            ttft_ms=_percentiles_ms([r.ttft_s for r in out]),
            e2e_ms=_percentiles_ms([r.e2e_s for r in out]),
            slot_occupancy=occupancy,
            prefill_compiles=self.prefill_compiles,
            decode_steps=decode_steps,
            coded=guard.stats() if guard is not None else None,
        )
