"""Continuous-batching serving tier (ISSUE 9).

Acceptance:
* ``prefill_into_cache`` writes the prompt's K/V bit-exactly equal to the
  per-token refeed it replaces on every layer's prompt-region rows (the
  padded bucket tail is never attended — decode overwrites a position
  before reading it), leaving every other slot's cache row untouched.
* Greedy decode of N staggered requests through the slot scheduler is
  token-identical to the same prompts run one-at-a-time through the
  compiled prefill+decode path — dense and MLA+MoE variants, plus an
  8-forced-host-device (2×4 data×model) mesh variant in a subprocess.
* The fixed-batch ``Engine`` reports generated-tokens-only throughput and
  per-sequence EOS-trimmed ``lengths``.
"""

import functools
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import smoke_config
from repro.models import build_model
from repro.obs.metrics import MetricsRegistry
from repro.serve import (
    ContinuousEngine,
    DEFAULT_BUCKETS,
    Engine,
    LengthBand,
    Request,
    SlotScheduler,
    bucket_for,
    poisson_trace,
)
from repro.train.train_loop import make_decode_step, make_prefill_step

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PROMPTS = [[5, 9, 2, 7, 1], [3, 3, 8], [11, 4, 6, 2, 9, 10, 1], [2], [7, 5, 5, 5, 1, 2]]


@functools.lru_cache(maxsize=4)
def _smoke(arch: str):
    cfg = smoke_config(arch).replace(n_layers=2)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    return cfg, model, params


def _one_at_a_time(model, params, prompts, max_new, buckets, max_len):
    """Reference: each prompt alone through the compiled prefill graph +
    B=1 decode steps (greedy). The engine must reproduce this exactly."""
    pf = jax.jit(make_prefill_step(model, into_cache=True))
    dec = jax.jit(make_decode_step(model))
    V = model.cfg.vocab_size
    out = []
    for p in prompts:
        b = bucket_for(len(p), buckets)
        cache = model.init_cache(1, max_len)
        tb = np.zeros((1, b), np.int32)
        tb[0, : len(p)] = p
        last, cache = pf(params, cache, jnp.asarray(tb), jnp.int32(0), jnp.int32(len(p)))
        toks = [int(jnp.argmax(last[0, :V]))]
        pos = len(p)
        for _ in range(max_new - 1):
            lg, cache = dec(
                params, cache,
                jnp.asarray([[toks[-1]]], jnp.int32),
                jnp.asarray([pos], jnp.int32),
            )
            toks.append(int(jnp.argmax(lg[0, 0, :V])))
            pos += 1
        out.append(list(p) + toks)
    return out


# ---------------------------------------------------------------------------
# scheduler + traffic units
# ---------------------------------------------------------------------------


def test_bucket_for_rounds_up_and_bounds():
    assert bucket_for(1) == 32 and bucket_for(32) == 32
    assert bucket_for(33) == 64 and bucket_for(129) == 256
    assert bucket_for(5, (8, 16)) == 8
    with pytest.raises(ValueError):
        bucket_for(300, DEFAULT_BUCKETS)
    with pytest.raises(ValueError):
        bucket_for(0)


def test_scheduler_fifo_arrival_gating_and_refill():
    s = SlotScheduler(2)
    for i, arr in enumerate([0.0, 0.0, 0.0, 5.0]):
        s.submit(Request(id=f"r{i}", prompt=[1], arrival_s=arr))
    a = s.next_assignment(now_s=0.0)
    b = s.next_assignment(now_s=0.0)
    assert a is not None and b is not None
    assert a[1].id == "r0" and a[0] == 0
    assert b[1].id == "r1" and b[0] == 1
    # pool full: r2 waits even though it has arrived
    assert s.next_assignment(now_s=0.0) is None
    assert s.pending == 2 and s.occupied == [0, 1] and s.has_work
    # retiring slot 0 lets r2 in — mid-decode refill, FIFO order
    assert s.retire(0).id == "r0"
    c = s.next_assignment(now_s=0.0)
    assert c is not None and c[0] == 0 and c[1].id == "r2"
    # r3 hasn't arrived yet at t=0, but is assignable at t=5
    s.retire(1)
    assert s.next_assignment(now_s=0.0) is None
    assert s.next_arrival_s() == 5.0
    d = s.next_assignment(now_s=5.0)
    assert d is not None and d[1].id == "r3"
    s.retire(d[0])
    s.retire(0)
    assert not s.has_work and s.free == [0, 1]


def test_poisson_trace_seeded_and_mixed():
    mix = (LengthBand(2, 4, 0.5), LengthBand(5, 9, 0.5))
    a = poisson_trace(32, 100.0, mix=mix, max_new_tokens=8, seed=3)
    b = poisson_trace(32, 100.0, mix=mix, max_new_tokens=8, seed=3)
    assert [(r.prompt, r.arrival_s, r.max_new_tokens) for r in a] == [
        (r.prompt, r.arrival_s, r.max_new_tokens) for r in b
    ]
    arr = [r.arrival_s for r in a]
    assert arr == sorted(arr) and arr[0] > 0
    for r in a:
        assert 2 <= len(r.prompt) <= 9
        assert 4 <= r.max_new_tokens <= 8
    # both bands actually drawn from
    assert {len(r.prompt) <= 4 for r in a} == {True, False}
    c = poisson_trace(32, 100.0, mix=mix, max_new_tokens=8, seed=4)
    assert [r.prompt for r in a] != [r.prompt for r in c]


# ---------------------------------------------------------------------------
# prefill graph correctness
# ---------------------------------------------------------------------------


def test_prefill_into_cache_bitexact_vs_refeed():
    """One-pass prefill writes byte-identical prompt-region K/V to the
    per-token refeed it replaces, into the right slot, touching nothing
    else. (Bucket-tail rows beyond plen are scratch: decode overwrites a
    position before ever attending it.)"""
    cfg, model, params = _smoke("qwen3-1.7b")
    B, smax, bucket = 3, 32, 8
    prompt = [5, 9, 2, 7, 1]
    plen = len(prompt)

    step = jax.jit(make_decode_step(model))
    cache_refeed = model.init_cache(B, smax)
    for t in range(plen):
        toks = np.zeros((B,), np.int32)
        toks[1] = prompt[t]
        logits_r, cache_refeed = step(
            params, cache_refeed, jnp.asarray(toks)[:, None],
            jnp.full((B,), t, jnp.int32),
        )

    pf = jax.jit(make_prefill_step(model, into_cache=True))
    cache_init = model.init_cache(B, smax)
    tb = np.zeros((1, bucket), np.int32)
    tb[0, :plen] = prompt
    last, cache_pf = pf(
        params, cache_init, jnp.asarray(tb), jnp.int32(1), jnp.int32(plen)
    )

    ra, rb = jax.tree.flatten(cache_refeed)[0], jax.tree.flatten(cache_pf)[0]
    ri = jax.tree.flatten(model.init_cache(B, smax))[0]
    for leaf_r, leaf_p, leaf_0 in zip(ra, rb, ri):
        r, p, z = (np.asarray(x) for x in (leaf_r, leaf_p, leaf_0))
        # layout (R, B, Smax, ...): prompt region of slot 1 bit-exact
        np.testing.assert_array_equal(r[:, 1, :plen], p[:, 1, :plen])
        # every other slot untouched (still the init value)
        np.testing.assert_array_equal(p[:, 0], z[:, 0])
        np.testing.assert_array_equal(p[:, 2], z[:, 2])
    # same first-token distribution argmax as the refeed's last step
    V = cfg.vocab_size
    assert int(jnp.argmax(last[0, :V])) == int(jnp.argmax(logits_r[1, 0, :V]))


def test_prefill_unsupported_kinds_fall_back():
    cfg, model, params = _smoke("rwkv6-3b")
    assert not model.supports_prefill
    with pytest.raises(NotImplementedError):
        ContinuousEngine(model, params, n_slots=2, max_len=32)
    with pytest.raises(NotImplementedError):
        model.prefill_into_cache(params, model.init_cache(1, 8), jnp.zeros((1, 8), jnp.int32), 0)


# ---------------------------------------------------------------------------
# continuous batching == one-at-a-time (the tentpole invariant)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["qwen3-1.7b", "deepseek-v3-671b"])
def test_continuous_matches_one_at_a_time(arch):
    """N staggered requests through 2 slots (forcing mid-decode refills)
    produce token-for-token what each prompt produces alone through the
    compiled prefill+decode path. The serving programs run MoE layers
    dropless, so no batch size can make the batched run diverge
    (``test_deepseek_v3.py`` checks 8 slots, where a capacity would drop)."""
    cfg, model, params = _smoke(arch)
    max_new, buckets, max_len = 6, (8, 16), 32
    reqs = [
        Request(id=f"r{i}", prompt=p, max_new_tokens=max_new)
        for i, p in enumerate(PROMPTS)
    ]
    eng = ContinuousEngine(
        model, params, n_slots=2, max_len=max_len, buckets=buckets,
        max_new_tokens=8, metrics=MetricsRegistry(),
    )
    rep = eng.serve(reqs, greedy=True, sync_every=2)
    want = _one_at_a_time(model, params, PROMPTS, max_new, buckets, max_len)
    got = [r.tokens for r in rep.results]
    assert got == want
    assert rep.prefill_compiles <= len(buckets)
    assert all(r.gen_len == max_new for r in rep.results)
    assert all(r.ttft_s >= 0 and r.e2e_s >= r.ttft_s for r in rep.results)


def test_sampled_decoding_batch_invariant():
    """ISSUE 10 fix: sampled decoding (temperature > 0) draws token i of a
    request from fold_in(request_key, i) — a per-slot stream independent
    of batch composition — so slot-scheduled output is token-identical to
    the same prompts served one at a time, like greedy already was."""
    cfg, model, params = _smoke("qwen3-1.7b")
    reqs = [
        Request(id=f"r{i}", prompt=p, max_new_tokens=5)
        for i, p in enumerate(PROMPTS)
    ]
    batched = ContinuousEngine(
        model, params, n_slots=3, max_len=32, buckets=(8, 16),
        max_new_tokens=8, metrics=MetricsRegistry(),
    )
    solo = ContinuousEngine(
        model, params, n_slots=1, max_len=32, buckets=(8, 16),
        max_new_tokens=8, metrics=MetricsRegistry(),
    )
    rep = batched.serve(reqs, greedy=False, seed=3, temperature=0.8, sync_every=2)
    got = {r.id: r.tokens for r in rep.results}
    for req in reqs:
        one = solo.serve([req], greedy=False, seed=3, temperature=0.8)
        assert got[req.id] == one.results[0].tokens, req.id
    # an explicit per-request seed overrides the id-derived stream
    seeded = [
        Request(id=f"s{i}", prompt=p, max_new_tokens=5, seed=77)
        for i, p in enumerate(PROMPTS[:2])
    ]
    rep2 = batched.serve(seeded, greedy=False, seed=3, temperature=0.8)
    same_prompt = [
        Request(id="other-id", prompt=PROMPTS[0], max_new_tokens=5, seed=77)
    ]
    rep3 = solo.serve(same_prompt, greedy=False, seed=3, temperature=0.8)
    assert rep2.results[0].tokens == rep3.results[0].tokens
    # temperature must be positive when sampling
    with pytest.raises(ValueError, match="temperature"):
        batched.serve(reqs, greedy=False, temperature=0.0)


def test_continuous_eos_trims_generation():
    cfg, model, params = _smoke("qwen3-1.7b")
    buckets, max_len, max_new = (8,), 24, 6
    reqs = [Request(id=f"r{i}", prompt=p, max_new_tokens=max_new)
            for i, p in enumerate(PROMPTS[:3])]
    eng = ContinuousEngine(
        model, params, n_slots=2, max_len=max_len, buckets=buckets,
        max_new_tokens=8, metrics=MetricsRegistry(),
    )
    free = eng.serve(reqs, greedy=True, sync_every=2)
    # pick a token request 0 actually generates as EOS and re-serve: the
    # sequence must stop at its FIRST occurrence (EOS token included),
    # others unchanged unless they emit it too
    r0 = free.results[0]
    gen0 = r0.tokens[r0.prompt_len :]
    eos = gen0[2]
    first = gen0.index(eos)
    rep = eng.serve(reqs, greedy=True, eos_id=eos, sync_every=2)
    t0 = rep.results[0]
    assert t0.gen_len == first + 1
    assert t0.tokens == r0.tokens[: r0.prompt_len + first + 1]
    for a, b in zip(rep.results, free.results):
        cut = a.prompt_len + a.gen_len
        assert a.tokens == b.tokens[:cut]
        assert a.gen_len == max_new or a.tokens[-1] == eos


def test_continuous_mesh_8_host_devices():
    """The 2×4 (data×model) forced-host mesh variant: same staggered trace,
    same tokens as the no-mesh reference."""
    code = """
    import numpy as np, jax, jax.numpy as jnp
    from repro.configs import smoke_config
    from repro.configs.base import ShapeSpec
    from repro.launch.mesh import make_mesh
    from repro.launch.profiles import BASELINE, rules_for
    from repro.models import build_model
    from repro.obs.metrics import MetricsRegistry
    from repro.serve import ContinuousEngine, Request

    assert jax.device_count() == 8
    cfg = smoke_config("qwen3-1.7b").replace(n_layers=2)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    prompts = [[5, 9, 2, 7, 1], [3, 3, 8], [11, 4, 6, 2, 9, 10, 1], [2],
               [7, 5, 5, 5, 1, 2]]
    reqs = [Request(id=f"r{i}", prompt=p, max_new_tokens=6)
            for i, p in enumerate(prompts)]

    mesh = make_mesh((2, 4), ("data", "model"))
    rules = rules_for(cfg, ShapeSpec("serve-test", "decode", 32, 4), BASELINE)
    meshed = ContinuousEngine(
        model, params, n_slots=4, max_len=32, buckets=(8, 16),
        max_new_tokens=8, mesh=mesh, rules=rules, metrics=MetricsRegistry())
    plain = ContinuousEngine(
        model, params, n_slots=2, max_len=32, buckets=(8, 16),
        max_new_tokens=8, metrics=MetricsRegistry())
    got = [r.tokens for r in meshed.serve(reqs, greedy=True, sync_every=2).results]
    want = [r.tokens for r in plain.serve(reqs, greedy=True, sync_every=3).results]
    assert got == want, (got, want)
    print("MESH-OK")
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert r.returncode == 0, f"child failed:\nSTDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    assert "MESH-OK" in r.stdout


# ---------------------------------------------------------------------------
# fixed-batch engine satellites
# ---------------------------------------------------------------------------


def test_engine_lengths_and_generated_only_throughput():
    cfg, model, params = _smoke("qwen3-1.7b")
    reg = MetricsRegistry()
    eng = Engine(model, params, max_len=24, metrics=reg)
    res = eng.generate(PROMPTS[:3], max_new_tokens=4)
    plens = np.array([len(p) for p in PROMPTS[:3]])
    np.testing.assert_array_equal(res.prompt_lens, plens)
    np.testing.assert_array_equal(res.lengths, plens + 4)
    # throughput counts generated tokens only, not prompt-refeed steps
    snap = reg.snapshot()
    wall_s = snap["serve.generate_ms"]["value"] / 1e3
    tps = snap["serve.tokens_per_s"]["value"]
    assert tps == pytest.approx(12 / wall_s, rel=1e-6)
    assert tps < res.steps * len(PROMPTS[:3]) / wall_s  # old formula inflated


def test_engine_lengths_eos_trimmed():
    cfg, model, params = _smoke("qwen3-1.7b")
    eng = Engine(model, params, max_len=24, metrics=MetricsRegistry())
    free = eng.generate(PROMPTS[:2], max_new_tokens=5)
    p0 = len(PROMPTS[0])
    gen0 = free.tokens[0, p0 : p0 + 5].tolist()
    eos = gen0[1]  # a token seq 0 actually generates
    first = gen0.index(eos)
    reg = MetricsRegistry()
    eng2 = Engine(model, params, max_len=24, metrics=reg)
    res = eng2.generate(PROMPTS[:2], max_new_tokens=5, eos_id=eos,
                        eos_check_every=100)
    # trimmed at the first EOS occurrence, the EOS token itself counted
    assert res.lengths[0] == p0 + first + 1
    for b in range(2):
        assert res.lengths[b] <= len(PROMPTS[b]) + 5
    gen_total = int((res.lengths - res.prompt_lens).sum())
    snap = reg.snapshot()
    wall_s = snap["serve.generate_ms"]["value"] / 1e3
    assert snap["serve.tokens_per_s"]["value"] == pytest.approx(
        gen_total / wall_s, rel=1e-6
    )


# ---------------------------------------------------------------------------
# observability + harness record gating
# ---------------------------------------------------------------------------


def test_continuous_metrics_and_report():
    cfg, model, params = _smoke("qwen3-1.7b")
    reg = MetricsRegistry()
    eng = ContinuousEngine(
        model, params, n_slots=2, max_len=32, buckets=(8, 16),
        max_new_tokens=8, metrics=reg,
    )
    reqs = [Request(id=f"r{i}", prompt=p, max_new_tokens=4)
            for i, p in enumerate(PROMPTS)]
    rep = eng.serve(reqs, greedy=True, sync_every=2)
    snap = reg.snapshot()
    assert snap["serve.prefill_compiles"]["value"] == rep.prefill_compiles
    assert rep.prefill_compiles <= 2
    assert snap["serve.decode_steps"]["value"] == rep.decode_steps
    assert snap["serve.ttft_ms"]["count"] == len(reqs)
    assert snap["serve.e2e_ms"]["count"] == len(reqs)
    assert 0.0 <= rep.slot_occupancy <= 1.0
    assert rep.tokens_per_s > 0
    rec = rep.to_record()
    assert rec["ttft_ms"]["p50"] <= rec["ttft_ms"]["p99"]
    # re-serving reuses the compiled graphs: no new prefill compiles
    eng.serve(reqs, greedy=True, sync_every=2)
    assert eng.prefill_compiles == rep.prefill_compiles
