"""DeepSeek-V3 through the serving path, against the plain reference
(``tests/ref_mla_moe.py``) on seeded random weights, at a CPU's size.

* prefill into a decode slot, then decode through the cache, gives the
  reference's full-forward logits (float32 program: tight; bfloat16: the
  serving dtype), and ``ContinuousEngine``'s served tokens sit at the
  reference's top;
* the router is noaux_tc, checked against a line-by-line numpy
  transcription, with ties and with one group;
* YaRN's frequencies and softmax scale are DeepSeek's;
* the held share: 32 chips' held-expert parts, with the shared expert
  counted once, add up to the uncut layer;
* batched serving equals one-at-a-time at 8 slots, where the capacity path
  would have dropped pairs;
* ``launch/serve.py --arch deepseek-v3-ep32`` serves at smoke size.
"""

import math
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import ref_mla_moe as ref
from repro.configs import get, smoke_config
from repro.configs.base import MoEConfig
from repro.models import build_model, layers
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import Tracer
from repro.serve import ContinuousEngine, Request
from repro.train.train_loop import make_decode_step, make_prefill_step

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _model(dtype="bfloat16", seed=0, arch="deepseek-v3-ep32"):
    cfg = smoke_config(arch).replace(dtype=dtype)
    model = build_model(cfg)
    return cfg, model, ref.seeded_params(model, seed)


# ---------------------------------------------------------------------------
# prefill + decode through the cache == the reference's full forward
# ---------------------------------------------------------------------------


def _served_logits(model, params, seq, plen, bucket, slot=1, slots=3):
    """Logits of positions plen-1 .. len(seq)-2: the prompt prefilled into
    ``slot`` of a ``slots``-row cache, the rest fed one token at a time."""
    cfg = model.cfg
    pf = jax.jit(make_prefill_step(model, into_cache=True))
    dec = jax.jit(make_decode_step(model))
    cache = model.init_cache(slots, 64)
    tb = np.zeros((1, bucket), np.int32)
    tb[0, :plen] = seq[:plen]
    last, cache = pf(params, cache, jnp.asarray(tb), jnp.int32(slot), jnp.int32(plen))
    out = [np.asarray(last[0, : cfg.vocab_size], np.float32)]
    for p in range(plen, len(seq) - 1):
        toks = np.zeros((slots, 1), np.int32)
        toks[slot, 0] = seq[p]
        pos = np.zeros((slots,), np.int32)
        pos[slot] = p
        lg, cache = dec(params, cache, jnp.asarray(toks), jnp.asarray(pos))
        out.append(np.asarray(lg[slot, 0, : cfg.vocab_size], np.float32))
    return np.stack(out)


# Gaps as a share of the largest |logit|, over seeds 0-5 on the CPU.
# float32: the program and the reference differ only in the order of f32
# sums (absorbed against decompressed attention, grouped expert products):
# 2.2e-7 to 3.6e-7, so 1e-5. bfloat16, the serving dtype: weights and
# activations round to 8 bits of mantissa through 2 layers: 4.2e-3 to
# 6.0e-3, so 2e-2.
@pytest.mark.parametrize("dtype,tol", [("float32", 1e-5), ("bfloat16", 2e-2)])
def test_prefill_then_decode_matches_reference(dtype, tol):
    cfg, model, params = _model(dtype, seed=3)
    rng = np.random.default_rng(3)
    seq = rng.integers(1, cfg.vocab_size, size=20).tolist()
    plen = 7
    got = _served_logits(model, params, seq, plen, bucket=8)
    want = np.asarray(ref.forward(params, cfg, seq))[plen - 1 : len(seq) - 1]
    scale = np.abs(want).max()
    assert np.abs(got - want).max() <= tol * scale, np.abs(got - want).max() / scale


def test_engine_serves_the_references_top_tokens():
    """Greedy tokens through ``ContinuousEngine`` (4 slots, staggered
    requests) lie at the top of the float32 reference: each served token's
    logit is within 2% of the largest |logit| of the best (bfloat16
    near-ties), and most are the argmax."""
    cfg, model, params = _model("bfloat16", seed=5)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, cfg.vocab_size, size=n).tolist() for n in (5, 9, 3, 12, 7, 6)]
    eng = ContinuousEngine(model, params, n_slots=4, max_len=48, buckets=(8, 16),
                           max_new_tokens=12, metrics=MetricsRegistry())
    rep = eng.serve([Request(id=f"r{i}", prompt=p, max_new_tokens=10)
                     for i, p in enumerate(prompts)], greedy=True, sync_every=3)
    top = total = 0
    for r in rep.results:
        served = r.tokens[r.prompt_len:]
        lg = np.asarray(ref.forward(params, cfg, r.tokens))[r.prompt_len - 1 : -1]
        gap = (lg.max(-1) - lg[np.arange(len(served)), served]) / np.abs(lg).max(-1)
        assert gap.max() <= 0.02, (r.id, gap.max())
        top += int((gap == 0).sum())
        total += len(served)
    assert top >= 0.9 * total


# ---------------------------------------------------------------------------
# the router: noaux_tc
# ---------------------------------------------------------------------------


def _topk_idx(x, k):
    """Indices of the k largest along the last axis, ties to the lower index."""
    return np.argsort(-x, axis=-1, kind="stable")[..., :k]


def noaux_tc_numpy(logits, bias, n_group, topk_group, top_k, norm, scale):
    """MoEGate.forward of modeling_deepseek.py, line by line, in numpy."""
    n, E = logits.shape
    scores = 1.0 / (1.0 + np.exp(-logits.astype(np.float64)))  # logits.sigmoid()
    scores_for_choice = scores + bias[None, :]
    grouped = scores_for_choice.reshape(n, n_group, -1)
    group_scores = np.take_along_axis(grouped, _topk_idx(grouped, 2), -1).sum(-1)  # [n, n_group]
    group_idx = _topk_idx(group_scores, topk_group)  # [n, top_k_group]
    group_mask = np.zeros_like(group_scores)  # [n, n_group]
    np.put_along_axis(group_mask, group_idx, 1, axis=1)  # scatter_
    score_mask = np.repeat(group_mask[:, :, None], E // n_group, axis=2).reshape(n, -1)
    tmp_scores = np.where(score_mask.astype(bool), scores_for_choice, -np.inf)  # masked_fill
    topk_idx = _topk_idx(tmp_scores, top_k)
    topk_weight = np.take_along_axis(scores, topk_idx, 1)  # scores.gather
    if top_k > 1 and norm:
        topk_weight = topk_weight / (topk_weight.sum(-1, keepdims=True) + 1e-20)
    return topk_weight * scale, topk_idx


def _mc(E, n_group, topk_group, k, norm=True, scale=2.5):
    return MoEConfig(n_experts=E, top_k=k, expert_ff=8, scoring="sigmoid", norm_topk_prob=norm,
                     n_group=n_group, topk_group=topk_group, routed_scaling_factor=scale)


@pytest.mark.parametrize("E,n_group,topk_group,k", [
    (256, 8, 4, 8),  # DeepSeek-V3's
    (16, 1, 1, 4),   # one group: no group limit
    (32, 4, 2, 3),
])
def test_router_matches_numpy_noaux_tc(E, n_group, topk_group, k):
    rng = np.random.default_rng(E + k)
    logits = rng.normal(0, 2, size=(64, E)).astype(np.float32)
    # exact ties: repeated logits within and across groups, and a row of
    # equal scores (every group ties; the lower indices win)
    logits[1] = logits[1, 0]
    logits[2, : E // 2] = logits[2, E // 2:]
    logits[3, ::3] = 0.25
    bias = (0.05 * rng.normal(size=E)).astype(np.float32)
    bias[: E // 2] = bias[E // 2:]
    gates, eidx = layers.moe_route(jnp.asarray(logits), _mc(E, n_group, topk_group, k),
                                   jnp.asarray(bias))
    want_g, want_i = noaux_tc_numpy(logits, bias, n_group, topk_group, k, True, 2.5)
    np.testing.assert_array_equal(np.sort(np.asarray(eidx), -1), np.sort(want_i, -1))
    order = lambda i: np.argsort(i, -1)  # noqa: E731
    np.testing.assert_allclose(np.take_along_axis(np.asarray(gates), order(np.asarray(eidx)), -1),
                               np.take_along_axis(want_g, order(want_i), -1), rtol=1e-6)
    # normalised and scaled: each row of gates sums to 2.5
    np.testing.assert_allclose(np.asarray(gates).sum(-1), 2.5, rtol=1e-6)
    # the kept experts lie in at most topk_group groups
    per_row = [len(set((row // (E // n_group)).tolist())) for row in np.asarray(eidx)]
    assert max(per_row) <= topk_group


def test_router_selection_bias_steers_choice_not_gates():
    """The bias picks experts; the gates are the unbiased scores."""
    E, k = 16, 2
    logits = np.zeros((1, E), np.float32)
    logits[0, :4] = [3.0, 2.0, 1.0, 0.5]
    bias = np.zeros(E, np.float32)
    bias[3] = 10.0  # expert 3 is chosen by its bias alone
    gates, eidx = layers.moe_route(jnp.asarray(logits), _mc(E, 1, 1, k, norm=False, scale=1.0),
                                   jnp.asarray(bias))
    assert sorted(np.asarray(eidx)[0].tolist()) == [0, 3]
    s = 1 / (1 + np.exp(-logits[0]))
    np.testing.assert_allclose(sorted(np.asarray(gates)[0]), sorted([s[0], s[3]]), rtol=1e-6)


# ---------------------------------------------------------------------------
# YaRN
# ---------------------------------------------------------------------------


def test_yarn_frequencies_and_scale():
    from repro.models.layers import rope_angles, rope_freqs
    from repro.models.mla import softmax_scale

    cfg = get("deepseek-v3-ep32")
    dr = cfg.mla.qk_rope_head_dim
    got = rope_freqs(dr, cfg.rope_theta, cfg.yarn)
    want = ref.yarn_inv_freq(dr, cfg.rope_theta, cfg.yarn)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    plain = 1.0 / 1e4 ** (np.arange(0, dr, 2) / dr)
    # the fastest dimensions keep their frequency, the slowest are divided by 40
    np.testing.assert_allclose(got[:10], plain[:10], rtol=1e-6)
    np.testing.assert_allclose(got[-4:], plain[-4:] / 40.0, rtol=1e-6)
    assert np.all((got <= plain * (1 + 1e-6)) & (got >= plain / 40 * (1 - 1e-6)))
    mscale = 0.1 * math.log(40) + 1.0
    assert softmax_scale(cfg) == pytest.approx(192 ** -0.5 * mscale**2, rel=1e-12)
    assert ref.softmax_scale(cfg) == pytest.approx(softmax_scale(cfg), rel=1e-12)
    # angles at a position are position × frequency
    cos, sin = rope_angles(jnp.asarray([3000]), dr, cfg.rope_theta, cfg.yarn)
    np.testing.assert_allclose(np.asarray(cos)[0], np.cos(3000 * want), atol=2e-3)
    # without YaRN the frequencies are the plain ones (qwen3 unchanged)
    q = get("qwen3-1.7b")
    assert q.yarn is None
    np.testing.assert_array_equal(
        rope_freqs(128, q.rope_theta),
        1.0 / (q.rope_theta ** (np.arange(0, 64, dtype=np.float32) * 2.0 / 128)))


# ---------------------------------------------------------------------------
# the held share: 32 chips' parts add up to the whole layer
# ---------------------------------------------------------------------------


def test_expert_shares_add_up_to_the_uncut_layer():
    """64 experts over 32 chips of 2: each chip's dropless layer computes
    its experts' part for the tokens routed to them, plus the shared
    expert; the 32 parts with the shared expert counted once equal the
    uncut reference layer, and the program's uncut dropless layer."""
    d, E, chips = 32, 64, 32
    whole = get("deepseek-v3-671b").replace(
        d_model=d, dtype="float32",
        moe=MoEConfig(n_experts=E, top_k=6, expert_ff=16, shared_ff=16, scoring="sigmoid",
                      norm_topk_prob=True, n_group=8, topk_group=3,
                      routed_scaling_factor=2.5))
    model = build_model(whole)
    k = jax.random.key(11)
    params = layers.moe_init(k, whole, jnp.float32)
    params = jax.tree.map(
        lambda a: 0.3 * jax.random.normal(jax.random.fold_in(k, a.size), a.shape), params)
    x = jax.random.normal(jax.random.fold_in(k, 1), (3, 10, d))
    assert model.cfg.moe.held == E
    want = ref.moe_layer(params, x.reshape(30, d), whole.moe)
    full, _, pairs_full = layers.moe_dropless(params, x, whole)
    np.testing.assert_allclose(np.asarray(full).reshape(30, d), np.asarray(want), rtol=1e-4,
                               atol=1e-4)
    n = E // chips
    shared = ref.moe_layer(params, x.reshape(30, d), whole.moe, 0, 0)  # the shared expert alone
    total = jnp.zeros_like(want)
    pairs = []
    for c in range(chips):
        share = whole.replace(moe=MoEConfig(**{**whole.moe.__dict__, "held_first": c * n,
                                                "n_held": n}))
        p = dict(params, **{w: params[w][c * n:(c + 1) * n] for w in ("w_gate", "w_up", "w_down")})
        out, _, pc = layers.moe_dropless(p, x, share)
        total = total + np.asarray(out).reshape(30, d) - shared
        pairs.append(np.asarray(pc))
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(want), rtol=1e-4, atol=1e-4)
    # every (token, expert) pair is computed once, on the chip that holds it
    np.testing.assert_array_equal(np.concatenate(pairs), np.asarray(pairs_full))
    assert int(np.asarray(pairs_full).sum()) == 30 * 6


# ---------------------------------------------------------------------------
# batched == one at a time, where a capacity would have dropped
# ---------------------------------------------------------------------------


def test_batched_equals_one_at_a_time_at_8_slots():
    """8 slots decode 16 (token, expert) pairs a tick over 8 experts: the
    capacity path's C = max(ceil(16 / 8 · 1.25), 4) = 4, and the held-pairs
    counter shows ticks where one expert took more. Dropless, batched
    serving still equals each prompt served alone."""
    from test_serve import _one_at_a_time

    cfg, model, params = _model("bfloat16", seed=2, arch="deepseek-v3-671b")
    assert cfg.moe.held == cfg.moe.n_experts == 8 and cfg.moe.top_k == 2
    rng = np.random.default_rng(2)
    prompts = [rng.integers(1, cfg.vocab_size, size=int(n)).tolist()
               for n in rng.integers(2, 15, size=12)]
    max_new, buckets, max_len = 8, (8, 16), 32
    tracer = Tracer()
    eng = ContinuousEngine(model, params, n_slots=8, max_len=max_len, buckets=buckets,
                           max_new_tokens=max_new, metrics=MetricsRegistry(), tracer=tracer)
    rep = eng.serve([Request(id=f"r{i:02d}", prompt=p, max_new_tokens=max_new)
                     for i, p in enumerate(prompts)], greedy=True, sync_every=1)
    want = _one_at_a_time(model, params, prompts, max_new, buckets, max_len)
    assert [r.tokens for r in rep.results] == want
    chunks = [s.attrs for s in tracer.spans if s.name == "serve.decode_chunk"]
    n_moe = cfg.n_layers - cfg.moe.first_dense
    assert all(a["moe_pairs"] == 8 * 2 * n_moe for a in chunks)
    assert max(a["moe_pairs_max"] for a in chunks) > 4


def test_chunk_span_reports_cache_rows_and_pairs_counter():
    """``cache_rows`` is the occupied slots' positions at the chunk's start;
    the counter ``models.moe.held_pairs`` sums the chunks' pairs."""
    cfg, model, params = _model("bfloat16", seed=4)
    reg, tracer = MetricsRegistry(), Tracer()
    eng = ContinuousEngine(model, params, n_slots=2, max_len=32, buckets=(8,),
                           max_new_tokens=8, metrics=reg, tracer=tracer)
    eng.serve([Request(id="a", prompt=[1, 2, 3], max_new_tokens=8),
               Request(id="b", prompt=[4, 5, 6, 7, 8], max_new_tokens=8)],
              greedy=True, sync_every=2)
    chunks = [s.attrs for s in tracer.spans if s.name == "serve.decode_chunk"]
    # both slots start at their prompt lengths and advance 2 a chunk
    assert [a["cache_rows"] for a in chunks] == [8, 12, 16, 20]
    pairs = sum(a["moe_pairs"] for a in chunks)
    assert reg.counter("models.moe.held_pairs").value == pairs > 0
    assert all(0 < a["moe_pairs_max"] <= a["moe_pairs"] for a in chunks)
    # a dense model's state and spans carry nothing of this
    dense = build_model(smoke_config("qwen3-1.7b"))
    d_eng = ContinuousEngine(dense, None, n_slots=2, max_len=32, buckets=(8,), max_new_tokens=8)
    assert "moe_pairs" not in jax.eval_shape(d_eng.init_state)[1]


# ---------------------------------------------------------------------------
# the launcher
# ---------------------------------------------------------------------------


def test_launch_serve_deepseek_v3_ep32_smoke(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.path.join(REPO, "src"),
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--arch", "deepseek-v3-ep32", "--smoke",
         "--prompts", "1,2,3;4,5,6,7", "--max-new", "5", "--max-len", "32"],
        env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "2 reqs" in r.stdout and "cli-1:" in r.stdout
