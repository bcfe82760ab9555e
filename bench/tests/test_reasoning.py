"""The DeepSeek-V3 EP32 reasoning cell at a tiny size on the CPU: a rehearsal
through the harness's driver path (untraced and traced), a planted fault
(one routed expert's gate zeroed) that the check must catch, the counted
work against the program's own parameters, and the cell's readers on
synthetic evidence.

The program config is ``deepseek-v3-ep32`` cut to a CPU's size with its
structure kept (a dense layer then MoE layers, MLA with YaRN, 2 of 8
sigmoid-routed experts held, in 2 groups of which a token uses 1, a shared
expert), wide enough that an expert's part of the result is a large share
of it; the harness's ``get`` is pointed at it. Run with
``pytest bench/tests``."""

import json

import pytest

from bench import counts_mla_moe as counts
from bench.harness import BENCH, Evidence, load_module, run_cell
from bench.trace_reduce import Device, TraceSummary

CELL = "deepseek-v3-ep32.reasoning"

#: the bench config's keys at the tiny cut's sizes
TINY_CONFIG = {
    "num_hidden_layers": 3, "first_k_dense_replace": 1, "hidden_size": 256,
    "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 32,
    "intermediate_size": 256, "moe_intermediate_size": 128, "vocab_size": 503,
    "q_lora_rank": 64, "kv_lora_rank": 32, "qk_nope_head_dim": 32,
    "qk_rope_head_dim": 16, "v_head_dim": 32, "router_experts": 8,
    "n_routed_experts": 2, "experts_held_first": 0, "num_experts_per_tok": 2,
    "n_group": 2, "topk_group": 1,
}
TINY_TRAFFIC = {
    "arrivals": {"pattern": "backlog", "requests": 2048},
    "prompt": {"dist": "lognormal", "median": 24, "sigma": 0.8, "min": 4, "max": 60},
    "output": {"dist": "lognormal", "median": 16, "sigma": 0.6, "min": 8, "max": 48},
    "block": 4,
    "pool": {"slots": 4, "max_len": 128, "buckets": [32, 64], "sync_every": 4},
    "check": {"served_tokens": 60, "ref_len": 112},
}


def tiny_program():
    from repro.configs import get
    from repro.configs.base import MLAConfig, MoEConfig

    full = get("deepseek-v3-ep32")
    return full.replace(
        name="deepseek-v3-ep32-tiny", n_layers=3, d_model=256, n_heads=4, n_kv_heads=4,
        head_dim=32, d_ff=256, vocab_size=503, vocab_padded=0, remat="none",
        mla=MLAConfig(q_lora_rank=64, kv_lora_rank=32, qk_nope_head_dim=32,
                      qk_rope_head_dim=16, v_head_dim=32),
        moe=MoEConfig(**{**full.moe.__dict__, "n_experts": 8, "top_k": 2, "expert_ff": 128,
                         "shared_ff": 128, "first_dense": 1, "dense_ff": 256, "n_group": 2,
                         "topk_group": 1, "n_held": 2}))


@pytest.fixture
def tiny(monkeypatch):
    """The harness builds the tiny cut of the program config."""
    import repro.configs as configs

    small = tiny_program()
    monkeypatch.setattr(configs, "get", lambda name: small)
    return small


def _run(seed, trace=False):
    return run_cell(CELL, seed, 3.0, trace, require_tpu=False,
                    overrides={"config": TINY_CONFIG, "traffic": TINY_TRAFFIC}, cache=False)


def test_reasoning_cell_end_to_end(tiny, capsys):
    line = _run(2**31 + 99)
    assert line["correct"], line["checks"]
    assert set(line["checks"]) == {"max_logit_shortfall", "no_request_finished"}
    assert set(line["metrics"]) == {"tokens_per_s", "setup_s"}
    assert "compiles inside the window: 0" in capsys.readouterr().out


def test_traced_rehearsal_reports_the_span_readers(tiny):
    """A CPU trace has no TPU planes: the span readers report, the readers
    of the device trace do not."""
    line = _run(2**31 + 7, trace=True)
    assert line["correct"], line["checks"]
    assert set(line["metrics"]) == {"models.moe_imbalance.reasoning",
                                    "serve.prefill_ms.reasoning"}
    assert line["metrics"]["models.moe_imbalance.reasoning"]["value"] >= 1.0


def test_a_routed_experts_gate_zeroed(tiny, monkeypatch):
    """One held expert's gate zeroed in the program (its pairs add nothing)
    turns ``correct`` false."""
    from repro.models import layers

    real = layers.moe_route

    def without_expert_0(logits, mc, select_bias=None):
        gates, eidx = real(logits, mc, select_bias)
        return gates * (eidx != mc.held_first), eidx

    monkeypatch.setattr(layers, "moe_route", without_expert_0)
    line = _run(2**31 + 99)
    assert not line["correct"]
    c = line["checks"]["max_logit_shortfall"]
    assert c["value"] > c["limit"]


# -- counted work -------------------------------------------------------------


def _full_config():
    return json.loads((BENCH / "configs" / "deepseek-v3-ep32.json").read_text())


def test_param_count_equals_the_programs_leaves():
    import jax
    import numpy as np

    from repro.models import build_model

    model = build_model(tiny_program())
    shapes = jax.eval_shape(lambda: model.init(jax.random.key(0)))
    leaves = jax.tree.leaves(shapes)
    config = dict(_full_config(), **TINY_CONFIG)
    vocab = model.cfg.vocab_padded
    assert counts.param_count(config, vocab=vocab) == sum(int(np.prod(s.shape)) for s in leaves)
    assert counts.weight_bytes(config, vocab=vocab) == sum(
        int(np.prod(s.shape)) * s.dtype.itemsize for s in leaves)


def test_full_size_counts():
    import jax
    import numpy as np

    from repro.configs import get
    from repro.models import build_model

    cfg = _full_config()
    shapes = build_model(get("deepseek-v3-ep32")).param_specs()[0]
    leaves = jax.tree.leaves(shapes)
    # the program pads the vocabulary slice to 16,384 rows
    assert counts.param_count(cfg, vocab=16_384) == sum(int(np.prod(s.shape)) for s in leaves)
    # 3 dense and 4 MoE layers (8 experts held) of ≈ 583.5 M each, and a
    # 16,160-row embedding and head
    assert counts.param_count(cfg) == 4_323_401_728
    assert counts.weight_bytes(cfg) == 8_661_485_568
    assert counts.latent_row_bytes(cfg) == 7 * 576 * 2
    assert counts.tick_weight_bytes(cfg, 96) == (
        counts.weight_bytes(cfg) - 16_160 * 7168 * 2 + 96 * 7168 * 2)
    # a chunk of 2 ticks, 3 slots at positions summing to 100
    assert counts.chunk_bytes(cfg, 96, 2, 3, 100) == (
        2 * counts.tick_weight_bytes(cfg, 96) + (103 + 106) * 8064)


# -- readers on synthetic evidence --------------------------------------------


def _reader(name):
    return load_module(BENCH / "metrics" / f"{name}.py", f"test_reader_{name}")


def _ev(spans=(), trace=None, peaks=None):
    return Evidence(cell={}, config=_full_config(), traffic={"pool": {"slots": 96}},
                    facts={}, spans=list(spans), trace=trace, peaks=peaks or {})


def _trace(ticks_ns, window=(0, 10_000_000_000)):
    dev = Device("/device:TPU:0", ops=[("jit_tick/fusion", s, e) for s, e in ticks_ns],
                 modules=[("jit_tick(123)", s, e) for s, e in ticks_ns]
                 + [("jit_prefill(9)", 9_000_000_000, 9_100_000_000)])
    return TraceSummary(window=window, devices=[dev], host=[])


CHUNKS = [("serve.decode_chunk", 0.2, {"ticks": 4, "occupied": 96, "cache_rows": 40_000,
                                        "moe_pairs": 3_000, "moe_pairs_max": 600}),
          ("serve.decode_chunk", 0.2, {"ticks": 4, "occupied": 96, "cache_rows": 40_384,
                                        "moe_pairs": 3_200, "moe_pairs_max": 500}),
          ("serve.prefill", 0.03, {"bucket": 512}), ("serve.prefill", 0.01, {"bucket": 256}),
          ("serve.prefill", 0.02, {"bucket": 256})]
#: eight tick runs of 40, 50, ... ms
TICKS = [(i * 100_000_000, i * 100_000_000 + (40 + 10 * (i % 3)) * 1_000_000) for i in range(8)]


def test_decode_tick_reader():
    read = _reader("models.decode_tick_ms.reasoning").read
    assert read(_ev(trace=_trace(TICKS))) == pytest.approx(50.0)
    assert read(_ev()) is None
    assert read(_ev(trace=_trace([]))) is None


def test_decode_roofline_reader():
    read = _reader("models.decode_roofline.reasoning").read
    peaks = {"hbm_bytes_per_s": 819e9}
    cfg = _full_config()
    need = sum(counts.chunk_bytes(cfg, 96, a["ticks"], a["occupied"], a["cache_rows"])
               for n, _, a in CHUNKS if n == "serve.decode_chunk")
    per_tick = sum(e - s for s, e in TICKS) / len(TICKS) / 1e9
    want = need / 8 / 819e9 / per_tick * 100
    assert read(_ev(CHUNKS, _trace(TICKS), peaks)) == pytest.approx(want)
    assert 0 < want < 100
    assert read(_ev(CHUNKS, None, peaks)) is None  # no device trace
    assert read(_ev(CHUNKS[2:], _trace(TICKS), peaks)) is None  # no chunk spans


def test_moe_imbalance_reader():
    read = _reader("models.moe_imbalance.reasoning").read
    assert read(_ev(CHUNKS)) == pytest.approx(1_100 / (6_200 / 8))
    # a program without the expert counters reports nothing
    plain = [(n, d, {k: v for k, v in a.items() if not k.startswith("moe")})
             for n, d, a in CHUNKS]
    assert read(_ev(plain)) is None


def test_prefill_reader():
    read = _reader("serve.prefill_ms.reasoning").read
    assert read(_ev(CHUNKS)) == pytest.approx(20.0)
    assert read(_ev(CHUNKS[:2])) is None


def test_idle_share_reader():
    read = _reader("device.idle_share.reasoning").read
    busy = sum(e - s for s, e in TICKS)
    assert read(_ev(trace=_trace(TICKS))) == pytest.approx((1 - busy / 1e10) * 100)
    assert read(_ev()) is None
