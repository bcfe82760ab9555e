"""CPU checks of the counted work: parameters, state limbs, LocalOp words,
the reference generator, and the traffic generator's fixed work."""

import json

import numpy as np
import pytest

from bench import counts, traffic
from bench.harness import BENCH
from bench.tests.tiny import CODED, TINY_MODEL


def tiny_config():
    cfg = json.loads((BENCH / "configs" / "qwen3-1.7b.json").read_text())
    cfg.update({k: v for k, v in TINY_MODEL.items() if k != "program_overrides"})
    return cfg


def tiny_model():
    from repro.configs import get
    from repro.models import build_model

    pcfg = get("qwen3-1.7b").replace(**TINY_MODEL["program_overrides"])
    return build_model(pcfg)


def test_param_count_equals_model_leaves():
    import jax

    model = tiny_model()
    shapes = jax.eval_shape(lambda: model.init(jax.random.key(0)))
    leaves = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    assert counts.param_count(tiny_config(), vocab=model.cfg.vocab_padded) == leaves


def test_full_size_counts():
    cfg = json.loads((BENCH / "configs" / "qwen3-1.7b.json").read_text())
    assert counts.param_count(cfg) == 1_720_574_976
    assert counts.shard_words(counts.pool_state_limbs(cfg, 4, 1024, 256), 3) == 78_294_374


def test_pool_limbs_equal_the_programs_shards():
    from repro.coded.rs_checkpoint import shard_state_limbs
    from repro.serve import ContinuousEngine

    model = tiny_model()
    eng = ContinuousEngine(model, params=None, n_slots=3, max_len=64, buckets=(32,),
                           max_new_tokens=16)
    shards, meta = shard_state_limbs(eng.init_state(), 3)
    assert meta.total == counts.pool_state_limbs(tiny_config(), 3, 64, 16)
    assert shards.shape[1] == counts.shard_words(meta.total, 3)


def test_localop_words_match_the_ir():
    from repro.coded.lagrange_compute import build_lcc, lcc_generator
    from repro.core.ir import CommRound, LocalOp
    from repro.core.schedule import plan_prepare_shoot

    got = counts.prepare_shoot_counts(4, 1)
    assert got == {"rounds": 2, "ppermutes": 2, "local_ops": 1,
                   "localop_words_per_word": 4, "wire_words_per_word": 2}
    plan = build_lcc(3, p=1, R=1)
    ir = plan_prepare_shoot(4, 1).to_ir(lcc_generator(plan), q=plan.q)
    ops = [s for s in ir.steps if isinstance(s, LocalOp)]
    assert sum(len(o.in_slots) + len(o.out_slots) for o in ops) == 4
    assert ops[0].coeffs.shape == (4, 2, 2)
    assert len([s for s in ir.steps if isinstance(s, CommRound)]) == 2


def test_reference_generator_equals_the_programs():
    from repro.coded.lagrange_compute import build_lcc, lcc_generator
    from bench.references import lcc_encode

    code = json.loads((BENCH / "configs" / "lcc-k3r1-ntt.json").read_text())
    plan = build_lcc(code["K"], p=code["p"], q=code["q"], R=code["R"])
    np.testing.assert_array_equal(lcc_encode.generator(code), lcc_generator(plan))


def test_reference_encode_equals_the_oracle():
    from repro.core.prepare_shoot import encode_oracle
    from bench.references import lcc_encode

    code = json.loads((BENCH / "configs" / "lcc-k3r1-ntt.json").read_text())
    rng = np.random.default_rng(0)
    x = rng.integers(0, 1 << 16, size=(4, 999), dtype=np.uint64)
    x[3] = 0
    want = encode_oracle(x, lcc_encode.generator(code), code["q"])
    np.testing.assert_array_equal(lcc_encode.encode(x.astype(np.uint32), code), want)
    assert lcc_encode.words_wrong(lcc_encode.encode_control(x, code), want) > 0


@pytest.mark.parametrize("seed", [0, 2**31 + 11, 987654321987])
def test_every_seed_gets_the_same_work(seed):
    base = traffic.generate(CODED, 1, 512)
    got = traffic.generate(CODED, seed, 512)
    n = CODED["arrivals"]["requests"]
    assert len(got) == len(base) == n
    assert [len(r.prompt) for r in got] != [len(r.prompt) for r in base]
    for lo in range(0, n, CODED["block"]):
        blk = slice(lo, lo + CODED["block"])
        assert sorted(len(r.prompt) for r in got[blk]) == sorted(len(r.prompt) for r in base[blk])
        assert sorted(r.max_new_tokens for r in got[blk]) == sorted(
            r.max_new_tokens for r in base[blk])
    assert all(r.arrival_s == 0.0 for r in got)
    assert all(CODED["prompt"]["min"] <= len(r.prompt) <= CODED["prompt"]["max"] for r in got)


@pytest.mark.parametrize("mix", sorted(p.stem for p in (BENCH / "traffic").glob("*.json")))
def test_a_block_is_what_the_pool_serves_at_once(mix):
    """The window's close cuts a pool's work inside one block of its mix."""
    t = json.loads((BENCH / "traffic" / f"{mix}.json").read_text())
    if "pool" in t:
        assert t["block"] == t["pool"]["slots"]


def test_lognormal_quantiles_have_the_median():
    q = traffic.length_quantiles({"dist": "lognormal", "median": 512, "sigma": 0.8,
                                  "min": 32, "max": 2048}, 16)
    assert q[7] < 512 < q[8] and q.min() >= 32 and q.max() <= 2048
