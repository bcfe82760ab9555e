"""The check must fail where the timed path is wrong: each run here skips the
look for a chip, breaks the timed path underneath, drives the rest of a run
at a tiny size on the CPU, and sees ``correct`` come out false. The control
(the reference at a lower precision in the program's place) must read worse
than the program on the same tokens."""

import json
import subprocess
import sys

import jax.numpy as jnp
import pytest

from bench import control
from bench.harness import BENCH, Ctx, load_json, run_cell
from bench.tests.test_rehearsal import _cpu_env
from bench.tests.tiny import CODED, TINY_MODEL, overrides


def _coded():
    return run_cell("qwen3-1.7b.coded-kill", 1234567890123, 3.0, False,
                    require_tpu=False, overrides=overrides("coded"), cache=False)


@pytest.fixture
def broken_tick(monkeypatch):
    """Patch the engine's decode tick; the test picks how it is broken."""
    from repro.serve.engine import ContinuousEngine

    real = ContinuousEngine._make_tick

    def install(fault):
        def make(self, greedy):
            tick = real(self, greedy)
            V = self.model.cfg.vocab_size

            def broken(params, cache, state, eos, temp):
                if fault == "state_unchanged":
                    return cache, state
                gc0 = jnp.array(state["gen_count"], copy=True)  # the tick donates it
                cache, st = tick(params, cache, state, eos, temp)
                G = st["gen_buf"].shape[1]
                wrote = (jnp.arange(G)[None, :] == gc0[:, None]) & \
                    (st["gen_count"] > gc0)[:, None]
                st = dict(st, gen_buf=jnp.where(wrote, (st["gen_buf"] + 1) % V, st["gen_buf"]))
                return cache, st

            return broken

        monkeypatch.setattr(ContinuousEngine, "_make_tick", make)

    return install


def test_a_token_altered_where_produced(broken_tick):
    broken_tick("token_altered")
    line = _coded()
    assert not line["correct"]
    c = line["checks"]["max_logit_shortfall"]
    assert c["value"] > c["limit"]


def test_a_tick_that_returns_its_state_unchanged(broken_tick):
    broken_tick("state_unchanged")
    line = _coded()
    assert not line["correct"]
    assert line["checks"]["no_request_finished"]["value"] > 0


def test_a_recovery_that_gives_back_other_data(monkeypatch):
    import repro.serve.coded as coded

    real = coded.lcc_decode

    def altered(plan, values, responders):
        X = real(plan, values, responders)
        X[0, 7] = (X[0, 7] + 1) % plan.q
        return X

    monkeypatch.setattr(coded, "lcc_decode", altered)
    line = _coded()
    assert not line["correct"]
    assert line["checks"]["recovered_words_wrong"]["value"] > 0


def test_a_recovery_the_harness_cannot_see(monkeypatch):
    """A recovery that does not go through ``group.reconstruct`` leaves the
    harness nothing to compare: the check reads it as wrong, not as absent."""
    import repro.serve.coded as coded

    real = coded.CodedServeGuard.recover

    def unseen(self, dead, requests_in_flight=0):
        watched = self.group.__dict__.pop("reconstruct", None)
        try:
            return real(self, dead, requests_in_flight)
        finally:
            if watched is not None:
                self.group.reconstruct = watched

    monkeypatch.setattr(coded.CodedServeGuard, "recover", unseen)
    line = _coded()
    assert not line["correct"]
    assert line["checks"]["kill_unrecovered"]["value"] == 0
    assert line["checks"]["recovered_words_wrong"]["value"] == 1


@pytest.mark.parametrize("fault", ["no_exchange", "altered"])
def test_encode_faults(fault):
    out = subprocess.run([sys.executable, str(BENCH / "tests" / "encode_cpu.py"), fault],
                         env=_cpu_env(4), capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert not line["correct"] and line["checks"]["encode_words_wrong"]["value"] > 0


def test_the_control_fails_where_the_program_passes():
    """Over three seeds, the control (fp8 e4m3 products in the reference) read
    on the same tokens makes the run not correct, and the program's own
    reading leaves it correct, judged as ``bench/control.py`` judges them.
    The stand-in is deep and wide in vocabulary enough for near-ties; the
    cells' own limits come from chip readings at full size (PERF.md)."""
    from bench.drivers import serve

    config = load_json(BENCH / "configs" / "qwen3-1.7b.json")
    config.update(NEAR_TIES)
    traffic = dict(load_json(BENCH / "traffic" / "coded-kill.json"), **CODED)
    for key in ("guard", "kill"):  # the served tokens alone
        traffic.pop(key)
    traffic["check"] = {"served_tokens": 400, "ref_len": 96}
    limits = {"max_logit_shortfall": {"limit": NEAR_TIES_LIMIT},
              "no_request_finished": {"limit": 0}}
    cell = {"name": "qwen3-1.7b.coded-kill", "chips": 1}
    prep = None
    program, fp8 = [], []
    for seed in (3, 4, 5):
        ctx = Ctx(cell, config, traffic, seed, float("inf"), False, limits)
        if prep is None:
            prep = serve.prepare(config, traffic, seed)
        else:
            serve.reseed(prep, config, seed)
        out = serve.measure(ctx, prep, controls=("fp8",), max_chunks=CHUNKS)
        assert out.facts["checked_tokens"] >= 300
        program.append(control.judged(out, out.checks["max_logit_shortfall"].value,
                                      NEAR_TIES_LIMIT))
        fp8.append(control.judged(out, out.facts["control_fp8"], NEAR_TIES_LIMIT))
    readings = [(p["value"], c["value"]) for p, c in zip(program, fp8)]
    assert all(p["correct"] for p in program), readings
    assert not any(c["correct"] for c in fp8), readings


#: a stand-in with near-ties among its tokens: 6 layers, 32768 tokens
NEAR_TIES = dict(TINY_MODEL, num_hidden_layers=6, vocab_size=32768,
                 program_overrides=dict(TINY_MODEL["program_overrides"], n_layers=6,
                                        vocab_size=32768, vocab_padded=32768))
#: its window, in chunks: a fixed one, so the readings do not hang on the CPU's speed
CHUNKS = 60
#: its limit: over seeds 3 to 14 the program reads 0 to 8.3e-3 and the fp8
#: control 1.5e-2 to 5.3e-2 (CPU; PERF.md)
NEAR_TIES_LIMIT = 0.01
