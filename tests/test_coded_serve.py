"""Coded straggler-tolerant serving (ISSUE 10): fault-injection
differential harness.

Acceptance:
* For EVERY survivor subset of size K (exhaustive at N ≤ 8 by killing
  each R-subset's complement; hypothesis-sampled above), the coded
  engine's token streams after mid-trace host kills are bit-identical to
  both the unfailed continuous run and the unfailed fixed-batch engine
  on the same seeded trace.
* An 8-forced-host-device subprocess variant SIGKILLs one real host
  process (``ProcessHostPool``) mid-decode while the guard's encode runs
  through the mesh collective (``ir_encode_jit``) — still bit-identical,
  ``serve.recoveries`` ≥ 1.
"""

import contextlib
import functools
import itertools
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest
from hyputil import given, settings, st

import jax

from repro.configs import smoke_config
from repro.launch.compile_cache import CompileLog
from repro.models import build_model
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import Tracer
from repro.serve import (
    CodedDecodeGroup,
    CodedServeGuard,
    ContinuousEngine,
    Engine,
    FaultInjector,
    ProcessHostPool,
    Request,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PROMPTS = [[5, 9, 2, 7, 1], [3, 3, 8], [11, 4, 6, 2], [2]]
MAX_NEW = 6


@functools.lru_cache(maxsize=2)
def _smoke(arch: str = "qwen3-1.7b"):
    cfg = smoke_config(arch).replace(n_layers=2)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    return cfg, model, params


@functools.lru_cache(maxsize=2)
def _engine():
    cfg, model, params = _smoke()
    return ContinuousEngine(
        model, params, n_slots=2, max_len=32, buckets=(8, 16),
        max_new_tokens=MAX_NEW, metrics=MetricsRegistry(),
    )


def _reqs(**kw):
    return [
        Request(id=f"r{i}", prompt=p, max_new_tokens=MAX_NEW, **kw)
        for i, p in enumerate(PROMPTS)
    ]


def _toks(report) -> dict:
    return {r.id: tuple(r.tokens) for r in report.results}


@functools.lru_cache(maxsize=4)
def _baseline(greedy: bool = True, temperature: float = 1.0):
    rep = _engine().serve(
        _reqs(), greedy=greedy, sync_every=2, seed=0, temperature=temperature
    )
    return _toks(rep)


# ---------------------------------------------------------------------------
# unit: injector + guard edges
# ---------------------------------------------------------------------------


def test_fault_injector_fires_each_kill_once():
    inj = FaultInjector(kills=((2, 0), (2, 3), (9, 1)))
    assert inj.due(1) == []
    assert inj.due(4) == [(2, 0), (2, 3)]
    assert inj.due(5) == []  # already fired
    assert inj.due(100) == [(9, 1)]
    assert inj.injected == 3


def test_guard_requires_parity_and_snapshot():
    with pytest.raises(ValueError):
        CodedServeGuard(K=4, R=0)
    g = CodedServeGuard(K=3, R=1)
    with pytest.raises(RuntimeError, match="no snapshot"):
        g.recover([0])


def test_guard_beyond_tolerance_raises():
    """Losing R+1 hosts is past the code: recover must raise, not return
    interpolated garbage."""
    import jax.numpy as jnp

    g = CodedServeGuard(K=3, R=1, injector=FaultInjector(kills=((0, 0), (0, 2))))
    state = {"x": jnp.arange(6, dtype=jnp.float32)}
    g.snapshot({}, state, tick=0)
    dead = g.poll(4)
    assert dead == [0, 2]
    with pytest.raises(RuntimeError, match="need K=3"):
        g.recover(dead)


# ---------------------------------------------------------------------------
# the tentpole differential: every survivor subset, exhaustive at N ≤ 8
# ---------------------------------------------------------------------------

K, R = 3, 2  # N = 5 hosts; killing each 2-subset forces every 3-survivor set


def test_coded_serve_every_survivor_subset_bit_identical():
    """Exhaustive at N = 5 ≤ 8: for every R-subset of hosts killed
    mid-trace (⇔ every survivor subset of size K reconstructs), the coded
    engine's tokens equal the unfailed continuous AND fixed-batch runs."""
    eng = _engine()
    base = _baseline()

    # the unfailed fixed-batch engine on the same trace (greedy)
    cfg, model, params = _smoke()
    fixed = Engine(model, params, max_len=32, metrics=MetricsRegistry())
    res = fixed.generate(PROMPTS, max_new_tokens=MAX_NEW)
    fixed_toks = {
        f"r{b}": tuple(res.tokens[b, : len(PROMPTS[b]) + MAX_NEW].tolist())
        for b in range(len(PROMPTS))
    }
    assert base == fixed_toks  # continuous == fixed-batch, unfailed

    for killed in itertools.combinations(range(K + R), R):
        inj = FaultInjector(kills=tuple((1, h) for h in killed))
        guard = CodedServeGuard(K=K, R=R, injector=inj)
        rep = eng.serve(_reqs(), greedy=True, sync_every=2, guard=guard)
        assert sorted(guard.alive) == [
            h for h in range(K + R) if h not in killed
        ]
        assert _toks(rep) == base, f"tokens diverged after killing {killed}"
        assert rep.recoveries == R
        assert rep.coded["injected_faults"] == R
        assert len(guard.recovery_us) >= 1


def test_coded_serve_staggered_kills_and_metrics():
    """Kills at different ticks (two separate recovery events), metrics +
    spans recorded, requests in flight recovered not dropped."""
    eng = _engine()
    reg, tracer = MetricsRegistry(), Tracer()
    saved = eng._metrics, eng._tracer
    eng._metrics, eng._tracer = reg, tracer
    try:
        guard = CodedServeGuard(
            K=K, R=R, injector=FaultInjector(kills=((1, 0), (5, 4)))
        )
        rep = eng.serve(_reqs(), greedy=True, sync_every=2, guard=guard)
    finally:
        eng._metrics, eng._tracer = saved
    assert _toks(rep) == _baseline()
    snap = reg.snapshot()
    assert snap["serve.recoveries"]["value"] == 2
    assert snap["serve.recovery_us"]["count"] == 2
    assert snap["serve.recovery_us"]["p50"] <= snap["serve.recovery_us"]["p99"]
    assert snap["serve.snapshots"]["value"] == rep.coded["snapshots"] > 0
    assert rep.requests_recovered >= 1
    spans = [s for s in tracer.spans if s.name == "serve.recovery"]
    assert len(spans) == 2 and all(s.dur_us > 0 for s in spans)


def test_coded_serve_sampled_temperature_bit_identical():
    """temperature > 0: per-slot PRNG streams live in the encoded state, so
    the replayed chunk resamples the SAME tokens."""
    eng = _engine()
    base = _baseline(greedy=False, temperature=0.7)
    guard = CodedServeGuard(K=K, R=R, injector=FaultInjector(kills=((2, 1),)))
    rep = eng.serve(
        _reqs(), greedy=False, sync_every=2, seed=0, temperature=0.7,
        guard=guard,
    )
    assert _toks(rep) == base
    assert rep.recoveries == 1


@given(seed=st.integers(0, 10_000))
@settings(max_examples=5, deadline=None)
def test_coded_serve_sampled_survivor_subsets_above_8(seed):
    """N = 10 > 8: hypothesis-sampled R-subsets of killed hosts (each ⇔ one
    survivor subset of size K) instead of all C(10,3) of them."""
    rng = np.random.default_rng(seed)
    Kb, Rb = 7, 3
    killed = tuple(int(h) for h in rng.choice(Kb + Rb, size=Rb, replace=False))
    eng = _engine()
    guard = CodedServeGuard(
        K=Kb, R=Rb, injector=FaultInjector(kills=tuple((1, h) for h in killed))
    )
    rep = eng.serve(_reqs(), greedy=True, sync_every=2, guard=guard)
    assert _toks(rep) == _baseline(), f"diverged for killed={killed}"
    assert rep.recoveries == Rb


# ---------------------------------------------------------------------------
# the device decode under the benchmark's code (K = 3, R = 1)
# ---------------------------------------------------------------------------


@contextlib.contextmanager
def _counting_compiles():
    """A :class:`CompileLog` that counts the backend compiles in the block."""
    log = CompileLog(cache_dir="")
    jax.monitoring.register_event_duration_secs_listener(log._on_duration)
    try:
        yield log
    finally:
        jax.monitoring.unregister_event_duration_listener(log._on_duration)


@pytest.mark.parametrize("host", range(4))
def test_coded_serve_device_recovery_per_killed_host(host):
    """K = 3, R = 1: whichever host dies, the rebuilt state comes back from
    the device decode as a (K, S) uint32 device array, compiled at the
    first snapshot (the rebuild compiles nothing), and the tokens equal
    the unfailed run's."""
    eng = _engine()
    guard = CodedServeGuard(K=3, R=1, injector=FaultInjector(kills=((1, host),)))
    reconstruct = guard.group.reconstruct
    seen = {}

    def watched():
        with _counting_compiles() as log:
            seen["X"] = reconstruct()
        seen["compiles"] = log.compiles
        return seen["X"]

    guard.group.reconstruct = watched
    rep = eng.serve(_reqs(), greedy=True, sync_every=2, guard=guard)
    assert _toks(rep) == _baseline()
    assert rep.recoveries == 1 and sorted(guard.alive) == [
        h for h in range(4) if h != host]
    X = seen["X"]
    S = -(-guard._meta.total // 3)
    assert isinstance(X, jax.Array)
    assert X.dtype == np.uint32 and X.shape == (3, S)
    assert seen["compiles"] == 0
    # the hosts still hold host uint32 shards
    assert all(isinstance(v, np.ndarray) and v.dtype == np.uint32
               and v.shape == (S,) for v in guard.group._mem.values())


def test_recovery_after_a_snapshot_compiles_nothing():
    """Once a snapshot has been taken and the unshard of an uploaded (K, S)
    array has run (as a serving warm-up leaves it), a whole recovery —
    upload, device decode, unshard — compiles nothing, and gives the
    snapshot's state back."""
    import jax.numpy as jnp

    from repro.coded import shard_state_limbs, unshard_state_limbs

    state = {"x": jnp.linspace(-3.0, 3.0, 1001, dtype=jnp.float32),
             "n": jnp.arange(77, dtype=jnp.int32)}
    guard = CodedServeGuard(K=3, R=1, injector=FaultInjector(kills=((0, 1),)))
    guard.snapshot({}, state, tick=0)
    shards, meta = shard_state_limbs(({}, state), 3)
    unshard_state_limbs(jnp.asarray(np.zeros(shards.shape, np.uint32)), meta)
    dead = guard.poll(4)
    assert dead == [1]
    with _counting_compiles() as log:
        _, back = guard.recover(dead)
    assert log.compiles == 0
    for k in state:
        np.testing.assert_array_equal(np.asarray(back[k]), np.asarray(state[k]))


def test_later_snapshots_and_a_recovery_compile_nothing():
    """After the first snapshot of a shape, snapshots that copy some, all
    or none of the blocks, a recovery, and the whole-shard snapshot after
    it compile nothing; the recovery gives the last snapshot's state."""
    import jax.numpy as jnp

    from repro.coded import shard_state_limbs, unshard_state_limbs

    x = jnp.linspace(-3.0, 3.0, 20_011, dtype=jnp.float32)
    n = jnp.arange(77, dtype=jnp.int32)
    states = [{"x": x, "n": n}, {"x": x.at[:5].set(9.0), "n": n},
              {"x": -x, "n": n + 1}, {"x": -x, "n": n + 1}]
    tracer = Tracer()
    guard = CodedServeGuard(K=3, R=1, injector=FaultInjector(kills=((0, 1),)))
    guard.attach(MetricsRegistry(), tracer)
    guard.snapshot({}, states[0], tick=0)
    shards, meta = shard_state_limbs(({}, states[0]), 3)
    unshard_state_limbs(jnp.asarray(np.zeros(shards.shape, np.uint32)), meta)
    with _counting_compiles() as log:
        for t, st_ in enumerate(states[1:], 1):
            guard.snapshot({}, st_, tick=t)
        dead = guard.poll(4)
        _, back = guard.recover(dead)
        guard.snapshot({}, states[0], tick=4)
    assert dead == [1]
    assert log.compiles == 0
    copied = [sp.attrs for sp in tracer.spans if sp.name == "serve.snapshot.to_host"]
    n_blocks = copied[0]["blocks"]
    assert n_blocks > 4 and shards.shape[1] % n_blocks
    assert [a["full"] for a in copied] == [1, 0, 0, 0, 1]
    assert 0 < copied[1]["blocks"] < n_blocks
    assert copied[2]["blocks"] == n_blocks and copied[3]["blocks"] == 0
    for k in states[-1]:
        np.testing.assert_array_equal(np.asarray(back[k]), np.asarray(states[-1][k]))


# ---------------------------------------------------------------------------
# delta snapshots: the hosts' shards after every snapshot
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=1)
def _long_engine():
    """The smoke model with 1024 cache rows a slot: its coded state spans
    many snapshot blocks, and a decode chunk writes few of them."""
    cfg, model, params = _smoke()
    return ContinuousEngine(
        model, params, n_slots=2, max_len=1024, buckets=(8, 16),
        max_new_tokens=MAX_NEW, metrics=MetricsRegistry(),
    )


@functools.lru_cache(maxsize=1)
def _long_baseline():
    return _toks(_long_engine().serve(_reqs(), greedy=True, sync_every=2, seed=0))


def _serve_checked(guard, check):
    """A traced serve of ``_reqs()`` on the long engine under ``guard``,
    calling ``check(cache, state)`` after every snapshot; returns (tokens,
    the registry's snapshot, the ``serve.snapshot.to_host`` spans' attrs)."""
    eng = _long_engine()
    reg, tracer = MetricsRegistry(), Tracer()
    saved = eng._metrics, eng._tracer
    snapshot = guard.snapshot

    def checked(cache, state, tick):
        snapshot(cache, state, tick)
        check(cache, state)

    guard.snapshot = checked
    eng._metrics, eng._tracer = reg, tracer
    try:
        rep = eng.serve(_reqs(), greedy=True, sync_every=2, guard=guard)
    finally:
        eng._metrics, eng._tracer = saved
    copied = [sp.attrs for sp in tracer.spans if sp.name == "serve.snapshot.to_host"]
    return _toks(rep), reg.snapshot(), copied


@pytest.mark.parametrize("hosts", ["memory", "processes"])
def test_delta_snapshots_hold_the_full_encode(hosts):
    """Through admissions, retirements, a kill and its replay, under a
    state whose S is no multiple of the block: after every snapshot each
    live host holds row j of the full encode of the snapshot's state, bit
    for bit; decode-only snapshots copy part of the state (whole shards
    go only at the first snapshot and after the recovery); the tokens
    equal the unfailed run's."""
    from repro.coded import lcc_encode, lcc_pad, shard_state_limbs
    from repro.serve.coded import SNAPSHOT_BLOCK

    with contextlib.ExitStack() as stack:
        pool = (stack.enter_context(ProcessHostPool(4))
                if hosts == "processes" else None)
        guard = CodedServeGuard(
            K=3, R=1, injector=FaultInjector(kills=((3, 1),)), hosts=pool
        )
        sizes = []

        def check(cache, state):
            shards, _ = shard_state_limbs((cache, state), 3)
            want = np.asarray(lcc_encode(guard.plan, lcc_pad(guard.plan, shards)[:3]))
            sizes.append(shards.shape[1])
            for j in sorted(guard.alive):
                held = pool.fetch(j) if pool is not None else guard.group._mem[j]
                np.testing.assert_array_equal(held, want[j])

        toks, snap, copied = _serve_checked(guard, check)
    assert toks == _long_baseline()
    assert guard.recoveries == 1 and sorted(guard.alive) == [0, 2, 3]
    assert sizes[0] % SNAPSHOT_BLOCK and len(sizes) == guard.snapshots
    assert snap["serve.snapshots_full"]["value"] == 2  # the first, after the recovery
    assert snap["serve.snapshots_full"]["value"] < snap["serve.snapshots"]["value"]
    whole = copied[0]["blocks"]
    assert [a["full"] for a in copied].count(1) == 2
    assert all(0 < a["blocks"] < whole for a in copied if not a["full"])
    assert snap["serve.snapshot_bytes_copied"]["value"] == sum(a["bytes"] for a in copied)


def test_arrays_held_at_a_recovery_are_never_written_again():
    """A caller that keeps the arrays the hosts held at a recovery (as the
    benchmark's check does) finds them as they were after more chunks: the
    snapshot after a recovery stores into fresh arrays."""
    guard = CodedServeGuard(K=3, R=1, injector=FaultInjector(kills=((1, 0),)))
    recover = guard.recover
    held = {}

    def watched(dead, requests_in_flight=0):
        held.update({j: (v, v.copy()) for j, v in guard.group._mem.items()})
        held["snapshots"] = guard.snapshots
        return recover(dead, requests_in_flight)

    guard.recover = watched
    toks, snap, _ = _serve_checked(guard, lambda cache, state: None)
    assert toks == _long_baseline() and guard.recoveries == 1
    at_recovery = held.pop("snapshots")
    assert guard.snapshots >= at_recovery + 2  # a patched snapshot came after
    assert snap["serve.snapshots_full"]["value"] == 2
    assert sorted(held) == [0, 1, 2, 3]
    for j, (ref, copy) in held.items():
        np.testing.assert_array_equal(ref, copy)
        assert all(not np.shares_memory(ref, v) for v in guard.group._mem.values())


# ---------------------------------------------------------------------------
# real host processes: SIGKILL mid-decode, 8 forced host devices
# ---------------------------------------------------------------------------


def test_process_host_pool_store_fetch_kill():
    with ProcessHostPool(3) as pool:
        arr = np.arange(17, dtype=np.uint32)
        assert pool.store(0, arr)
        np.testing.assert_array_equal(pool.fetch(0), arr)
        assert pool.fetch(1) is None  # nothing stored yet
        pool.kill(2)
        assert not pool.alive(2)
        assert not pool.store(2, arr)
        assert pool.fetch(2) is None


def test_coded_serve_sigkilled_host_process():
    """In-process engine + real OS host processes: the injector's kill is a
    SIGKILL; tokens still bit-identical."""
    eng = _engine()
    with ProcessHostPool(K + R) as pool:
        guard = CodedServeGuard(
            K=K, R=R, injector=FaultInjector(kills=((1, 2),)), hosts=pool
        )
        rep = eng.serve(_reqs(), greedy=True, sync_every=2, guard=guard)
        assert not pool.alive(2)  # actually dead, not simulated
        assert _toks(rep) == _baseline()
        assert rep.recoveries == 1


def test_coded_serve_mesh_8_host_devices_sigkill():
    """The satellite's subprocess variant: 8 forced host devices, the
    guard's Lagrange encode running as a mesh collective (ppermute rounds
    via ir_encode_jit on an 8-wide 'hosts' axis), one ProcessHostPool host
    SIGKILLed mid-decode — recovered, bit-identical, recoveries ≥ 1."""
    code = """
    import numpy as np, jax
    from repro.configs import smoke_config
    from repro.launch.mesh import make_mesh
    from repro.models import build_model
    from repro.obs.metrics import MetricsRegistry
    from repro.serve import (CodedServeGuard, ContinuousEngine, FaultInjector,
                             ProcessHostPool, Request)

    assert jax.device_count() == 8
    cfg = smoke_config("qwen3-1.7b").replace(n_layers=2)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    prompts = [[5, 9, 2, 7, 1], [3, 3, 8], [11, 4, 6, 2], [2]]
    def reqs():
        return [Request(id=f"r{i}", prompt=p, max_new_tokens=6)
                for i, p in enumerate(prompts)]
    reg = MetricsRegistry()
    eng = ContinuousEngine(model, params, n_slots=2, max_len=32,
                           buckets=(8, 16), max_new_tokens=6, metrics=reg)
    base = [r.tokens for r in eng.serve(reqs(), greedy=True, sync_every=2).results]

    mesh = make_mesh((8,), ("hosts",))  # N = K + R = 8 coded shard hosts
    with ProcessHostPool(8) as pool:
        guard = CodedServeGuard(K=6, R=2, injector=FaultInjector(kills=((1, 3),)),
                                hosts=pool, mesh=mesh, axis="hosts")
        rep = eng.serve(reqs(), greedy=True, sync_every=2, guard=guard)
        assert not pool.alive(3)          # the SIGKILL landed
        got = [r.tokens for r in rep.results]
        assert got == base, (got, base)
        assert rep.recoveries >= 1
        assert reg.snapshot()["serve.recoveries"]["value"] >= 1
    print("CODED-MESH-OK")
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
    assert "CODED-MESH-OK" in r.stdout


# ---------------------------------------------------------------------------
# decode group (host bookkeeping without an engine)
# ---------------------------------------------------------------------------


def test_decode_group_reconstructs_any_k_of_n():
    from repro.coded import build_lcc, lcc_encode, lcc_pad

    plan = build_lcc(3, R=2)
    X = np.arange(3 * 11, dtype=np.uint32).reshape(3, 11)
    coded = np.asarray(lcc_encode(plan, lcc_pad(plan, X)[: plan.K]))
    for killed in itertools.combinations(range(5), 2):
        grp = CodedDecodeGroup(plan)
        grp.store(coded.astype(np.uint32).reshape(5, -1))
        for h in killed:
            assert grp.kill(h)
            assert not grp.kill(h)  # can't die twice
        np.testing.assert_array_equal(grp.reconstruct().reshape(3, 11), X)


def test_decode_group_host_count_mismatch():
    from repro.coded import build_lcc

    plan = build_lcc(3, R=2)
    with ProcessHostPool(4) as pool:  # needs 5
        with pytest.raises(ValueError, match="need N=5"):
            CodedDecodeGroup(plan, hosts=pool)
