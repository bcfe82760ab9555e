"""Coded fault-tolerance layer: bit-exact RS/Cauchy recovery, gradient
coding, Lagrange coded computing."""

import numpy as np
import pytest
from hyputil import given, settings, st

import jax
import jax.numpy as jnp

from repro.coded import (
    aggregate,
    build_grad_coding,
    build_lcc,
    build_parity_plan,
    encode_parity,
    lcc_compute_and_decode,
    lcc_decode,
    lcc_encode,
    lcc_pad,
    limbs_to_state,
    recover_lost,
    shard_state_limbs,
    state_to_limbs,
    unshard_state_limbs,
    worker_combine,
)
from repro.coded.lagrange_compute import _interpolate, lcc_decode_device
from repro.core.field import M31, NTT, Field
from repro.core.matrices import cauchy_matrix


def test_limb_bitcast_roundtrip():
    state = {
        "w": jnp.asarray(np.random.default_rng(0).normal(size=(7, 5)).astype(np.float32)),
        "m": jnp.asarray(np.random.default_rng(1).normal(size=(11,)).astype(np.float32)),
        "b16": jnp.asarray(np.random.default_rng(2).normal(size=(3, 3)), dtype=jnp.bfloat16),
        "i": jnp.arange(9, dtype=jnp.int32),
        "flag": jnp.asarray([True, False, True]),  # odd byte count: padded limb
        "i8": jnp.asarray([-128, -1, 0, 7, 127], dtype=jnp.int8),
        "s": jnp.float32(3.5),
    }
    limbs, meta = state_to_limbs(state)
    assert limbs.dtype == jnp.uint32 and int(limbs.max()) < 2**16
    # limbs are each leaf's bytes in memory order, two per limb, little-endian
    n_b16 = 0
    for leaf in jax.tree.leaves(state)[:jax.tree.leaves(state).index(state["b16"])]:
        n_b16 += -(-leaf.size * leaf.dtype.itemsize // 2)
    np.testing.assert_array_equal(
        np.asarray(limbs[n_b16 : n_b16 + 9]),
        np.asarray(state["b16"]).reshape(-1).view(np.uint16),
    )
    back = limbs_to_state(limbs, meta)
    for k in state:
        np.testing.assert_array_equal(np.asarray(back[k]), np.asarray(state[k]))


def test_cauchy_all_submatrices_invertible():
    f = Field(M31)
    A = cauchy_matrix(f, 6)
    import itertools

    for rows in itertools.combinations(range(6), 3):
        for cols in itertools.combinations(range(6), 3):
            sub = A[np.ix_(rows, cols)]
            f.inv_matrix(sub)  # raises if singular


@pytest.mark.parametrize("K,f_lost", [(4, 1), (8, 2), (8, 3), (16, 5)])
def test_coded_checkpoint_recovery_bit_exact(K, f_lost):
    """Kill f nodes; recover their float state bit-exactly from survivors."""
    rng = np.random.default_rng(K)
    state = {
        "params": jnp.asarray(rng.normal(size=(K * 37,)).astype(np.float32)),
        "m": jnp.asarray(rng.normal(size=(K * 13,)).astype(np.float32)),
        "step": jnp.asarray(123, jnp.int32),
    }
    shards, meta = shard_state_limbs(state, K)  # (K, S)
    plan = build_parity_plan(K, p=1)
    parity = np.asarray(encode_parity(shards, plan), dtype=np.uint64)
    shards_np = np.asarray(shards, dtype=np.uint64)

    lost = list(rng.choice(K, size=f_lost, replace=False))
    surviving_x = {k: shards_np[k] for k in range(K) if k not in lost}
    surviving_p = {k: parity[k] for k in range(K) if k not in lost}
    rec = recover_lost(plan, lost, surviving_x, surviving_p)
    for k in lost:
        np.testing.assert_array_equal(rec[k], shards_np[k])
    # full state reassembles bit-exactly
    full = shards_np.copy()
    for k in lost:
        full[k] = rec[k]
    back = unshard_state_limbs(jnp.asarray(full.astype(np.uint32)), meta)
    for k in state:
        np.testing.assert_array_equal(np.asarray(back[k]), np.asarray(state[k]))


@given(K=st.integers(3, 12), seed=st.integers(0, 100))
@settings(max_examples=10, deadline=None)
def test_coded_checkpoint_recovery_property(K, seed):
    rng = np.random.default_rng(seed)
    f_lost = int(rng.integers(1, max(2, K // 2)))
    shards = jnp.asarray(rng.integers(0, 2**16, size=(K, 29), dtype=np.uint32))
    plan = build_parity_plan(K, p=1)
    parity = np.asarray(encode_parity(shards, plan), dtype=np.uint64)
    sn = np.asarray(shards, dtype=np.uint64)
    lost = list(rng.choice(K, size=f_lost, replace=False))
    rec = recover_lost(
        plan,
        lost,
        {k: sn[k] for k in range(K) if k not in lost},
        {k: parity[k] for k in range(K) if k not in lost},
    )
    for k in lost:
        np.testing.assert_array_equal(rec[k], sn[k])


@pytest.mark.parametrize("K,s", [(5, 1), (8, 2), (12, 3)])
def test_gradient_coding_tolerates_stragglers(K, s):
    rng = np.random.default_rng(0)
    plan = build_grad_coding(K, s, seed=1)
    shard_grads = {
        j: {"w": jnp.asarray(rng.normal(size=(4, 3)).astype(np.float32))} for j in range(K)
    }
    want = sum(np.asarray(shard_grads[j]["w"]) for j in range(K))
    sent = {i: worker_combine(plan, i, shard_grads) for i in range(K)}
    # drop the s slowest workers (worst case: any subset)
    for drop_seed in range(3):
        drop = set(np.random.default_rng(drop_seed).choice(K, size=s, replace=False).tolist())
        received = {i: c for i, c in sent.items() if i not in drop}
        got = aggregate(plan, received)
        np.testing.assert_allclose(np.asarray(got["w"]), want, rtol=1e-4, atol=1e-4)


def test_lcc_coded_matmul():
    K, q = 8, NTT
    f = Field(q)
    rng = np.random.default_rng(7)
    plan = build_lcc(K, p=1, q=q)
    X = rng.integers(0, 1000, size=(K, 6, 4), dtype=np.uint32)  # small ints: exact
    W = rng.integers(0, 1000, size=(4, 5), dtype=np.uint64)
    encoded = lcc_encode(plan, jnp.asarray(X))
    # any K responders decode (here: all, then a rotated subset of exactly K)
    out = lcc_compute_and_decode(plan, np.asarray(encoded), W, list(range(K)))
    for i in range(K):
        np.testing.assert_array_equal(out[i], f.matmul(X[i].astype(np.uint64), W))


# ---------------------------------------------------------------------------
# LCC erasure codes (N = K + R): ISSUE 10 property + edge cases
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("q", [M31, NTT])
@pytest.mark.parametrize("K", [4, 8, 16])
def test_lcc_erasure_roundtrip_both_fields(q, K):
    """encode → drop R shards → decode is the identity over both fields,
    K ∈ {4, 8, 16}, odd payload shapes."""
    R = 2
    rng = np.random.default_rng(K * 17 + (q & 0xFF))
    plan = build_lcc(K, p=1, q=q, R=R)
    assert plan.N == K + R
    X = rng.integers(0, q, size=(K, 7, 3), dtype=np.uint64)  # odd payload
    coded = np.asarray(lcc_encode(plan, jnp.asarray(X)), dtype=np.uint64)
    assert coded.shape == (K + R,) + X.shape[1:]
    # rows 0..K-1 of the coded output are NOT the data (Lagrange points
    # differ from data points) — decode is what recovers it
    for _ in range(5):
        survivors = sorted(
            int(r) for r in rng.choice(K + R, size=K, replace=False)
        )
        got = lcc_decode(plan, coded[survivors], survivors)
        np.testing.assert_array_equal(got, X % q)


@pytest.mark.parametrize("q", [M31, NTT])
def test_lcc_compute_and_decode_with_parity_responders(q):
    """f(X_i) = X_i @ W recovered from any K responders INCLUDING parity
    hosts (indices ≥ K), over both fields."""
    K, R = 4, 3
    f = Field(q)
    rng = np.random.default_rng(3)
    plan = build_lcc(K, p=1, q=q, R=R)
    X = rng.integers(0, 1 << 20, size=(K, 5, 3), dtype=np.uint64)
    W = rng.integers(0, 1 << 20, size=(3, 2), dtype=np.uint64)
    encoded = np.asarray(lcc_encode(plan, jnp.asarray(X)), dtype=np.uint64)
    for responders in ([0, 1, 2, 3], [3, 4, 5, 6], [6, 0, 5, 2], [1, 6, 3, 5]):
        out = lcc_compute_and_decode(plan, encoded, W, responders)
        for i in range(K):
            np.testing.assert_array_equal(
                out[i], f.matmul(X[i] % q, W % q)
            )


def test_lcc_zero_size_payload_roundtrip():
    """A (K, 0) payload must encode/decode without error — the degenerate
    snapshot of an empty pytree."""
    K, R = 4, 2
    plan = build_lcc(K, R=R)
    X = np.zeros((K, 0), dtype=np.uint64)
    coded = np.asarray(lcc_encode(plan, jnp.asarray(X)))
    assert coded.shape == (K + R, 0)
    got = lcc_decode(plan, coded[:K], list(range(K)))
    assert got.shape == (K, 0)


def test_lcc_k_minus_1_survivors_raise_not_garbage():
    """K−1 responders under-determine the degree-(K−1) polynomial: decode
    must raise ValueError, never return interpolated garbage."""
    K, R = 4, 2
    plan = build_lcc(K, R=R)
    X = np.arange(K * 6, dtype=np.uint64).reshape(K, 6)
    coded = np.asarray(lcc_encode(plan, jnp.asarray(X)), dtype=np.uint64)
    with pytest.raises(ValueError, match="need ≥4 responders"):
        lcc_decode(plan, coded[: K - 1], list(range(K - 1)))
    with pytest.raises(ValueError, match="duplicate"):
        lcc_decode(plan, coded[[0, 0, 1, 2]], [0, 0, 1, 2])
    with pytest.raises(ValueError, match="outside"):
        lcc_decode(plan, coded[:K], [0, 1, 2, K + R])
    with pytest.raises(ValueError):
        build_lcc(K, R=-1)
    with pytest.raises(ValueError, match="K=4 rows"):
        lcc_pad(plan, np.zeros((K + 1, 3), np.uint64))


@given(
    K=st.sampled_from([4, 8, 16]),
    R=st.integers(1, 4),
    pay=st.integers(1, 31),
    seed=st.integers(0, 1000),
)
@settings(max_examples=12, deadline=None)
def test_lcc_erasure_roundtrip_property(K, R, pay, seed):
    q = NTT if seed % 2 else M31
    rng = np.random.default_rng(seed)
    plan = build_lcc(K, p=1, q=q, R=R)
    X = rng.integers(0, q, size=(K, pay), dtype=np.uint64)
    coded = np.asarray(lcc_encode(plan, jnp.asarray(X)), dtype=np.uint64)
    survivors = sorted(int(r) for r in rng.choice(K + R, size=K, replace=False))
    np.testing.assert_array_equal(
        lcc_decode(plan, coded[survivors], survivors), X % q
    )


# ---------------------------------------------------------------------------
# the device decode: one compiled program, coefficients as arguments
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("q", [NTT, M31])
@pytest.mark.parametrize("K,R", [(3, 1), (4, 2)])
def test_lcc_decode_device_matches_host_for_every_subset(K, R, q):
    """The device decode equals the host oracle for every K-subset of the N
    responders, in any order, on data that holds 0 and q − 1; the encoded
    data comes back; one executable serves every subset."""
    import itertools

    plan = build_lcc(K, p=1, q=q, R=R)
    S = 1000 + 10 * K + R + (q & 7)  # a shape no other test compiles
    rng = np.random.default_rng(K * 31 + R)
    X = rng.integers(0, q, size=(K, S), dtype=np.uint64)
    X[:, :4] = [0, q - 1, 0, q - 1]
    coded = np.asarray(lcc_encode(plan, jnp.asarray(X)), dtype=np.uint32)
    Y = rng.integers(0, q, size=(plan.N, S), dtype=np.uint64).astype(np.uint32)
    Y[:, 4:8] = [q - 1, 0, q - 1, 0]
    before = _interpolate._cache_size()
    for subset in itertools.combinations(range(plan.N), K):
        resp = list(subset)[::-1] if sum(subset) % 2 else list(subset)
        got = lcc_decode_device(plan, coded[resp], resp)
        assert isinstance(got, jax.Array)
        assert got.dtype == jnp.uint32 and got.shape == (K, S)
        np.testing.assert_array_equal(np.asarray(got), X)
        np.testing.assert_array_equal(
            np.asarray(lcc_decode_device(plan, list(Y[resp]), resp)),
            lcc_decode(plan, Y[resp], resp),
        )
    assert _interpolate._cache_size() == before + 1


def test_lcc_decode_device_takes_exactly_k_distinct_responders():
    K, R = 3, 2
    plan = build_lcc(K, R=R)
    Y = np.zeros((K + 1, 5), np.uint32)
    with pytest.raises(ValueError, match="need ≥3 responders"):
        lcc_decode_device(plan, Y[: K - 1], list(range(K - 1)))
    with pytest.raises(ValueError, match="exactly K=3"):
        lcc_decode_device(plan, Y, list(range(K + 1)))
    with pytest.raises(ValueError, match="duplicate"):
        lcc_decode_device(plan, Y[:K], [0, 0, 1])
    with pytest.raises(ValueError, match="outside"):
        lcc_decode_device(plan, Y[:K], [0, 1, K + R])
