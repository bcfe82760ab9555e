"""Pallas kernel validation: interpret=True vs pure-jnp/host oracles, with
shape and prime sweeps + hypothesis property tests.

The wrappers default to the compiled TPU kernel (``interpret=False``), so
every call here asks for the interpreter explicitly; the compiled kernels
are covered by tests/test_tpu_compile.py and chip_smoke.py."""

import numpy as np
import pytest
from hyputil import given, settings, st

import jax.numpy as jnp

from repro.core.field import M31, NTT, Field, shoup_precompute
from repro.kernels.butterfly.ops import butterfly_mac, butterfly_mac_reference
from repro.kernels.gf_matmul.ops import gf_matmul, gf_matmul_batched
from repro.kernels.gf_matmul.ref import gf_matmul_host, gf_matmul_ref

PRIMES = [M31, NTT, 65537, 97]


def rand_u32(shape, q, seed):
    rng = np.random.default_rng(seed)
    return rng.integers(0, q, size=shape, dtype=np.uint32)


@pytest.mark.parametrize("q", PRIMES)
@pytest.mark.parametrize(
    "M,K,N",
    [
        (8, 8, 128),     # single small block
        (128, 512, 128), # exactly one default block
        (256, 1024, 256),# multi-block in every dim
        (130, 70, 200),  # ragged (padding path)
        (1, 16, 1),      # degenerate
    ],
)
def test_gf_matmul_vs_host_oracle(q, M, K, N):
    a = rand_u32((M, K), q, seed=M + K)
    b = rand_u32((K, N), q, seed=N + K)
    out = np.asarray(gf_matmul(jnp.asarray(a), jnp.asarray(b), q=q, interpret=True), dtype=np.uint64)
    want = gf_matmul_host(a, b, q)
    np.testing.assert_array_equal(out, want)


@pytest.mark.parametrize("q", [M31, NTT])
def test_gf_matmul_vs_jnp_ref(q):
    a = rand_u32((16, 24), q, seed=0)
    b = rand_u32((24, 8), q, seed=1)
    out = gf_matmul(jnp.asarray(a), jnp.asarray(b), q=q, interpret=True)
    ref = gf_matmul_ref(jnp.asarray(a), jnp.asarray(b), q)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_gf_matmul_extreme_values():
    """q-1 everywhere: worst-case limb magnitudes."""
    for q in (M31, NTT):
        a = np.full((64, 512), q - 1, dtype=np.uint32)
        b = np.full((512, 128), q - 1, dtype=np.uint32)
        out = np.asarray(gf_matmul(jnp.asarray(a), jnp.asarray(b), q=q, interpret=True), dtype=np.uint64)
        want = gf_matmul_host(a, b, q)
        np.testing.assert_array_equal(out, want)


def test_gf_matmul_batched():
    q = M31
    a = rand_u32((6, 9, 17), q, seed=3)
    b = rand_u32((6, 17, 5), q, seed=4)
    out = np.asarray(gf_matmul_batched(jnp.asarray(a), jnp.asarray(b), q=q, interpret=True), dtype=np.uint64)
    for i in range(6):
        np.testing.assert_array_equal(out[i], gf_matmul_host(a[i], b[i], q))


@given(
    m=st.integers(1, 40),
    k=st.integers(1, 60),
    n=st.integers(1, 40),
    qi=st.integers(0, len(PRIMES) - 1),
    seed=st.integers(0, 10000),
)
@settings(max_examples=15, deadline=None)
def test_gf_matmul_property(m, k, n, qi, seed):
    q = PRIMES[qi]
    a = rand_u32((m, k), q, seed)
    b = rand_u32((k, n), q, seed + 1)
    out = np.asarray(gf_matmul(jnp.asarray(a), jnp.asarray(b), q=q, interpret=True), dtype=np.uint64)
    np.testing.assert_array_equal(out, gf_matmul_host(a, b, q))


@pytest.mark.parametrize("q", [M31, NTT])
@pytest.mark.parametrize("radix,B,P", [(2, 8, 16), (2, 256, 512), (3, 9, 100), (4, 64, 1000)])
def test_butterfly_mac_vs_ref(q, radix, B, P):
    rng = np.random.default_rng(B + P)
    parts = rng.integers(0, q, size=(radix, B, P), dtype=np.uint32)
    tw = rng.integers(0, q, size=(B, radix), dtype=np.uint32)
    tw_sh = np.asarray(shoup_precompute(tw, q))
    out = butterfly_mac(
        jnp.asarray(parts), jnp.asarray(tw), jnp.asarray(tw_sh), q=q, interpret=True
    )
    ref = butterfly_mac_reference(jnp.asarray(parts), jnp.asarray(tw), jnp.asarray(tw_sh), q=q)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    # independent host check
    f = Field(q)
    want = np.zeros((B, P), dtype=np.uint64)
    for r in range(radix):
        want = f.add(want, f.mul(parts[r], tw[:, r : r + 1]))
    np.testing.assert_array_equal(np.asarray(out, dtype=np.uint64), want)


def test_butterfly_mac_payload_dims():
    q = NTT
    rng = np.random.default_rng(0)
    parts = rng.integers(0, q, size=(2, 16, 3, 5, 7), dtype=np.uint32)
    tw = rng.integers(0, q, size=(16, 2), dtype=np.uint32)
    tw_sh = np.asarray(shoup_precompute(tw, q))
    out = butterfly_mac(
        jnp.asarray(parts), jnp.asarray(tw), jnp.asarray(tw_sh), q=q, interpret=True
    )
    assert out.shape == (16, 3, 5, 7)
    ref = butterfly_mac_reference(jnp.asarray(parts), jnp.asarray(tw), jnp.asarray(tw_sh), q=q)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


# ---------------------------------------------------------------------------
# ISSUE 8: block-size grids, padding pins, zero-size guards, interpret plumb
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("bm,bn,bk", [(8, 128, 8), (16, 128, 32), (64, 256, 64)])
@pytest.mark.parametrize("M,K,N", [(8, 8, 128), (13, 21, 130), (40, 100, 257)])
def test_gf_matmul_block_size_grid(bm, bn, bk, M, K, N):
    """The wrapper is exact for every (block_m, block_n, block_k) choice,
    including shapes that are NOT multiples of the blocks (the _pad_to /
    _round_up path) — padding with zeros is absorbing mod q."""
    q = M31
    a = rand_u32((M, K), q, seed=bm + M)
    b = rand_u32((K, N), q, seed=bn + N)
    out = np.asarray(
        gf_matmul(
            jnp.asarray(a), jnp.asarray(b), q=q, block_m=bm, block_n=bn, block_k=bk,
            interpret=True,
        ),
        dtype=np.uint64,
    )
    np.testing.assert_array_equal(out, gf_matmul_host(a, b, q))


@pytest.mark.parametrize(
    "M,K,N", [(0, 8, 8), (8, 0, 8), (8, 8, 0), (0, 0, 0)]
)
def test_gf_matmul_zero_size_guard(M, K, N):
    """Empty operands (e.g. a slot emptied by fuse_trivial_rounds) must
    short-circuit to an empty/zero result instead of padding up into the
    kernel. K == 0 is a sum over zero terms: an all-zeros (M, N) result."""
    q = M31
    a = jnp.zeros((M, K), dtype=jnp.uint32)
    b = jnp.zeros((K, N), dtype=jnp.uint32)
    out = gf_matmul(a, b, q=q, interpret=True)
    assert out.shape == (M, N) and out.dtype == jnp.uint32
    np.testing.assert_array_equal(np.asarray(out), np.zeros((M, N), np.uint32))


def test_gf_matmul_batched_zero_size_guard():
    q = M31
    out = gf_matmul_batched(
        jnp.zeros((3, 0, 7), dtype=jnp.uint32),
        jnp.zeros((3, 7, 5), dtype=jnp.uint32),
        q=q,
        interpret=True,
    )
    assert out.shape == (3, 0, 5)
    out = gf_matmul_batched(
        jnp.zeros((2, 4, 0), dtype=jnp.uint32),
        jnp.zeros((2, 0, 5), dtype=jnp.uint32),
        q=q,
        interpret=True,
    )
    assert out.shape == (2, 4, 5)
    np.testing.assert_array_equal(np.asarray(out), np.zeros((2, 4, 5), np.uint32))


def _pad_roundtrip_shapes():
    # non-multiple shapes around each tiling boundary the wrappers pin
    return [(1, 1), (7, 127), (8, 128), (9, 129), (17, 300)]


@pytest.mark.parametrize("r,c", _pad_roundtrip_shapes())
def test_pad_to_and_round_up_pins(r, c):
    """_pad_to pads up to multiples with zeros, never truncates; _round_up
    is the exact ceiling multiple (the kernels' 8×128 uint32 tile floor)."""
    from repro.kernels.gf_matmul.ops import _pad_to, _round_up

    x = jnp.arange(r * c, dtype=jnp.uint32).reshape(r, c)
    p = _pad_to(x, 8, 128)
    assert p.shape == (_round_up(r, 8), _round_up(c, 128))
    assert p.shape[0] % 8 == 0 and p.shape[1] % 128 == 0
    np.testing.assert_array_equal(np.asarray(p[:r, :c]), np.asarray(x))
    assert int(np.asarray(p).sum()) == int(np.asarray(x, dtype=np.uint64).sum())
    assert _round_up(r, 8) - r < 8 and _round_up(c, 128) - c < 128


@pytest.mark.parametrize("B,P", [(1, 1), (7, 100), (8, 128), (9, 513)])
def test_butterfly_mac_ragged_shapes(B, P):
    """Non-multiple (B, P) — the wrapper's pad/slice path — stays exact for
    every radix against the host field arithmetic."""
    q = M31
    for radix in (2, 3):
        rng = np.random.default_rng(radix * 1000 + B + P)
        parts = rng.integers(0, q, size=(radix, B, P), dtype=np.uint32)
        tw = rng.integers(0, q, size=(B, radix), dtype=np.uint32)
        tw_sh = np.asarray(shoup_precompute(tw, q))
        out = butterfly_mac(
            jnp.asarray(parts), jnp.asarray(tw), jnp.asarray(tw_sh), q=q,
            interpret=True,
        )
        f = Field(q)
        want = np.zeros((B, P), dtype=np.uint64)
        for r in range(radix):
            want = f.add(want, f.mul(parts[r], tw[:, r : r + 1]))
        np.testing.assert_array_equal(np.asarray(out, dtype=np.uint64), want)


def test_butterfly_mac_forwards_interpret_flag():
    """Regression: butterfly_mac must pass interpret= through to the Pallas
    kernel (it was silently dropped once — on a TPU-less host the explicit
    interpret=True call is the only one that can run)."""
    import inspect

    from repro.kernels.butterfly import ops as bops

    src = inspect.getsource(bops.butterfly_mac.__wrapped__)
    assert "interpret=interpret" in src
    q = NTT
    rng = np.random.default_rng(9)
    parts = rng.integers(0, q, size=(2, 8, 16), dtype=np.uint32)
    tw = rng.integers(0, q, size=(8, 2), dtype=np.uint32)
    tw_sh = np.asarray(shoup_precompute(tw, q))
    out = butterfly_mac(
        jnp.asarray(parts), jnp.asarray(tw), jnp.asarray(tw_sh), q=q, interpret=True
    )
    ref = butterfly_mac_reference(
        jnp.asarray(parts), jnp.asarray(tw), jnp.asarray(tw_sh), q=q
    )
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@given(
    b=st.integers(1, 20),
    p_=st.integers(1, 80),
    radix=st.integers(2, 4),
    seed=st.integers(0, 10000),
)
@settings(max_examples=10, deadline=None)
def test_butterfly_mac_property(b, p_, radix, seed):
    q = M31
    rng = np.random.default_rng(seed)
    parts = rng.integers(0, q, size=(radix, b, p_), dtype=np.uint32)
    tw = rng.integers(0, q, size=(b, radix), dtype=np.uint32)
    tw_sh = np.asarray(shoup_precompute(tw, q))
    out = butterfly_mac(
        jnp.asarray(parts), jnp.asarray(tw), jnp.asarray(tw_sh), q=q, interpret=True
    )
    f = Field(q)
    want = np.zeros((b, p_), dtype=np.uint64)
    for r in range(radix):
        want = f.add(want, f.mul(parts[r], tw[:, r : r + 1]))
    np.testing.assert_array_equal(np.asarray(out, dtype=np.uint64), want)


def test_gf_matmul_limbs_fit_signed_int8():
    """The MXU multiplies int8 as signed, so every limb the kernel feeds it
    must be a nonnegative int8 (8-bit limbs came back wrong on a v5e for any
    byte ≥ 128), and the limbs must still rebuild the whole uint32 word."""
    from repro.kernels.gf_matmul.kernel import _LIMB_BITS, _NLIMB, _limb

    words = np.array([[0, 1, 127, 128, 2**31 - 1, 2**32 - 1]], dtype=np.uint32)
    limbs = [np.asarray(_limb(jnp.asarray(words), i)) for i in range(_NLIMB)]
    assert all(l.dtype == np.int8 and l.min() >= 0 for l in limbs)
    back = sum(l.astype(np.uint64) << np.uint64(_LIMB_BITS * i) for i, l in enumerate(limbs))
    np.testing.assert_array_equal(back, words.astype(np.uint64))
