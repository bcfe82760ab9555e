"""Validation of the paper's claims (Lemmas 1-4, Theorems 1-4) against the
cost-exact simulator and the matrix oracle. These are the EXPERIMENTS.md
§Paper-claims results."""

import math

import numpy as np
import pytest

from repro.core import bounds
from repro.core.field import M31, NTT, Field
from repro.core.matrices import (
    butterfly_target_matrix,
    lagrange_matrix,
    random_matrix,
    random_vector,
    vandermonde,
)
from repro.core.schedule import (
    draw_loose_target_matrix,
    plan_butterfly,
    plan_draw_loose,
    plan_prepare_shoot,
)
from repro.core.simulator import (
    simulate_butterfly,
    simulate_draw_loose,
    simulate_prepare_shoot,
)

KS = [2, 3, 4, 5, 7, 8, 9, 12, 16, 17, 25, 31, 32, 64, 65, 100, 128, 256, 512]
PS = [1, 2, 3]


@pytest.mark.parametrize("p", PS)
@pytest.mark.parametrize("K", KS)
def test_prepare_shoot_correct_and_costs(K, p):
    """Universal algorithm computes any A; C1 strictly optimal (Lemma 1 /
    Theorem 1), C2 equals the Lemma-3+4 closed form, within sqrt(2)·lower
    bound asymptotics (Lemma 2)."""
    f = Field(M31)
    plan = plan_prepare_shoot(K, p)
    A = random_matrix(f, K, seed=K * 7 + p)
    x = random_vector(f, K, seed=K * 13 + p)
    out, stats = simulate_prepare_shoot(x, A, plan, f)
    want = f.matmul(x, A)
    np.testing.assert_array_equal(out, want)
    # C1: strictly optimal
    assert stats.C1 == bounds.lemma1_c1_lower(K, p) == plan.c1
    # C2: equals exact live-slot accounting, bounded by the Theorem-1 form
    from repro.core.schedule import counted_c2

    assert stats.C2 == counted_c2(plan)
    assert stats.C2 <= bounds.theorem1_c2(K, p) == plan.c2
    # C2 lower bound holds
    assert stats.C2 >= math.floor(bounds.lemma2_c2_lower(K, p)) - 1


@pytest.mark.parametrize(
    "K,p", [(2, 1), (4, 1), (8, 1), (16, 1), (32, 1), (64, 1), (256, 1), (3, 2), (9, 2), (16, 3)]
)
def test_butterfly_dft_exact_and_strictly_optimal(K, p):
    """Theorem 2: C1 = C2 = log_{p+1}K; computes the (rev-row) DFT matrix."""
    q = NTT if (NTT - 1) % K == 0 and K % (p + 1) == 0 else M31
    if (q - 1) % K != 0:
        pytest.skip("no K-th root of unity")
    f = Field(q)
    plan = plan_butterfly(K, p, q)
    x = random_vector(f, K, seed=K)
    out, stats = simulate_butterfly(x, plan, f)
    G = butterfly_target_matrix(f, K, p + 1)
    np.testing.assert_array_equal(out, f.matmul(x, G))
    H = bounds.ceil_log(K, p + 1)
    assert stats.C1 == stats.C2 == H
    # exponential improvement over universal C2 (Remark 4) for large K
    assert stats.C2 <= bounds.theorem1_c2(K, p)
    if K >= 16 and p == 1:
        assert stats.C2 < bounds.theorem1_c2(K, p)


@pytest.mark.parametrize("K,p", [(4, 1), (8, 1), (16, 1), (9, 2)])
def test_butterfly_inverse_roundtrip(K, p):
    """Lemma 5: the butterfly is invertible with the same C1/C2."""
    q = NTT if p == 1 else M31
    f = Field(q)
    plan = plan_butterfly(K, p, q)
    x = random_vector(f, K, seed=3 * K)
    y, st_f = simulate_butterfly(x, plan, f)
    back, st_b = simulate_butterfly(y, plan, f, inverse=True)
    np.testing.assert_array_equal(back, x)
    assert st_b.C1 == st_f.C1 and st_b.C2 == st_f.C2


@pytest.mark.parametrize(
    "K,p,q",
    [
        (8, 1, NTT),  # M=1, H=3: pure butterfly
        (12, 1, NTT),  # M=3, H=2
        (20, 1, NTT),  # M=5, H=2
        (18, 2, M31),  # M=2, H=2 (radix 3 over M31: 3^2 | q-1)
        (24, 1, NTT),  # M=3, H=3
        (48, 1, NTT),  # M=3, H=4
        (96, 1, NTT),  # M=3, H=5
        (7, 1, NTT),  # H=0 → degrades to pure universal draw (Remark 5)
    ],
)
def test_draw_loose_vandermonde(K, p, q):
    """Theorem 3: computes a (row-permuted) Vandermonde with C1=⌈log⌉ and
    C2 = H + Ψ(M)."""
    f = Field(q)
    plan = plan_draw_loose(K, p, q, seed=1)
    x = random_vector(f, K, seed=5 * K)
    out, stats = simulate_draw_loose(x, plan, f)
    G = draw_loose_target_matrix(plan)
    np.testing.assert_array_equal(out, f.matmul(x, G))
    c1, c2 = bounds.theorem3_c1_c2(K, p, plan.M, plan.H)
    assert stats.C1 <= c1  # ⌈log_{p+1}K⌉ is an upper bound; subgroup split can beat it
    assert stats.C2 == c2 == plan.c2
    # the generator is Vandermonde up to row permutation
    V = vandermonde(f, plan.points)
    np.testing.assert_array_equal(G, V[plan.source_perm, :])
    # and the C2 never exceeds universal prepare-and-shoot's
    assert stats.C2 <= bounds.theorem1_c2(K, p)


def test_draw_loose_gain_over_universal():
    """Remark 4/5: large-H cases give (near-)exponential C2 gains."""
    K, p, q = 64, 1, NTT
    plan = plan_draw_loose(K, p, q)
    assert plan.M == 1 and plan.H == 6
    assert plan.c2 == 6  # = log2 K
    assert bounds.theorem1_c2(K, p) == 14  # universal: (8-1)/1 + (8-1)/1


@pytest.mark.parametrize("K,p,q", [(8, 1, NTT), (12, 1, NTT), (6, 1, NTT)])
def test_lagrange_via_inverse_forward(K, p, q):
    """Theorem 4: inverse-Vandermonde(ω) then forward-Vandermonde(α) computes
    the Lagrange matrix; source permutations cancel exactly (DESIGN §3)."""
    f = Field(q)
    plan_w = plan_draw_loose(K, p, q, seed=11)
    plan_a = plan_draw_loose(K, p, q, seed=22)
    # simulate: decode ω-plan (inverse loose then inverse draw), then encode α
    x = random_vector(f, K, seed=9 * K)

    # host-exact composite via target matrices:
    Gw = draw_loose_target_matrix(plan_w)
    Ga = draw_loose_target_matrix(plan_a)
    composite = f.matmul(f.inv_matrix(Gw), Ga)
    Ltrue = lagrange_matrix(f, plan_a.points, plan_w.points)
    np.testing.assert_array_equal(composite, Ltrue)

    # algorithmic path (array-level executor is exercised in test_encode_api;
    # here verify the simulator pieces compose):
    coeffs = f.solve(Gw.T, x)  # x = coeffs @ Gw
    out, _ = simulate_draw_loose(coeffs, plan_a, f)
    np.testing.assert_array_equal(out, f.matmul(x, Ltrue))


def test_theorem1_even_L_discrepancy_documented():
    """For even L, Theorem 1's printed C2 disagrees with its own Lemmas 3+4;
    we implement/validate the lemma-consistent value (EXPERIMENTS.md)."""
    K, p = 5, 1  # L = 2 (even)
    assert bounds.ps_params(K, p)[0] == 2
    assert bounds.theorem1_c2(K, p) == 4  # (m-1)/p + (n-1)/p = 3 + 1
    # printed: ((p+1)^{L/2+1} - 2)/p = 2 — it UNDERCOUNTS its own Lemma 3+4
    # sum (the (p+1)^{L/2} shoot term is dropped)
    assert bounds.theorem1_c2_as_printed(K, p) == 2
    # simulator agrees with the lemma-consistent value
    f = Field(M31)
    plan = plan_prepare_shoot(K, p)
    out, stats = simulate_prepare_shoot(
        random_vector(f, K, seed=0), random_matrix(f, K, seed=0), plan, f
    )
    assert stats.C2 == 4


def test_baselines_are_worse():
    """prepare-and-shoot C2 ~ O(√K/p) beats all-gather (~K/p) and direct
    (~K/p) for large K — the paper's raison d'être."""
    for K in [64, 256, 1024]:
        for p in PS:
            ps = bounds.theorem1_c2(K, p)
            ag = bounds.allgather_baseline_c1_c2(K, p)[1]
            di = bounds.direct_baseline_c1_c2(K, p)[1]
            assert ps < ag and ps < di
