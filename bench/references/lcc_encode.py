"""Plain reference of the LCC encode with R parity shards over GF(q).

The generator is built here from the configuration's evaluation points:
row k < K is the Lagrange basis polynomial of the points omega, evaluated at
every alpha_j; rows K..N-1 are zero (they meet the zero padding). Coded shard
j is ``sum_k x_k * A[k, j] mod q``, computed in exact integer arithmetic in
blocks of words. Nothing of the program is imported.

The control breaks the configuration's guarantee of exact arithmetic: the
same sums in float32, as a change that moved the products onto a float
path would compute them.
"""

from __future__ import annotations

import numpy as np

BLOCK = 1 << 22


def generator(config: dict) -> np.ndarray:
    """The (N, N) padded Lagrange generator, as Python-int arithmetic mod q."""
    q, K, N = int(config["q"]), int(config["K"]), int(config["N"])
    om = [int(w) for w in config["omega_points"]]
    al = [int(a) for a in config["alpha_points"]]
    if len(om) != K or len(al) != N:
        raise ValueError("the configuration needs K omega points and N alpha points")
    A = np.zeros((N, N), np.uint64)
    for k in range(K):
        den = 1
        for m in range(K):
            if m != k:
                den = den * (om[k] - om[m]) % q
        inv = pow(den, q - 2, q)
        for j in range(N):
            num = 1
            for m in range(K):
                if m != k:
                    num = num * (al[j] - om[m]) % q
            A[k, j] = num * inv % q
    return A


def encode(x: np.ndarray, config: dict) -> np.ndarray:
    """x: (N, S) words (rows K..N-1 zero) -> the (N, S) coded shards, uint32."""
    q, K, N = int(config["q"]), int(config["K"]), int(config["N"])
    A = generator(config)
    S = x.shape[1]
    out = np.empty((N, S), np.uint32)
    for lo in range(0, S, BLOCK):
        xb = x[:K, lo:lo + BLOCK].astype(np.uint64) % q
        for j in range(N):
            acc = np.zeros(xb.shape[1], np.uint64)
            for k in range(K):
                acc += (xb[k] * A[k, j]) % q
            out[j, lo:lo + BLOCK] = (acc % q).astype(np.uint32)
    return out


def encode_control(x: np.ndarray, config: dict) -> np.ndarray:
    """The control: the same sums in float32."""
    q, K, N = int(config["q"]), int(config["K"]), int(config["N"])
    A = generator(config).astype(np.float32)
    S = x.shape[1]
    out = np.empty((N, S), np.uint32)
    for lo in range(0, S, BLOCK):
        xb = x[:K, lo:lo + BLOCK].astype(np.float32)
        for j in range(N):
            acc = np.zeros(xb.shape[1], np.float32)
            for k in range(K):
                acc += xb[k] * A[k, j]
            out[j, lo:lo + BLOCK] = np.fmod(acc, np.float32(q)).astype(np.uint32)
    return out


def words_wrong(got: np.ndarray, want: np.ndarray) -> int:
    return int(np.count_nonzero(np.asarray(got, np.uint32) != want))
