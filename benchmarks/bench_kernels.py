"""Kernel benchmarks: Pallas vs pure-jnp ref vs numpy host, plus the
analytic MXU roofline of the 7-bit-limb gf_matmul formulation.

The Pallas kernels run compiled on a TPU and in interpret mode anywhere
else; every row name and the printed ``kernel_mode`` line say which. On a
CPU the wall times are interpreter times (a correctness harness), NOT TPU
times; the derived column carries the analytic TPU-side numbers
(25 int8-MXU passes per mod-matmul → peak_eff ≈ 393/25 TOP/s-equivalents
for the 62-bit exact product, see DESIGN §7).
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.field import M31, NTT, shoup_precompute
from repro.kernels.butterfly.ops import butterfly_mac, butterfly_mac_reference
from repro.kernels.gf_matmul.ops import gf_matmul
from repro.kernels.gf_matmul.ref import gf_matmul_host, gf_matmul_ref

from .common import emit, time_fn


def run():
    interpret = jax.default_backend() != "tpu"
    mode = "interp" if interpret else "compiled"
    print(f"# kernel_mode={mode} backend={jax.default_backend()}")
    rng = np.random.default_rng(0)
    q = M31
    M, K, N = 128, 512, 128
    a = jnp.asarray(rng.integers(0, q, size=(M, K), dtype=np.uint32))
    b = jnp.asarray(rng.integers(0, q, size=(K, N), dtype=np.uint32))
    us_pallas = time_fn(
        lambda: gf_matmul(a, b, q=q, interpret=interpret),
        iters=3, metric="bench.gf_matmul_us",
    )
    # analytic: 25 int8 dot passes (5×5 7-bit limbs) of M*N*K MACs on the
    # 393 TOP/s int8 MXU
    macs = M * N * K
    tpu_us = 25 * 2 * macs / 393e12 * 1e6
    emit(f"gf_matmul_128x512x128_pallas_{mode}", us_pallas, f"analytic_tpu_us={tpu_us:.2f}")
    us_ref = time_fn(lambda: gf_matmul_ref(a, b, q), iters=3, metric="bench.gf_matmul_ref_us")
    emit("gf_matmul_128x512x128_jnp_ref", us_ref, "oracle")
    import time as _t

    t0 = _t.perf_counter()
    gf_matmul_host(np.asarray(a), np.asarray(b), q)
    emit("gf_matmul_128x512x128_numpy_host", ( _t.perf_counter() - t0) * 1e6, "host_oracle")

    # butterfly fused MAC vs unfused ref
    radix, B, P = 2, 256, 4096
    parts = jnp.asarray(rng.integers(0, NTT, size=(radix, B, P), dtype=np.uint32))
    tw = jnp.asarray(rng.integers(0, NTT, size=(B, radix), dtype=np.uint32))
    tw_sh = jnp.asarray(np.asarray(shoup_precompute(np.asarray(tw), NTT)))
    us_fused = time_fn(
        lambda: butterfly_mac(parts, tw, tw_sh, q=NTT, interpret=interpret),
        iters=3,
        metric="bench.butterfly_mac_us",
    )
    us_unfused = time_fn(
        lambda: butterfly_mac_reference(parts, tw, tw_sh, q=NTT),
        iters=3,
        metric="bench.butterfly_mac_ref_us",
    )
    # analytic HBM traffic: fused reads radix·B·P + writes B·P once (vs
    # unfused writing radix intermediate rounds): bytes ratio (radix+1)/(2radix)
    emit(
        f"butterfly_mac_r2_256x4096_fused_{mode}",
        us_fused,
        f"unfused_us={us_unfused:.1f},hbm_bytes_fused={(radix + 1) * B * P * 4}",
    )

    # the executor's fused LocalOp contraction (ISSUE 8): the exact shape
    # ir_encode_jit lowers per device — n_out×n_in coefficient rows over a
    # ≥64k payload — as the madd-folded row-batched Shoup fold ("fused"
    # kernels mode) vs the legacy per-(i,j) loop ("jnp" mode)
    from repro.core.field import madd, shoup_mul

    n_out, n_in, pay = 15, 8, 1 << 16
    c = rng.integers(0, q, size=(n_out, n_in), dtype=np.uint32)
    csh = np.asarray(shoup_precompute(c, q))
    xs = jnp.asarray(rng.integers(0, q, size=(n_in, pay), dtype=np.uint32))
    cj, cshj = jnp.asarray(c), jnp.asarray(csh)

    @jax.jit
    def contraction_fused(xs):
        acc = None
        for j in range(n_in):
            term = shoup_mul(xs[j][None], cj[:, j, None], cshj[:, j, None], q)
            acc = term if acc is None else madd(acc, term, q)
        return acc

    @jax.jit
    def contraction_loop(xs):
        outs = []
        for i in range(n_out):
            acc = None
            for j in range(n_in):
                t = shoup_mul(xs[j], cj[i, j], cshj[i, j], q)
                acc = t if acc is None else madd(acc, t, q)
            outs.append(acc)
        return jnp.stack(outs)

    np.testing.assert_array_equal(
        np.asarray(contraction_fused(xs)), np.asarray(contraction_loop(xs))
    )
    us_f = time_fn(contraction_fused, xs, iters=5, metric="bench.localop_fused_us")
    us_l = time_fn(contraction_loop, xs, iters=5, metric="bench.localop_jnp_us")
    emit(
        f"localop_contraction_{n_out}x{n_in}x{pay}_fused",
        us_f,
        f"jnp_loop_us={us_l:.1f},speedup={us_l / us_f:.2f}x",
    )


if __name__ == "__main__":
    run()
