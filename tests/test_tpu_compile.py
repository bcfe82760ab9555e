"""Compiles of the hot path for a described TPU v5e, with no chip attached.

The TPU compiler is installed with JAX, and it compiles for a topology that
is described rather than attached (``jax.experimental.topologies``). What
it refuses here — a tiling Mosaic cannot lower, a kernel that uses too much
fast memory, a program that does not fit HBM — it would refuse on the chip.
Nothing runs: these tests say nothing about results or times.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may load the TPU library, and pytest
workers each import every test file. All compiles stay in this one file so
that the worker given it is the only one that loads the library.
"""

from __future__ import annotations

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.core.field import M31, NTT, Field
from repro.core.matrices import random_matrix

#: what one v5e chip lets a program hold: ``memory_stats()["bytes_limit"]``
#: read on the chip, below its 16 GiB of HBM
HBM_LIMIT_BYTES = 16_909_336_064


@pytest.fixture(scope="module")
def topo():
    """The v5e:2x2 topology, with the persistent compilation cache off
    around the compiles (a compile for a described chip is written to the
    cache but cannot be read back without one)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            desc = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _u32(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.uint32, sharding=sharding)


@pytest.mark.parametrize(
    "M,K,N",
    [
        (128, 512, 128),  # one default block
        (16, 8, 65536),  # a LocalOp: 16 output rows over 8 slots of a 64k payload
    ],
)
def test_gf_matmul_compiles(one_chip, M, K, N):
    from repro.kernels.gf_matmul.ops import gf_matmul

    compiled = gf_matmul.lower(
        _u32((M, K), one_chip), _u32((K, N), one_chip), q=M31
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("radix", [2, 3])
def test_butterfly_mac_compiles(one_chip, radix):
    from repro.kernels.butterfly.ops import butterfly_mac

    B, Pw = 256, 8192
    compiled = butterfly_mac.lower(
        _u32((radix, B, Pw), one_chip),
        _u32((B, radix), one_chip),
        _u32((B, radix), one_chip),
        q=NTT,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_ps_encode_compiles_permute_only(topo):
    """K=4 universal encode on the four described chips: the lowering
    follows the mesh's platform to the compiled Pallas kernels, and the
    rounds lower to collective-permutes only."""
    from repro.dist import ps_encode_jit

    mesh = Mesh(np.asarray(topo.devices[:4]), ("enc",))
    A = np.asarray(random_matrix(Field(M31), 4, seed=0))
    fn, _ = ps_encode_jit(mesh, "enc", A)
    text = fn.lower(_u32((4, 1 << 20), NamedSharding(mesh, P("enc")))).compile().as_text()
    assert "tpu_custom_call" in text
    assert "collective-permute" in text
    assert "all-gather" not in text


def test_lcc_decode_device_fits(one_chip):
    """The recovery's device decode at the coded pool's shard size (K = 3
    shards of 78,294,374 words, qwen3-1.7b at 4 × 1024), compiled from the
    uploaded rows: its temporaries stay under 1 GB."""
    from repro.coded.lagrange_compute import _interpolate, build_lcc

    plan = build_lcc(3, R=1)
    S = 78_294_374
    compiled = _interpolate.lower(
        [_u32((1, S), one_chip)] * 3, _u32((3, 3), one_chip), _u32((3, 3), one_chip),
        q=plan.q,
    ).compile()
    mem = compiled.memory_analysis()
    print(f"decode 3x{S}: arguments {mem.argument_size_in_bytes} B, temporaries "
          f"{mem.temp_size_in_bytes} B, outputs {mem.output_size_in_bytes} B")
    assert mem.argument_size_in_bytes >= 3 * S * 4
    assert mem.output_size_in_bytes >= 3 * S * 4
    assert mem.temp_size_in_bytes < 1_000_000_000


def _engine_program(one_chip, which: str, slots: int, max_len: int,
                    arch: str = "qwen3-1.7b", bucket: int = 256, max_new: int = 32):
    """The serving engine's own jitted decode tick or ``bucket``-token
    prefill for ``arch`` at its published widths, compiled from the shapes
    of the engine's own start state placed on one described chip; with the
    bytes of the weights and the decode state it must take as arguments."""
    from repro.configs import get
    from repro.models import build_model
    from repro.serve import ContinuousEngine

    model = build_model(get(arch))
    eng = ContinuousEngine(model, None, n_slots=slots, max_len=max_len,
                           buckets=(bucket,), max_new_tokens=max_new)

    def on_chip(tree):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip), tree
        )

    params = on_chip(model.param_specs()[0])
    cache, state = on_chip(jax.eval_shape(eng.init_state))
    held = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves((params, cache, state)))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    temp = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    if which == "decode":
        lowered = eng._tick_for(True).lower(params, cache, state, i32(), temp)
    else:
        lowered = eng._prefill_for(bucket, True).lower(
            params, cache, state, i32(1, bucket), i32(), i32(), i32(), i32(),
            jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip), temp,
        )
    return lowered.compile(), held


@pytest.mark.parametrize(
    "which,slots,max_len",
    [
        ("decode", 8, 4096),  # the serving phase of chip_smoke.py
        ("prefill", 8, 4096),
        ("decode", 4, 1024),  # its coded serving phase
    ],
)
def test_qwen3_serving_program_fits_one_chip(one_chip, which, slots, max_len):
    """Arguments, temporaries and unaliased outputs of the decode tick and of
    a 256-token prefill stay inside what one chip lets a program hold."""
    compiled, held = _engine_program(one_chip, which, slots, max_len)
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    print(f"{which} {slots}x{max_len}: arguments {mem.argument_size_in_bytes} B, "
          f"temporaries {mem.temp_size_in_bytes} B, outputs "
          f"{mem.output_size_in_bytes} B of which aliased {mem.alias_size_in_bytes} B")
    assert mem.argument_size_in_bytes >= held  # weights and the whole cache
    assert used < HBM_LIMIT_BYTES, (which, used)


@pytest.mark.parametrize("which", ["decode", "prefill"])
def test_deepseek_ep32_serving_program_fits_one_chip(one_chip, which):
    """One chip of EP32 DeepSeek-V3 (8.67 GB of weights) at the reasoning
    cell's pool, 96 slots × 4096 (a 3.17 GB latent cache): the decode tick
    and a 1024-token prefill leave at least 0.5 GB of the chip free. (At 128
    slots the prefill left 0.47 GB.)"""
    compiled, held = _engine_program(one_chip, which, 96, 4096, arch="deepseek-v3-ep32",
                                     bucket=1024, max_new=3072)
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    print(f"deepseek-v3-ep32 {which} 96x4096: arguments {mem.argument_size_in_bytes} B, "
          f"temporaries {mem.temp_size_in_bytes} B, free {HBM_LIMIT_BYTES - used} B")
    assert mem.argument_size_in_bytes >= held
    assert used + 500_000_000 <= HBM_LIMIT_BYTES, (which, used)
