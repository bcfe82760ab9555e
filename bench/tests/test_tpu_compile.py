"""The encode cell's program compiled for a described v5e:2x2 at the cell's
own packet size, with no chip: what the TPU compiler would refuse, and the
bytes each device needs (``memory_analysis()``, recorded in PERF.md).

Run with ``pytest -s bench/tests/test_tpu_compile.py`` to see the bytes. The
topology is described inside a fixture, never at import."""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bench.drivers.encode import packet_words
from bench.harness import BENCH, load_json

HBM_LIMIT_BYTES = 16_909_336_064


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
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


def test_encode_cell_compiles_for_four_chips(topo):
    from repro.coded.lagrange_compute import build_lcc, lcc_generator
    from repro.dist import ps_encode_jit

    config = load_json(BENCH / "configs" / "lcc-k3r1-ntt.json")
    S = packet_words(config)
    assert S == 78_294_374
    plan = build_lcc(config["K"], p=config["p"], q=config["q"], R=config["R"])
    mesh = Mesh(np.asarray(topo.devices[:4]), ("enc",))
    fn, _ = ps_encode_jit(mesh, "enc", lcc_generator(plan), p=plan.p, q=plan.q)
    x = jax.ShapeDtypeStruct((4, S), jnp.uint32, sharding=NamedSharding(mesh, P("enc")))
    compiled = fn.lower(x).compile()
    text = compiled.as_text()
    assert "collective-permute" in text and "tpu_custom_call" in text
    assert "all-gather" not in text
    mem = compiled.memory_analysis()
    per_device = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                  + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    print(f"\nencode {4} x {S} words per device: arguments {mem.argument_size_in_bytes}, "
          f"outputs {mem.output_size_in_bytes}, temporaries {mem.temp_size_in_bytes}, "
          f"aliased {mem.alias_size_in_bytes}")
    assert per_device < HBM_LIMIT_BYTES
