"""Optimization-profile rules: shardings stay valid/divisible for the
hillclimb cells, and levers change exactly the intended logical axes."""

import jax
import pytest

from repro.configs import SHAPES, get
from repro.dist.sharding import spec_for
from repro.launch.mesh import make_mesh
from repro.launch.profiles import BASELINE, OPT, Profile, rules_for


@pytest.fixture(scope="module")
def mesh():
    return make_mesh((1, 1), ("data", "model"))


def test_moe_resident_unshards_expert_d(mesh):
    cfg = get("deepseek-v3-671b")
    shape = SHAPES["train_4k"]
    base = rules_for(cfg, shape, BASELINE)
    opt = rules_for(cfg, shape, Profile("x", moe_resident=True))
    assert base.axes_for("expert_d") == ("data",)
    assert opt.axes_for("expert_d") == ()
    assert opt.axes_for("experts") == ("model", "data")


def test_dp_only_batch_all_axes(mesh):
    cfg = get("qwen3-1.7b")
    shape = SHAPES["train_4k"]
    r = rules_for(cfg, shape, Profile("x", dp_only=True))
    assert r.axes_for("batch") == ("pod", "data", "model")
    assert r.axes_for("d_model") == ()
    # spec on a (batch=256, seq) array over (data=1, model=1) degrades fine
    s = spec_for(mesh, r, ("batch", "seq"), (256, 4096))
    assert "data" in str(s) or "model" in str(s) or s  # valid spec


def test_flags_propagate():
    cfg = get("qwen3-1.7b")
    r = rules_for(cfg, SHAPES["train_4k"], Profile("x", attn_heads=True, logits_vocab=True))
    assert r.has("attn_heads") and r.has("logits_vocab")
    assert not rules_for(cfg, SHAPES["train_4k"], BASELINE).has("attn_heads")


def test_decode_rules_shard_kv_seq():
    cfg = get("deepseek-coder-33b")
    r = rules_for(cfg, SHAPES["decode_32k"], BASELINE)
    assert r.axes_for("kv_seq") == ("model",)
    r5 = rules_for(get("rwkv6-3b"), SHAPES["long_500k"], BASELINE)
    assert r5.axes_for("kv_seq") == ("data", "model")


def test_resolve_profile_picks_hierarchical_from_mesh_topology():
    """Acceptance: make_production_mesh(multi_pod=True)'s derived three-level
    chip < slice < pod hierarchy makes the autotuner select the recursive
    multi-level encode for the coded-checkpoint DP axis; single pod selects
    the two-level hierarchical schedule. Pure host-side (no devices)."""
    from repro.launch.mesh import production_topology
    from repro.launch.profiles import resolve_profile

    prof = resolve_profile(multi_pod=True)
    # compute-aware pricing may pick the pipelined rewrite of the same
    # family; the base algorithm and plan are the contract here
    assert prof.algorithm.split("+")[0] == "multilevel"
    assert prof.levels == (4, 4, 2) == prof.plan.levels
    assert prof.topology.levels == production_topology(multi_pod=True).levels
    assert prof.tune.chosen.plan is prof.plan

    single = resolve_profile(multi_pod=False)
    assert single.algorithm.split("+")[0] == "hierarchical"
    assert single.levels == (4, 4)


def test_resolve_profile_from_live_mesh_shape():
    """mesh= path: the hierarchy is derived from the mesh's encode axes
    (outermost → innermost), so a 2×2×2 mesh resolves to the multilevel
    plan whose levels are the reversed axis sizes."""
    from types import SimpleNamespace

    from repro.launch.mesh import mesh_encode_levels, topology_for_mesh
    from repro.launch.profiles import resolve_profile

    mesh = SimpleNamespace(shape={"pod": 2, "slice": 2, "chip": 2})
    axes = ("pod", "slice", "chip")
    assert mesh_encode_levels(mesh, axes) == (2, 2, 2)
    assert topology_for_mesh(mesh, axes).levels == (2, 2, 2)
    prof = resolve_profile(mesh=mesh, axes=axes, payload_bytes=65536)
    # at 64k payloads the compute-aware price makes the pipelined rewrite of
    # the same schedule strictly cheaper, so accept an optional +<pipeline>
    # suffix — the base family and the plan factorization are the contract
    assert prof.algorithm.split("+")[0] == "multilevel"
    assert prof.plan.levels == (2, 2, 2)
    with pytest.raises(ValueError):
        resolve_profile(mesh=mesh)  # axes required with mesh


def test_resolve_profile_measured_override():
    """Wall-clock calibration flows through: forcing every algorithm but
    prepare-shoot to be slow flips the choice (the measured-override
    path)."""
    from repro.launch.profiles import resolve_profile

    base = resolve_profile(multi_pod=True)
    slow = {
        c.algorithm: 1.0
        for c in base.tune.candidates
        if c.algorithm != "prepare-shoot"
    }
    forced = resolve_profile(multi_pod=True,
                             measured={**slow, "prepare-shoot": 1e-9})
    assert forced.algorithm == "prepare-shoot"


def test_generator_kind_taxonomy():
    """Satellite: the checkpoint layer's matrix kind maps into the autotuner
    taxonomy; unknown kinds are a loud error."""
    from repro.launch.profiles import generator_kind_for

    assert generator_kind_for("cauchy") == "general"
    assert generator_kind_for("random") == "general"
    assert generator_kind_for("vandermonde") == "vandermonde"
    assert generator_kind_for("dft") == "dft"
    with pytest.raises(ValueError, match="unknown generator matrix kind"):
        generator_kind_for("hilbert")


def test_resolve_profile_threads_generator_kind():
    """Satellite: resolve_profile defaults the generator taxonomy from the
    checkpoint layer's Cauchy matrix (→ "general": no structured families),
    and an explicit generator= unlocks them."""
    from repro.core.field import NTT
    from repro.launch.profiles import resolve_profile

    default = resolve_profile(multi_pod=False)
    names = {c.base_algorithm for c in default.tune.candidates}
    assert "multilevel-dft" not in names and "draw-loose" not in names

    dft = resolve_profile(multi_pod=False, q=NTT, generator="dft")
    dft_names = {c.base_algorithm for c in dft.tune.candidates}
    assert "hierarchical-dft" in dft_names or "multilevel-dft" in dft_names


def test_opt_profile_smoke_compiles_1dev(mesh):
    """OPT-profile rules lower a tiny train step on a 1x1 mesh."""
    from repro.configs import smoke_config
    from repro.models import build_model, make_batch
    from repro.train import OptConfig, init_state, make_train_step

    cfg = smoke_config("jamba-v0.1-52b")
    r = rules_for(cfg, SHAPES["train_4k"], OPT)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    ocfg = OptConfig()
    step = jax.jit(make_train_step(model, ocfg, mesh=mesh, rules=r))
    batch = make_batch(cfg, 2, 16)
    p2, o2, m = step(params, init_state(ocfg, params), batch)
    assert float(m["loss"]) > 0
