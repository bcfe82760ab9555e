"""Docs hygiene (CI satellite): internal links in docs/*.md and README.md
resolve to real files, and every public ``topo``/``dist`` symbol a doc names
actually exists — stale docs fail the build, not the reader.
"""

import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = [
    os.path.join(REPO, "README.md"),
    *sorted(
        os.path.join(REPO, "docs", f)
        for f in os.listdir(os.path.join(REPO, "docs"))
        if f.endswith(".md")
    ),
]

LINK_RE = re.compile(r"\[[^\]]+\]\(([^)\s]+)\)")
# dotted references to repro.topo / repro.dist API inside code spans, e.g.
# `topo/autotune.py`, `dist.collectives.multilevel_encode_jit`,
# `launch.profiles.resolve_profile`
SYMBOL_RE = re.compile(
    # serve/models/train require the explicit ``repro.`` prefix: bare
    # ``serve.xxx`` in docs is usually a METRIC series name, not a symbol
    r"`(?:(?:repro\.)?(topo|dist|launch|coded|core|obs)"
    r"|repro\.(serve|models|train))\.([A-Za-z_][\w.]*)(?:\([^`]*\))?`",
    re.DOTALL,
)


def test_docs_exist_and_are_linked_from_readme():
    assert os.path.exists(os.path.join(REPO, "docs", "ARCHITECTURE.md"))
    assert os.path.exists(os.path.join(REPO, "docs", "TOPOLOGY.md"))
    assert os.path.exists(os.path.join(REPO, "docs", "OBSERVABILITY.md"))
    assert os.path.exists(os.path.join(REPO, "docs", "SERVING.md"))
    readme = open(os.path.join(REPO, "README.md")).read()
    assert "docs/ARCHITECTURE.md" in readme and "docs/TOPOLOGY.md" in readme
    assert "docs/OBSERVABILITY.md" in readme
    assert "docs/SERVING.md" in readme


@pytest.mark.parametrize("path", DOCS, ids=[os.path.relpath(p, REPO) for p in DOCS])
def test_internal_links_resolve(path):
    text = open(path).read()
    base = os.path.dirname(path)
    bad = []
    for target in LINK_RE.findall(text):
        if "://" in target or target.startswith(("mailto:", "#")):
            continue  # external / intra-page
        rel = target.split("#", 1)[0]
        if not (
            os.path.exists(os.path.join(base, rel))
            or os.path.exists(os.path.join(REPO, rel))
        ):
            bad.append(target)
    assert not bad, f"{os.path.relpath(path, REPO)}: broken links {bad}"


def _resolve(modname: str, dotted: str) -> bool:
    """True iff ``repro.<modname>.<dotted>`` names a real module/attr chain."""
    import importlib

    parts = dotted.split(".")
    try:
        obj = importlib.import_module(f"repro.{modname}")
    except ImportError:
        return False
    for i, part in enumerate(parts):
        if hasattr(obj, part):
            obj = getattr(obj, part)
            continue
        try:
            obj = importlib.import_module(
                f"repro.{modname}." + ".".join(parts[: i + 1])
            )
        except ImportError:
            return False
    return True


@pytest.mark.parametrize("path", DOCS, ids=[os.path.relpath(p, REPO) for p in DOCS])
def test_documented_symbols_exist(path):
    text = open(path).read()
    bad = []
    for legacy, prefixed, dotted in SYMBOL_RE.findall(text):
        modname = legacy or prefixed
        if not _resolve(modname, dotted):
            bad.append(f"{modname}.{dotted}")
    assert not bad, f"{os.path.relpath(path, REPO)}: unknown symbols {bad}"


def test_public_topo_and_dist_api_is_documented():
    """The load-bearing public surface must appear somewhere in the docs —
    new exports come with docs, or this list is updated consciously."""
    all_docs = "\n".join(open(p).read() for p in DOCS)
    for name in [
        "autotune",
        "make_topology",
        "Hierarchy",
        "TwoLevel",
        "lower",
        "plan_hierarchical",
        "plan_multilevel",
        "simulate_multilevel",
        "ps_encode_jit",
        "hierarchical_encode_jit",
        "multilevel_encode_jit",
        "resolve_profile",
        # the ScheduleIR pipeline (PR 4)
        "ScheduleIR",
        "to_ir",
        "interpret",
        "ir_encode_jit",
        "fuse_trivial_rounds",
        "remap_digits",
        "fit_level_costs",
        "plan_multilevel_dft",
        # the pass-pipeline optimizer + calibrated pricing (PR 6)
        "PassPipeline",
        "pipelines_for",
        "split_contended",
        "fuse_rounds",
        "align_subgroups",
        "generator_kind_for",
        "Torus3D",
        # the observability layer (PR 7)
        "Tracer",
        "write_chrome_trace",
        "read_spans",
        "MetricsRegistry",
        "get_registry",
        # fused kernels + pipelined rounds (PR 8)
        "pipeline_rounds",
        "ir_compute_time",
        "local_op_unit_work",
        "MAC_SECONDS",
        "gf_matmul",
        "butterfly_mac",
        # the continuous-batching serving tier (PR 9)
        "ContinuousEngine",
        "SlotScheduler",
        "ServeReport",
        "Request",
        "poisson_trace",
        "bucket_for",
        "prefill_into_cache",
        "supports_prefill",
        "make_prefill_step",
        "LengthBand",
        # coded straggler-tolerant serving (PR 10)
        "CodedServeGuard",
        "CodedDecodeGroup",
        "FaultInjector",
        "ProcessHostPool",
        "build_lcc",
        "lcc_encode_collective",
        "lcc_decode",
        "shard_state_limbs",
    ]:
        assert name in all_docs, f"public symbol {name} not mentioned in docs"
