"""Spans of the coded layer and the serve loop.

A traced coded serve puts the snapshot's and the recovery's steps under
child spans that carry their counts, puts every phase of a serve-loop
iteration under a top-level span, and opens a profiler annotation of the
same name around each span. Untraced, the same serve opens nothing and
serves the same tokens. The recovery also runs through a zero-argument
wrapper of ``group.reconstruct``, as a caller that inspects the rebuilt
data installs one.
"""

import functools

import pytest

import jax

from repro.configs import smoke_config
from repro.models import build_model
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import Tracer, optional_span
from repro.serve import CodedServeGuard, ContinuousEngine, FaultInjector, Request
from repro.serve.coded import GATHER_BLOCKS, SNAPSHOT_BLOCK

PROMPTS = [[5, 9, 2, 7, 1], [3, 3, 8], [11, 4, 6, 2], [2]]
MAX_NEW = 6
K, R = 3, 1
SNAPSHOT_CHILDREN = ("serve.snapshot.device", "serve.snapshot.to_host",
                     "serve.snapshot.store")
RECOVERY_CHILDREN = ("serve.recovery.fetch", "serve.recovery.decode",
                     "serve.recovery.to_device")
LOOP_PHASES = {"serve.admit", "serve.snapshot", "serve.decode_chunk",
               "serve.poll", "serve.recovery", "serve.harvest"}


class AnnotationRecorder:
    """Stands in for ``jax.profiler.TraceAnnotation``: records each name
    on entry and exit, starts no profile."""

    events: list = []

    def __init__(self, name, **kwargs):
        self.name = name

    def __enter__(self):
        self.events.append(("enter", self.name))
        return self

    def __exit__(self, *exc):
        self.events.append(("exit", self.name))
        return False


@pytest.fixture
def annotations(monkeypatch):
    AnnotationRecorder.events = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", AnnotationRecorder)
    return AnnotationRecorder.events


@functools.lru_cache(maxsize=1)
def _engine():
    cfg = smoke_config("qwen3-1.7b").replace(n_layers=2)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    return ContinuousEngine(
        model, params, n_slots=2, max_len=32, buckets=(8, 16),
        max_new_tokens=MAX_NEW, metrics=MetricsRegistry(),
    )


def _serve(tracer, wrap_reconstruct: bool = False):
    """A coded serve with one host killed after the first chunk; returns
    (tokens by request, guard, what the wrapper saw)."""
    eng = _engine()
    guard = CodedServeGuard(K=K, R=R, injector=FaultInjector(kills=((1, 2),)))
    seen = {}
    if wrap_reconstruct:
        reconstruct = guard.group.reconstruct

        def watched():
            seen["X"] = reconstruct()
            return seen["X"]

        guard.group.reconstruct = watched
    reqs = [Request(id=f"r{i}", prompt=p, max_new_tokens=MAX_NEW)
            for i, p in enumerate(PROMPTS)]
    saved = eng._tracer
    eng._tracer = tracer
    try:
        rep = eng.serve(reqs, greedy=True, sync_every=2, guard=guard)
    finally:
        eng._tracer = saved
    assert rep.recoveries == 1
    return {r.id: tuple(r.tokens) for r in rep.results}, guard, seen


@functools.lru_cache(maxsize=2)
def _traced(wrap_reconstruct: bool):
    tracer = Tracer()
    toks, guard, seen = _serve(tracer, wrap_reconstruct)
    return tracer.spans, toks, guard, seen


def _children(spans, parent_name):
    """(parent, [its children]) for every span named ``parent_name``."""
    out = []
    for i, sp in enumerate(spans):
        if sp.name == parent_name:
            out.append((sp, [c for c in spans if c.parent == i]))
    return out


def test_tracer_span_opens_an_annotation_of_its_name(annotations):
    tr = Tracer()
    with tr.span("serve.outer", tick=3):
        with tr.span("serve.outer.inner") as sp:
            sp.attrs["bytes"] = 8
    with pytest.raises(KeyError):
        with tr.span("serve.raises"):
            raise KeyError("x")
    assert annotations == [
        ("enter", "serve.outer"), ("enter", "serve.outer.inner"),
        ("exit", "serve.outer.inner"), ("exit", "serve.outer"),
        ("enter", "serve.raises"), ("exit", "serve.raises"),
    ]
    assert [s.name for s in tr.spans] == ["serve.outer", "serve.outer.inner",
                                          "serve.raises"]
    assert tr.spans[1].parent == 0 and tr.spans[1].attrs == {"bytes": 8}
    assert tr._stack == []
    with optional_span(None, "serve.off", tick=1) as sp:
        assert sp is None
    assert len(annotations) == 6


@pytest.mark.parametrize("wrap_reconstruct", [False, True], ids=["plain", "wrapped"])
@pytest.mark.parametrize("parent,children", [
    ("serve.snapshot", SNAPSHOT_CHILDREN),
    ("serve.recovery", RECOVERY_CHILDREN),
], ids=["snapshot", "recovery"])
def test_coded_span_tree(parent, children, wrap_reconstruct):
    """Each snapshot and recovery holds its steps, in order, as children
    that sum to no more than it; every child lies under its parent."""
    spans, _, guard, _ = _traced(wrap_reconstruct)
    tree = _children(spans, parent)
    want = guard.snapshots if parent == "serve.snapshot" else 1
    assert len(tree) == want > 0
    for sp, kids in tree:
        assert sp.parent is None  # a top-level phase of the serve loop
        assert tuple(c.name for c in kids) == children
        assert sum(c.dur_us for c in kids) <= sp.dur_us
    for sp in spans:
        if sp.name in children:
            assert spans[sp.parent].name == parent


@pytest.mark.parametrize("wrap_reconstruct", [False, True], ids=["plain", "wrapped"])
def test_coded_span_counts(wrap_reconstruct):
    """The counts on the spans: words K × S on the device and in the
    decode; to the host, the blocks copied and their bytes (N rows of
    whole gather calls), every block at the first snapshot and at the one
    after the recovery; K × S × 4 fetched and back to the device, one
    shard stored per live host."""
    spans, toks, guard, seen = _traced(wrap_reconstruct)
    by = {}
    for sp in spans:
        by.setdefault(sp.name, []).append(sp.attrs)
    S = guard._meta.total // K + (guard._meta.total % K > 0)
    assert all(a["words"] == K * S for a in by["serve.snapshot.device"])
    block = min(SNAPSHOT_BLOCK, S)
    n_blocks = -(-S // block)
    per_call = min(GATHER_BLOCKS, n_blocks)
    to_host = by["serve.snapshot.to_host"]
    assert all(a["bytes"] == -(-a["blocks"] // per_call) * per_call * guard.N * block * 4
               for a in to_host)
    assert [a["full"] for a in to_host][:2] == [1, 1]  # the kill follows the first chunk
    assert all(a["blocks"] == n_blocks for a in to_host if a["full"])
    assert all(a["blocks"] <= n_blocks for a in to_host)
    assert [a["tick"] for a in by["serve.snapshot"]] == sorted(
        a["tick"] for a in by["serve.snapshot"])
    stored = [a["shards"] for a in by["serve.snapshot.store"]]
    assert stored[0] == guard.N and stored[-1] == guard.N - 1
    (fetch,), (decode,), (back,) = (by[n] for n in RECOVERY_CHILDREN)
    assert fetch == {"bytes": K * S * 4, "responders": K}
    assert decode == {"words": K * S}
    assert back == {"bytes": K * S * 4}
    assert by["serve.recovery"] == [{"hosts": "[2]", "tick": 0}]
    # the wrapper saw the rebuilt data, and the tokens are the unwrapped run's
    assert ("X" in seen) == wrap_reconstruct
    if wrap_reconstruct:
        assert seen["X"].shape == (K, S)
        assert toks == _traced(False)[1]


def test_serve_loop_phases_are_top_level_spans():
    spans, toks, guard, _ = _traced(False)
    top = [sp for sp in spans if sp.parent is None]
    assert {sp.name for sp in top} == LOOP_PHASES
    for sp in spans:
        if sp.name == "serve.prefill":
            assert spans[sp.parent].name == "serve.admit"
    admits = [sp.attrs["admitted"] for sp in top if sp.name == "serve.admit"]
    harvests = [sp.attrs["finished"] for sp in top if sp.name == "serve.harvest"]
    assert sum(admits) == sum(harvests) == len(PROMPTS) == len(toks)
    chunks = [sp.attrs for sp in top if sp.name == "serve.decode_chunk"]
    assert [c.get("replay") for c in chunks].count(1) == 1
    assert len(chunks) == guard.snapshots + 1
    polls = [sp for sp in top if sp.name == "serve.poll"]
    assert len(polls) == guard.snapshots
    assert all(sp.dur_us >= 0 for sp in spans)


def test_untraced_serve_records_nothing_and_serves_the_same(annotations):
    traced_toks = _traced(False)[1]
    annotations.clear()
    toks, guard, _ = _serve(None)
    assert toks == traced_toks
    assert annotations == []
    assert guard.group.tracer is None and guard.snapshots > 0
