"""Spec-driven traversal engine: registry, caps policy, dispatch model.

The bit-exact engine-vs-wrapper parity over the full operator matrix lives
in oracle.assert_matches_oracle (every oracle-backed test drives it); this
file covers the engine's static surfaces — the spec registry, the unified
caps policy (frozen against the pre-unification values for the bench
configurations), and the stage-model dispatch validation.
"""
import numpy as np
import pytest

from repro.core import caps, rtree, traversal
from repro.core.counters import Counters, StageModel
from repro.core.layouts import LANES

from conftest import uniform_rects


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_registry_has_all_operators():
    names = traversal.spec_names()
    assert set(names) >= {"select", "join", "knn", "knn_join", "browse"}
    for name in names:
        spec = traversal.get_spec(name)
        assert spec.kind in ("mask", "distance")
        assert callable(spec.builder)
        assert spec.stage_model.inner > 0 and spec.stage_model.leaf > 0


def test_registry_unknown_spec():
    with pytest.raises(KeyError):
        traversal.get_spec("nope")


# ---------------------------------------------------------------------------
# unified caps policy — regression against the pre-unification outputs
# ---------------------------------------------------------------------------

class _FakeLevel:
    def __init__(self, n):
        self.n_nodes = n


class _FakeTree:
    """Caps only consume (height, fanout, per-level node counts)."""
    def __init__(self, fanout, sizes):
        self.fanout = fanout
        self.height = len(sizes)
        self.levels = [_FakeLevel(n) for n in sizes]


# (fanout, level sizes leaf→root) for the bench configurations, with the
# caps each policy produced before the unification (frozen 2026-07).
_BENCH_TREES = {
    "select_1m_f16": (16, [62500, 3910, 256, 16, 1]),
    "select_200k_f16": (16, [12544, 784, 49, 4, 1]),
    "f64_200k": (64, [3136, 49, 1]),
    "f256_50k": (256, [196, 1]),
    "oracle_2500_f16": (16, [160, 12, 1]),
}

_EXPECTED = {
    # (policy, tree key, target) → caps.  2026-08: the boosted select leaf
    # step now re-clamps to the leaf level's node count (a frontier of
    # distinct node ids can never exceed it) — f64_200k / f256_50k /
    # oracle_2500_f16 shrank accordingly; the other entries were already
    # below their leaf counts.
    ("select", "select_1m_f16", 4096): (128, 128, 1024, 16384),
    ("select", "select_200k_f16", 4096): (128, 128, 896, 12544),
    ("select", "select_200k_f16", 1000): (128, 128, 256, 4096),
    ("select", "f64_200k", 4096): (128, 3136),
    ("select", "f256_50k", 4096): (196,),
    ("select", "oracle_2500_f16", 4096): (128, 160),
    ("knn", "select_200k_f16", 8): (128, 128, 128, 128),
    ("knn", "select_200k_f16", 64): (128, 128, 128, 256),
    ("knn", "f64_200k", 8): (128, 128),
    ("knn", "oracle_2500_f16", 64): (128, 256),
    ("join", "select_200k_f16", 65536): (1024, 1024, 1024, 16384, 65536),
    ("join", "select_200k_f16", 16384): (1024, 1024, 1024, 4096, 16384),
    ("join", "f64_200k", 65536): (1024, 4096, 65536),
    ("join", "oracle_2500_f16", 16384): (1024, 4096, 16384),
}


@pytest.mark.parametrize("policy,tree_key,target",
                         sorted(_EXPECTED, key=str))
def test_caps_reproduce_pre_unification_values(policy, tree_key, target):
    fanout, sizes = _BENCH_TREES[tree_key]
    tree = _FakeTree(fanout, sizes)
    if policy == "select":
        got = caps.select_frontier_caps(tree, target)
    elif policy == "knn":
        got = caps.knn_frontier_caps(tree, target)
    else:
        got = caps.join_pair_caps(tree.height, fanout, target)
    assert got == _EXPECTED[(policy, tree_key, target)]


def test_caps_bench_slack_variant():
    # bench_select passes slack=2, min_cap=32 — frozen value for 200k/f16
    tree = _FakeTree(*_BENCH_TREES["select_200k_f16"])
    assert caps.select_frontier_caps(tree, 4096, slack=2, min_cap=32) == \
        (128, 128, 512, 8192)


def test_caps_match_real_tree():
    """The fake-tree regression values reproduce on an actually-built tree
    (same level sizes ⇒ same caps through the module-level wrappers)."""
    from repro.core import join_vector, knn_vector, select_vector
    rng = np.random.default_rng(3)
    tree = rtree.build_rtree(uniform_rects(rng, 2500, eps=0.002), fanout=16)
    fake = _FakeTree(tree.fanout,
                     [lvl.n_nodes for lvl in tree.levels])
    assert select_vector.frontier_caps(tree, 4096) == \
        caps.select_frontier_caps(fake, 4096)
    assert knn_vector.knn_frontier_caps(tree, 8) == \
        caps.knn_frontier_caps(fake, 8)
    assert join_vector.default_pair_caps(tree.height, 16, 16384) == \
        caps.join_pair_caps(fake.height, 16, 16384)


def test_caps_lane_round_in_one_place():
    """Row-frontier caps are lane multiples OR exact level node counts (the
    node-count clamp is the one thing allowed to break lane rounding — a
    frontier of distinct node ids can never exceed the level size); the
    join's flat pair caps are exempt by policy, not by a second rounding
    implementation."""
    tree = _FakeTree(*_BENCH_TREES["select_200k_f16"])
    sizes = [lvl.n_nodes for lvl in tree.levels]
    got = caps.select_frontier_caps(tree, 1000)
    for c, n in zip(got, reversed(sizes[:-1])):
        assert c % LANES == 0 or c == n
    for c in caps.knn_frontier_caps(tree, 7):
        assert c % LANES == 0
    # the leaf-entering select cap still clears the requested result budget
    # (up to the number of leaf nodes that exist)
    assert got[-1] >= min(1000, sizes[0])
    # boost re-clamp: a tiny tree cannot be asked for more leaf-frontier
    # rows than it has leaf nodes
    small = _FakeTree(*_BENCH_TREES["f256_50k"])
    assert caps.select_frontier_caps(small, 4096) == (196,)
    fr, defer, pool = caps.browse_caps(tree, 7)
    for c in fr + defer[:-1] + (pool,):
        assert c % LANES == 0
    assert defer[-1] == 1                       # the root defer slot
    assert len(defer) == tree.height
    assert pool >= 7
    from repro.core.layouts import round_up_to_lanes
    assert round_up_to_lanes(1) == LANES
    assert round_up_to_lanes(128) == 128
    assert round_up_to_lanes(129) == 256


def test_browse_caps_layout_lane_floor():
    """D3 (256-lane) browse floors are no longer double-rounded: a 128-row
    static floor stays 128 rows (a power of two below the lane count is a
    valid adaptive width), while caps at or above the lane count stay lane
    multiples; d1 caps are bit-identical to the historical policy."""
    tree = _FakeTree(*_BENCH_TREES["select_200k_f16"])
    fr1, de1, p1 = caps.browse_caps(tree, 7)
    fr3, de3, p3 = caps.browse_caps(tree, 7, lanes=256)
    for c in fr3 + de3[:-1] + (p3,):
        assert (c >= 256 and c % 256 == 0) or \
            (c < 256 and c & (c - 1) == 0)
    # the historical 128-row floors survive as 128 (not doubled to 256):
    # every d1 cap of exactly 128 maps to 128 in the d3 policy
    assert any(a == 128 for a in fr1 + de1[:-1])
    for a, b in zip(fr1 + de1[:-1] + (p1,), fr3 + de3[:-1] + (p3,)):
        if a == 128:
            assert b == 128
    # d1 caps are bit-identical to the historical policy (lane multiples
    # are fixed points of the adaptive rounding)
    assert (fr1, de1, p1) == caps.browse_caps(tree, 7, lanes=LANES)


# ---------------------------------------------------------------------------
# two-tier capacity system: adaptive ≡ static, escalation repairs overflow
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("op", ["select", "join", "knn", "knn_join",
                                "knn_filtered"])
def test_adaptive_static_parity(op):
    """Every layout × operator cell: the occupancy-adaptive default engine
    returns results bit-identical to the static-caps engine (and still
    matches the brute-force oracle)."""
    from oracle import assert_adaptive_static_parity
    assert assert_adaptive_static_parity(op) > 0


def test_escalating_engine_repairs_overflow():
    """A deliberately under-sized tight tier overflows, the wrapper
    escalates to the full tier, and the final answer is bit-identical to
    running the full tier directly (with the escalation counted)."""
    import jax.numpy as jnp
    from repro.core import select_vector
    rng = np.random.default_rng(11)
    rects = uniform_rects(rng, 3000, eps=0.004)
    tree = rtree.build_rtree(rects, fanout=16)
    lo = rng.random((4, 2)).astype(np.float32) * 0.6
    qs = jnp.asarray(np.concatenate([lo, lo + np.float32(0.3)], axis=1))
    full = caps.select_frontier_caps(tree, 4096)
    tight = (1,) * len(full)               # guaranteed to overflow
    esc = traversal.maybe_escalating(
        lambda c: select_vector.make_select_bfs(tree, caps=c,
                                                result_cap=4096),
        tight, full)
    res, counts, ctr = esc(qs)
    assert esc.escalation_count() == 1
    assert int(ctr.escalations) == 1
    ref = select_vector.make_select_bfs(tree, caps=full, result_cap=4096)
    rres, rcounts, rctr = ref(qs)
    np.testing.assert_array_equal(np.asarray(res), np.asarray(rres))
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(rcounts))
    # identical tiers short-circuit to a plain engine (no wrapper)
    plain = traversal.maybe_escalating(
        lambda c: select_vector.make_select_bfs(tree, caps=c,
                                                result_cap=4096),
        full, full)
    assert not hasattr(plain, "escalation_count")


def test_pinned_escalating_engine_counts_full_tier_calls_not_escalations():
    """After ``stick_after`` escalations in a row the engine pins itself to
    the full tier: later calls run that tier once, with no overflow
    read-back, and count as full-tier calls, not as escalations."""
    from repro.core import select_vector
    from repro.runtime import trace
    rng = np.random.default_rng(12)
    rects = uniform_rects(rng, 3000, eps=0.004)
    tree = rtree.build_rtree(rects, fanout=16)
    lo = rng.random((4, 2)).astype(np.float32) * 0.6
    qs = np.concatenate([lo, lo + np.float32(0.3)], axis=1)
    full = caps.select_frontier_caps(tree, 4096)
    esc = traversal.make_escalating_engine(
        lambda c: select_vector.make_select_bfs(tree, caps=c,
                                                result_cap=4096),
        (1,) * len(full), full, stick_after=2)
    before = trace.snapshot()
    ctrs = [esc(qs)[-1] for _ in range(5)]
    after = trace.snapshot()
    assert esc.stuck()
    assert esc.escalation_count() == 2
    assert [int(c.escalations) for c in ctrs] == [1, 1, 0, 0, 0]

    def delta(kind, name, field=None):
        a, b = after[kind].get(name), before[kind].get(name)
        if field is not None:
            a, b = a and a[field], b and b[field]
        return (a or 0) - (b or 0)
    assert delta("counters", "repro.engine.full_tier_calls") == 5
    assert delta("spans", "repro.engine.overflow_check", "count") == 2


def test_counters_occupancy_recorded():
    """Engines record per-step live/padded lane tallies; occupancy() is
    the live fraction and the adaptive tier never reports lower occupancy
    than the static tier on the same workload."""
    import jax.numpy as jnp
    from repro.core import knn_vector
    rng = np.random.default_rng(7)
    rects = uniform_rects(rng, 2500, eps=0.002)
    tree = rtree.build_rtree(rects, fanout=16)
    qs = jnp.asarray(rng.random((4, 2)).astype(np.float32))
    _, _, ca = knn_vector.make_knn_bfs(tree, k=4, caps_mode="adaptive")(qs)
    _, _, cs = knn_vector.make_knn_bfs(tree, k=4, caps_mode="static")(qs)
    for c in (ca, cs):
        live = np.asarray(c.lanes_live)
        padded = np.asarray(c.lanes_padded)
        assert live.shape == padded.shape and live.ndim == 1
        assert int(live.sum()) > 0
        assert 0.0 < c.occupancy() <= 1.0
    assert ca.occupancy() >= cs.occupancy()
    d = ca.asdict()
    assert isinstance(d["lanes_live"], list)
    assert isinstance(d["nodes_visited"], int)


# ---------------------------------------------------------------------------
# stage-model dispatch validation
# ---------------------------------------------------------------------------

def test_stage_model_totals():
    sm = StageModel(inner=4, leaf=3, fused=1)
    assert sm.total(1) == 3                      # leaf-only tree
    assert sm.total(4) == 3 * 4 + 3
    assert sm.total(4, fused=True) == 4
    assert sm.total(3, descents=5) == 5 * (2 * 4 + 3)
    with pytest.raises(ValueError):
        StageModel(inner=8, leaf=3).total(3, fused=True)


def test_counters_validate_dispatches():
    sm = StageModel(inner=3, leaf=3, fused=1)
    Counters(dispatches=9).validate_dispatches(sm, 3)
    with pytest.raises(AssertionError):
        Counters(dispatches=8).validate_dispatches(sm, 3)
    with pytest.raises(AssertionError):
        # a fused run must not pass validation against the unfused model
        Counters(dispatches=3).validate_dispatches(sm, 3, fused=False)


@pytest.mark.parametrize("fused,backend", [(False, None), (True, "xla")])
def test_engine_charges_spec_stage_model(fused, backend):
    """An under- (or over-) counting operator cannot pass: the engine's
    tally is derived from the spec the operator registered."""
    import jax.numpy as jnp
    rng = np.random.default_rng(5)
    tree = rtree.build_rtree(uniform_rects(rng, 2000, eps=0.003), fanout=16)
    q = jnp.asarray(rng.random((3, 2)).astype(np.float32))
    fn = traversal.build("knn", tree, k=5, backend=backend, fused=fused)
    _, _, ctr = fn(q)
    spec = traversal.get_spec("knn")
    ctr.validate_dispatches(spec.stage_model, tree.height, fused=fused)
    wrong = StageModel(inner=spec.stage_model.inner + 1,
                       leaf=spec.stage_model.leaf,
                       fused=(spec.stage_model.fused or 0) + 1)
    with pytest.raises(AssertionError):
        ctr.validate_dispatches(wrong, tree.height, fused=fused)
