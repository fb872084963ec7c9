"""Hierarchical mapping subsystem tests (repro.hier).

Covers: geometric aggregation (balance, centroids, volume
conservation), the router view, the two-level map (bijection, exact
coarse == fine volume-weighted metrics, the ~cores_per_node x
engine-pass point reduction, quality vs flat), the monotone swap
refinement, and the pipeline / meshmap wiring."""

import numpy as np
import pytest

from repro import obs
from repro.core import (TaskGraph, evaluate, gemini_xk7, identity_mapping,
                        logical_mesh_graph, make_machine, sfc_allocation,
                        stencil_graph, tpu_v5e_multipod)
from repro.core.machine import Allocation
from repro.core.metrics import evaluate_candidates
from repro.hier import (aggregate_tasks, assign_cores, refine_swaps,
                        router_view)
from repro.mapping import (HierarchySpec, MappingPipeline,
                           PipelineConfig)


def _grid(n):
    e = int(np.log2(n))
    a = e // 3
    return (1 << (e - 2 * a), 1 << a, 1 << a)


def _pipe(**kw):
    return MappingPipeline(PipelineConfig(**kw))


def _xk7_case(side=8, cores=16, nfragments=4, seed=1):
    m = gemini_xk7(dims=(2 * side, side, side), cores_per_node=cores)
    n = side ** 3 * cores  # half the machine
    alloc = sfc_allocation(m, n, nfragments=nfragments, seed=seed)
    g = stencil_graph(_grid(n))
    assert g.n == n
    return m, alloc, g


# ---------------------------------------------------------------------------
# aggregation
# ---------------------------------------------------------------------------

def test_aggregate_balanced_sizes_and_labels():
    g = stencil_graph((16, 16))
    agg = aggregate_tasks(g, 16)
    assert agg.nclusters == 16
    assert agg.sizes.sum() == g.n
    assert (agg.sizes == 16).all()  # unit weights: perfectly balanced
    assert agg.labels.min() == 0 and agg.labels.max() == 15
    assert np.array_equal(np.bincount(agg.labels), agg.sizes)


def test_aggregate_centroids_are_member_means():
    rng = np.random.default_rng(0)
    g = stencil_graph((8, 8))
    agg = aggregate_tasks(g, 4)
    for c in range(4):
        members = g.coords[agg.labels == c]
        assert np.allclose(agg.coarse.coords[c], members.mean(axis=0))
    # weighted centroids
    w = rng.uniform(0.5, 2.0, g.n)
    aggw = aggregate_tasks(g, 4, task_weights=w)
    for c in range(4):
        mask = aggw.labels == c
        expect = np.average(g.coords[mask], axis=0, weights=w[mask])
        assert np.allclose(aggw.coarse.coords[c], expect)
        assert np.isclose(aggw.weights[c], w[mask].sum())


def test_aggregate_volume_conservation_no_self_edges():
    g = stencil_graph((8, 8, 8))
    agg = aggregate_tasks(g, 32)
    assert (agg.coarse.edges[:, 0] != agg.coarse.edges[:, 1]).all()
    total = g.weights.sum()
    assert np.isclose(agg.coarse.weights.sum() + agg.intra_volume, total)
    # contracted volume between a cluster pair equals the fine volume
    ce = agg.labels[g.edges]
    a, b = agg.coarse.edges[0]
    fine = g.weights[(ce[:, 0] == a) & (ce[:, 1] == b)].sum()
    assert np.isclose(agg.coarse.weights[0], fine)


def test_aggregate_bounds():
    g = stencil_graph((4, 4))
    with pytest.raises(ValueError):
        aggregate_tasks(g, 0)
    with pytest.raises(ValueError):
        aggregate_tasks(g, 17)
    one = aggregate_tasks(g, 1)
    assert one.nclusters == 1 and len(one.coarse.edges) == 0
    assert np.isclose(one.intra_volume, g.weights.sum())


# ---------------------------------------------------------------------------
# router view
# ---------------------------------------------------------------------------

def test_router_view_roundtrip():
    m = gemini_xk7(dims=(4, 4, 4), cores_per_node=16)
    alloc = sfc_allocation(m, 8 * 16, seed=0)
    rc, core_router, ralloc = router_view(alloc)
    assert len(rc) == 8
    assert len(core_router) == alloc.n
    # every core row matches its router's network coords
    assert np.array_equal(alloc.coords[:, :3], rc[core_router])
    # the router allocation zero-pads core dims
    assert ralloc.coords.shape == (8, 4)
    assert (ralloc.coords[:, 3] == 0).all()


# ---------------------------------------------------------------------------
# two-level map
# ---------------------------------------------------------------------------

def test_hier_bijection_and_quality_vs_flat():
    m, alloc, g = _xk7_case()
    flat = _pipe(sfc="FZ", shift=True, rotations=8)
    node = _pipe(sfc="FZ", shift=True, rotations=8,
                 hierarchy=HierarchySpec.node())
    rf, rn = flat.map(g, alloc), node.map(g, alloc)
    assert np.array_equal(np.sort(rn.task_to_proc), np.arange(g.n))
    ef, en = evaluate(g, alloc, rf), evaluate(g, alloc, rn)
    assert en["weighted_hops"] <= 1.05 * ef["weighted_hops"]
    base = evaluate(g, alloc, identity_mapping(g, alloc))
    assert en["weighted_hops"] <= base["weighted_hops"]


def test_hier_point_reduction_matches_cores_per_node():
    m, alloc, g = _xk7_case()
    flat = _pipe(sfc="FZ").map(g, alloc)
    node = _pipe(sfc="FZ", hierarchy=HierarchySpec.node()).map(g, alloc)
    assert flat.stats["sweep_points"] == 2 * g.n
    assert node.stats["sweep_points"] == 2 * g.n // 16
    assert flat.stats["sweep_points"] / node.stats["sweep_points"] == 16
    assert node.stats["cores_per_node"] == 16
    assert node.stats["nclusters"] == node.stats["nrouters"] == g.n // 16


def test_coarse_score_equals_fine_weighted_hops():
    """Every task carries its node's router coordinates, so the
    contracted graph's weighted_hops is EXACTLY the fine mapping's."""
    m, alloc, g = _xk7_case(nfragments=2, seed=5)
    rn = _pipe(sfc="FZ", hierarchy=HierarchySpec.node()).map(g, alloc)
    fine = evaluate(g, alloc, rn)["weighted_hops"]
    assert rn.score == rn.stats["refine_final"] == fine


def test_hierarchy_flat_is_default_and_unchanged():
    m, alloc, g = _xk7_case(nfragments=2, seed=3)
    default = _pipe(sfc="FZ", rotations=4).map(g, alloc)
    flat = _pipe(sfc="FZ", rotations=4,
                 hierarchy=HierarchySpec.flat()).map(g, alloc)
    assert np.array_equal(default.task_to_proc, flat.task_to_proc)
    assert default.stats["hierarchy"] == "flat"


def test_invalid_hierarchy_rejected():
    m, alloc, g = _xk7_case(nfragments=1)
    with pytest.raises(ValueError, match="hierarchy"):
        MappingPipeline(PipelineConfig(hierarchy="bogus")).map(g, alloc)


def test_hier_machine_without_core_dims():
    """No core dims: one cluster per router (degenerate coarsening) —
    still a valid bijection, never worse than identity."""
    m = make_machine((16, 16), wrap=True)
    alloc = sfc_allocation(m, 64, nfragments=4, seed=7)
    g = stencil_graph((8, 8))
    res = MappingPipeline(PipelineConfig(hierarchy=HierarchySpec.node())).map(g, alloc)
    assert np.array_equal(np.sort(res.task_to_proc), np.arange(64))
    base = evaluate(g, alloc, identity_mapping(g, alloc))
    assert evaluate(g, alloc, res)["weighted_hops"] \
        <= base["weighted_hops"]


def test_hier_fewer_tasks_than_nodes():
    m = gemini_xk7(dims=(8, 4, 4), cores_per_node=16)
    alloc = sfc_allocation(m, 64 * 16, seed=0)  # 64 routers
    g = stencil_graph((16, 16))  # 256 tasks -> 16 clusters of 16
    res = MappingPipeline(PipelineConfig(hierarchy=HierarchySpec.node())).map(g, alloc)
    assert res.stats["nclusters"] == 16
    procs = np.unique(res.task_to_proc)
    assert len(procs) == 256  # distinct cores (16 routers x 16 cores)
    routers = np.unique(alloc.coords[res.task_to_proc][:, :3], axis=0)
    assert len(routers) == 16


def test_hier_bijection_with_uneven_router_core_counts():
    """nnodes not a multiple of cores_per_node trims the last router:
    the expansion must spill over-capacity tasks to free cores instead
    of oversubscribing the trimmed node (was a silent bijection break)."""
    m = gemini_xk7(dims=(4, 4, 4), cores_per_node=16)
    alloc = sfc_allocation(m, 100, seed=0)  # 6 full routers + 4 cores
    g = stencil_graph((10, 10))
    res = MappingPipeline(PipelineConfig(hierarchy=HierarchySpec.node())).map(g, alloc)
    assert np.array_equal(np.sort(res.task_to_proc), np.arange(100))


def test_hier_hilbert_sfc():
    """sfc="H" runs Hilbert numbering through aggregation and the
    coarse sweep (no silent substitution)."""
    m, alloc, g = _xk7_case(side=4, nfragments=2, seed=2)
    res = MappingPipeline(PipelineConfig(sfc="H",
                                         hierarchy=HierarchySpec.node())).map(g, alloc)
    assert np.array_equal(np.sort(res.task_to_proc), np.arange(g.n))
    base = evaluate(g, alloc, identity_mapping(g, alloc))
    assert evaluate(g, alloc, res)["weighted_hops"] \
        <= base["weighted_hops"]


def test_hier_oversubscribed_cores():
    m = gemini_xk7(dims=(4, 4, 2), cores_per_node=4)
    alloc = sfc_allocation(m, 64, seed=0)  # 16 routers x 4 cores
    g = stencil_graph((16, 8))  # 128 tasks on 64 cores
    res = MappingPipeline(PipelineConfig(hierarchy=HierarchySpec.node())).map(g, alloc)
    counts = np.bincount(res.task_to_proc, minlength=64)
    assert (counts == 2).all()  # even 2-task-per-core round-robin


# ---------------------------------------------------------------------------
# refinement
# ---------------------------------------------------------------------------

def _coarse_problem(seed=0, nclusters=48, side=8):
    rng = np.random.default_rng(seed)
    machine = make_machine((side, side), wrap=True)
    routers = np.stack(np.unravel_index(
        rng.choice(side * side, nclusters, replace=False), (side, side)),
        axis=1)
    g = stencil_graph((8, nclusters // 8))
    agg = aggregate_tasks(g, nclusters)
    return machine, agg.coarse, routers


@pytest.mark.parametrize("seed", range(4))
def test_refine_monotone_from_scrambled_start(seed):
    machine, coarse, routers = _coarse_problem(seed)
    rng = np.random.default_rng(seed + 100)
    c2r = rng.permutation(len(routers))
    c2r, stats = refine_swaps(machine, coarse, routers, c2r,
                              rounds=6, top=16, degree=4)
    hist = [h[0] for h in stats["refine_history"]]
    assert all(b <= a + 1e-9 for a, b in zip(hist, hist[1:]))
    assert stats["refine_final"] <= stats["refine_initial"]
    # a scrambled start has plenty of slack: refinement must find some
    assert stats["refine_final"] < stats["refine_initial"]
    # the refined assignment is still a valid injection
    assert len(np.unique(c2r)) == len(c2r)
    # reported final score matches a fresh evaluation
    ev = evaluate_candidates(machine, coarse.edges, coarse.weights,
                             routers[c2r][None])
    assert np.isclose(ev["weighted_hops"][0], stats["refine_final"])


def test_refine_noop_on_optimal_line():
    """A contiguous 1D chain on a line of routers is hop-optimal:
    refinement must accept nothing and change nothing."""
    machine = make_machine((16,), wrap=False)
    g = stencil_graph((16,))
    coarse = TaskGraph(g.coords, g.edges, g.weights)
    routers = np.arange(16)[:, None]
    c2r, stats = refine_swaps(machine, coarse, routers, np.arange(16),
                              rounds=3, top=8, degree=2)
    assert np.array_equal(c2r, np.arange(16))
    assert stats["refine_final"] == stats["refine_initial"]


def test_refine_latency_objective_monotone():
    """Non-separable (latency) objectives take the full-stack scoring
    path and must stay monotone too."""
    machine, coarse, routers = _coarse_problem(2)
    rng = np.random.default_rng(9)
    start = rng.permutation(len(routers))
    c2r, stats = refine_swaps(
        machine, coarse, routers, start, rounds=3, top=12, degree=3,
        objective=("latency_max", "weighted_hops"))
    hist = stats["refine_history"]
    for a, b in zip(hist, hist[1:]):
        assert tuple(b) <= tuple(a)


def test_assign_cores_groups_clusters_on_nodes():
    m = gemini_xk7(dims=(4, 4, 2), cores_per_node=8)
    alloc = sfc_allocation(m, 4 * 8, seed=1)
    rc, core_router, _ = router_view(alloc)
    g = stencil_graph((8, 4))
    agg = aggregate_tasks(g, 4)
    c2r = np.array([2, 0, 3, 1])
    t2p = assign_cores(agg.labels, c2r, core_router, g.coords, len(rc))
    assert np.array_equal(np.sort(t2p), np.arange(32))
    # every task of a cluster lands on its assigned router
    for c in range(4):
        rows = alloc.coords[t2p[agg.labels == c]]
        assert (rows[:, :3] == rc[c2r[c]]).all()


# ---------------------------------------------------------------------------
# fused on-device refinement (ISSUE 9): bit-identity vs host refine_swaps
# ---------------------------------------------------------------------------

def _fused_refine_case():
    pytest.importorskip("jax")
    m = gemini_xk7(dims=(8, 4, 4), cores_per_node=4)
    alloc = sfc_allocation(m, 256, nfragments=2, seed=3)
    g = stencil_graph(_grid(256))
    return m, alloc, g


_REFINE_STAT_KEYS = ("refine_rounds_run", "refine_accepted",
                     "refine_evaluated", "refine_initial", "refine_final")


@pytest.mark.parametrize("sfc", ["FZ", "H"])
def test_fused_refinement_bit_identity_wh(sfc):
    """The device refinement folded into the fused program must
    reproduce the host refine_swaps trajectory decision-for-decision:
    same accepted swaps, same per-round history, same final mapping."""
    m, alloc, g = _fused_refine_case()
    kw = dict(sfc=sfc, rotations=6, hierarchy=HierarchySpec.node())
    host = MappingPipeline(PipelineConfig(**kw)).map(g, alloc)
    dev = MappingPipeline(PipelineConfig(
        partition_backend="jax", score_backend="jax", **kw)).map(g, alloc)
    assert dev.stats.get("fused_refine") is True
    assert not host.stats.get("fused_refine")
    assert np.array_equal(host.task_to_proc, dev.task_to_proc)
    assert dev.stats["refine_history"] == host.stats["refine_history"]
    for k in _REFINE_STAT_KEYS:
        assert dev.stats[k] == host.stats[k], k
    # monotone on device too
    hist = dev.stats["refine_history"]
    for a, b in zip(hist, hist[1:]):
        assert tuple(b) <= tuple(a)
    # span-derived timings keep the host schema (refine_s always there)
    assert {"fused_s", "refine_s", "coarsen_s", "total_s"} \
        <= set(dev.stats["timings"])
    assert {"partition_s", "score_s", "refine_s"} \
        <= set(host.stats["timings"])


def test_fused_refinement_bit_identity_latency_objective():
    """Non-separable objectives route the fused refinement through the
    SAME inlined scorer kind as the host comparison — the (latency_max,
    weighted_hops) trajectory must match exactly."""
    m, alloc, g = _fused_refine_case()
    kw = dict(sfc="FZ", rotations=6, hierarchy=HierarchySpec.node(),
              objective=("latency_max", "weighted_hops"))
    host = MappingPipeline(PipelineConfig(
        score_backend="jax", **kw)).map(g, alloc)
    dev = MappingPipeline(PipelineConfig(
        partition_backend="jax", score_backend="jax", **kw)).map(g, alloc)
    assert dev.stats.get("fused_refine") is True
    assert np.array_equal(host.task_to_proc, dev.task_to_proc)
    assert dev.stats["refine_history"] == host.stats["refine_history"]
    hist = dev.stats["refine_history"]
    for a, b in zip(hist, hist[1:]):
        assert tuple(b) <= tuple(a)


def test_fused_refinement_refine_rounds_zero():
    """refine_rounds=0 must skip the device loop but keep the stats and
    timings schema (history of length 1, nothing accepted)."""
    m, alloc, g = _fused_refine_case()
    res = MappingPipeline(PipelineConfig(
        sfc="FZ", rotations=4, hierarchy=HierarchySpec.node(refine_rounds=0),
        partition_backend="jax", score_backend="jax")).map(g, alloc)
    assert res.stats["refine_rounds_run"] == 0
    assert res.stats["refine_accepted"] == 0
    assert len(res.stats["refine_history"]) == 1
    assert res.stats["refine_final"] == res.stats["refine_initial"]
    assert "refine_s" in res.stats["timings"]


def test_fused_refinement_ladder_unfused_rung_bit_identical():
    """PR-7 ladder honesty: the ``unfused`` rung (fused="off") of a
    Hilbert + refinement config must exist and serve a bit-identical
    result — degradation moves WHERE the algorithm runs, not what it
    returns."""
    pytest.importorskip("jax")
    from repro.mapping.pipeline import fused_engages
    from repro.serve.resilience import degradation_ladder
    m, alloc, g = _fused_refine_case()
    cfg = PipelineConfig(sfc="H", rotations=6, hierarchy=HierarchySpec.node(),
                         partition_backend="jax", score_backend="jax")
    assert fused_engages(cfg)
    ladder = dict(degradation_ladder(cfg))
    assert "unfused" in ladder
    full = MappingPipeline(cfg).map(g, alloc)
    unfused = MappingPipeline(ladder["unfused"]).map(g, alloc)
    assert full.stats.get("fused_refine") is True
    assert not unfused.stats.get("fused_refine")
    assert np.array_equal(full.task_to_proc, unfused.task_to_proc)
    assert full.stats["refine_history"] == unfused.stats["refine_history"]


def _fused_node_pipeline(sfc="FZ"):
    return MappingPipeline(PipelineConfig(
        sfc=sfc, rotations=4, hierarchy=HierarchySpec.node(),
        partition_backend="jax", score_backend="jax"))


def test_node_map_spans_name_contraction_expansion_and_execution():
    """Coarsening's contraction, the refine stage's expansion and the
    fused program's run get spans of their own, under the stage spans
    they split; the stage timings and per-level stats keep their
    schema and still read the stage spans."""
    m, alloc, g = _fused_refine_case()
    res = _fused_node_pipeline().map(g, alloc)
    assert res.stats.get("fused_refine") is True
    spans = obs.finished(res.stats["trace_id"])
    by_id = {s.span_id: s for s in spans}

    def parents(name):
        return [by_id[s.parent_id].name for s in spans if s.name == name]

    assert parents("pipeline.contract") == ["pipeline.coarsen"]
    assert parents("partition.jax") == ["pipeline.coarsen"]
    assert parents("pipeline.expand") == ["pipeline.refine"]
    assert parents("fused.execute") == ["pipeline.fused"]
    contract = next(s for s in spans if s.name == "pipeline.contract")
    assert contract.attrs["points"] == g.n
    t = res.stats["timings"]
    assert set(t) == {"coarsen_s", "fused_s", "refine_s", "total_s"}
    stage = {s.name: s.duration_s for s in spans}
    assert t["coarsen_s"] == stage["pipeline.coarsen"]
    assert t["refine_s"] == stage["pipeline.refine"]
    assert t["fused_s"] == stage["pipeline.fused"]
    assert [set(lv) for lv in res.stats["levels"]] == [{
        "level", "name", "points", "clusters", "units", "coarsen_s",
        "map_s", "refine_s", "refine_accepted", "refine_evaluated",
        "refine_history"}]


def test_fused_program_scopes_name_its_stages(monkeypatch):
    """The fused program keeps its module name ``jit_run`` and runs
    each stage under a named scope, which the lowered program's
    locations carry."""
    from repro.mapping import fused

    lowered = []
    real = fused._program

    def spy(*key):
        fn = real(*key)

        def call(*args):
            lowered.append(fn.lower(*args))
            return fn(*args)
        return call

    spy.cache_info = real.cache_info
    monkeypatch.setattr(fused, "_program", spy)
    m, alloc, g = _fused_refine_case()
    res = _fused_node_pipeline().map(g, alloc)
    assert res.stats.get("fused_refine") is True
    text = lowered[0].as_text(debug_info=True)
    assert "module @jit_run " in text
    for scope in ("partition", "match", "score", "refine"):
        assert f"jit(run)/{scope}/" in text, scope


# ---------------------------------------------------------------------------
# meshmap wiring
# ---------------------------------------------------------------------------

def test_select_mapping_hierarchy_node_never_worse_than_default():
    from repro.meshmap.device_mesh import select_mapping
    m = tpu_v5e_multipod(npods=2, side=4)
    alloc = Allocation(m, np.stack(np.unravel_index(
        np.arange(32), m.dims), axis=1))
    ab = (1.0, 8.0, 64.0)
    g = logical_mesh_graph((2, 4, 4), ab)
    best, best_m, base_m = select_mapping(g, alloc, ab, rotations=4,
                                          hierarchy=HierarchySpec.node())
    assert best_m["latency_max"] <= base_m["latency_max"] + 1e-9
    assert np.array_equal(np.sort(best.task_to_proc), np.arange(32))
