"""N-level hierarchy equivalence suite (ISSUE 10).

Covers the :class:`repro.hier.HierarchySpec` API end to end:

- constructors, validation and the ladder's combinators;
- depth-1 spec is the flat pipeline;
- depth-3/4 maps keep the bijection and monotone-objective invariants
  across the scenario registry;
- equal specs built through different constructors canonicalise to
  the SAME config signature (cache-key stability);
- anything but a spec fails at CONFIG CONSTRUCTION with a ValueError
  naming ``HierarchySpec``, and the retired flat refine fields are not
  config fields at all.
"""

import dataclasses
import warnings

import numpy as np
import pytest

from repro.core import evaluate, gemini_xk7, sfc_allocation, stencil_graph
from repro.core.signature import config_signature
from repro.hier import (HierarchySpec, Level, group_units, polish_groups,
                        refine_qap)
from repro.hier.spec import (DEFAULT_GROUP_ARITY, DEFAULT_QAP_ROUNDS,
                             DEFAULT_REFINE_ROUNDS)
from repro.mapping import MappingPipeline, PipelineConfig
from repro.serve.scenarios import Scenario


def _grid(n):
    e = int(np.log2(n))
    a = e // 3
    return (1 << (e - 2 * a), 1 << a, 1 << a)


def _xk7_case(side=8, cores=16, nfragments=4, seed=1):
    m = gemini_xk7(dims=(2 * side, side, side), cores_per_node=cores)
    n = side ** 3 * cores  # half the machine
    alloc = sfc_allocation(m, n, nfragments=nfragments, seed=seed)
    g = stencil_graph(_grid(n))
    return m, alloc, g


def _map(g, alloc, **kw):
    kw.setdefault("sfc", "H")
    kw.setdefault("rotations", 4)
    return MappingPipeline(PipelineConfig(**kw)).map(g, alloc)


# ---------------------------------------------------------------------------
# spec construction and validation
# ---------------------------------------------------------------------------

def test_depth_accessors():
    assert HierarchySpec.flat().depth == 1
    assert HierarchySpec.flat().is_flat
    assert HierarchySpec.node().depth == 2
    assert HierarchySpec.node().kind == "node"
    s = HierarchySpec.with_depth(4)
    assert s.depth == 4 and s.kind == "depth4"
    assert [lv.name for lv in s.levels] == ["node", "socket", "rack"]


def test_with_depth_2_equals_node():
    assert HierarchySpec.with_depth(2) == HierarchySpec.node()
    assert HierarchySpec.from_string("depth2") == HierarchySpec.node()
    assert HierarchySpec.from_string("depth1") == HierarchySpec.flat()


def test_with_depth_default_budgets():
    s = HierarchySpec.with_depth(3)
    # polish supersedes the node level's bounded pass at depth >= 3
    assert s.levels[0].refine_rounds == 0
    assert s.levels[0].polish_rounds > 0
    assert s.levels[1].refine_mode == "qap"
    assert s.levels[1].refine_rounds == DEFAULT_QAP_ROUNDS
    assert s.levels[1].arity == DEFAULT_GROUP_ARITY
    # an explicit refine_rounds applies to every level
    s5 = HierarchySpec.with_depth(3, refine_rounds=5)
    assert all(lv.refine_rounds == 5 for lv in s5.levels)
    # depth 2 keeps the bit-identity budget
    assert (HierarchySpec.with_depth(2).levels[0].refine_rounds
            == DEFAULT_REFINE_ROUNDS)


def test_from_machine_derives_node_arity():
    m = gemini_xk7(dims=(4, 2, 2), cores_per_node=8)
    s = HierarchySpec.from_machine(m, depth=3)
    assert s.levels[0].arity == 8
    assert s.levels[1].arity == DEFAULT_GROUP_ARITY
    # machines without core dims keep arity=None (legacy derivation)
    from repro.core import make_machine
    flat_m = make_machine((4, 4), wrap=True)
    assert HierarchySpec.from_machine(flat_m).levels[0].arity is None


def test_level_validation():
    with pytest.raises(ValueError, match="arity"):
        Level("node", 1)
    with pytest.raises(ValueError, match="refine_mode"):
        Level("node", refine_mode="anneal")
    with pytest.raises(ValueError, match="polish_rounds"):
        Level("node", polish_rounds=-1)


def test_unknown_hierarchy_raises_at_config_construction():
    # the 4xx-style error arrives when the CONFIG is built — before any
    # mapping work, serve admission or degradation rung — and names the
    # spec type and the parser of hierarchy names
    for bad in ("node", "mesh", 42):
        with pytest.raises(ValueError,
                           match="HierarchySpec.*HierarchySpec.from_string"):
            PipelineConfig(hierarchy=bad)
    # the retired flat refine knobs are not config fields
    with pytest.raises(TypeError):
        PipelineConfig(refine_rounds=2)
    # the names parser still lists what it accepts
    with pytest.raises(ValueError, match="flat.*node.*depth<N>"):
        HierarchySpec.from_string("nod")


def test_spec_combinators():
    s = HierarchySpec.with_depth(4)
    assert s.truncated(2) == HierarchySpec(s.levels[:1])
    assert s.truncated(1).is_flat
    z = s.with_refine(rounds=0, polish=0)
    assert z.refine_rounds_total == 0 and z.polish_rounds_total == 0
    assert [lv.name for lv in z.levels] == [lv.name for lv in s.levels]


def test_flat_default_is_warning_free():
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        cfg = PipelineConfig()
        assert cfg.hierarchy == HierarchySpec.flat()
        cfg = PipelineConfig(hierarchy=HierarchySpec.with_depth(3))
        assert cfg.hierarchy.depth == 3


# ---------------------------------------------------------------------------
# equivalence: depth-1 == flat
# ---------------------------------------------------------------------------

def test_depth1_is_flat():
    _, alloc, g = _xk7_case(side=4)
    a = _map(g, alloc, hierarchy=HierarchySpec.flat())
    b = _map(g, alloc)  # default config: flat
    assert np.array_equal(a.task_to_proc, b.task_to_proc)
    assert a.stats["hierarchy"] == "flat"
    assert a.stats["depth"] == 1


# ---------------------------------------------------------------------------
# depth-3/4 invariants across the scenario registry
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("allocation", ["xk7_sparse", "bgq_block",
                                        "tpu_mesh", "fat_tree"])
@pytest.mark.parametrize("depth", [3, 4])
def test_deep_hierarchy_bijection_and_monotone(allocation, depth):
    sc = Scenario("minighost", allocation, "depth3", "wh", scale=512)
    g = sc.graph()
    alloc = sc.alloc_for(g)
    cfg = dataclasses.replace(sc.config(),
                              hierarchy=HierarchySpec.with_depth(depth))
    from repro.mapping import MappingPipeline
    res = MappingPipeline(cfg).map(g, alloc)
    t2p = res.task_to_proc
    assert len(t2p) == g.n
    if g.n == alloc.n:
        assert len(np.unique(t2p)) == g.n  # bijection
    assert res.stats["schema"] == 2
    assert res.stats["depth"] == depth
    assert len(res.stats["levels"]) == depth - 1
    # every per-level refine/polish history is monotone non-increasing
    for lv in res.stats["levels"]:
        hist = [h[0] for h in lv.get("refine_history", [])]
        assert all(x >= y - 1e-9 for x, y in zip(hist, hist[1:]))
        if "polish_initial" in lv:
            assert lv["polish_final"] <= lv["polish_initial"] + 1e-9
    # the reported score is the exact fine weighted hops
    assert evaluate(g, alloc, res)["weighted_hops"] == pytest.approx(
        res.score)


def test_deep_hierarchy_scenario_config_runs():
    from repro.mapping import MappingPipeline
    sc = Scenario("random", "xk7_sparse", "depth3", "wh", scale=512)
    req = sc.request()
    res = MappingPipeline(req.config).map(req.graph, req.alloc)
    assert res.stats["hierarchy"] == "depth3"


# ---------------------------------------------------------------------------
# refinement passes: sparse-QAP search and intra-group polish
# ---------------------------------------------------------------------------

def _cluster_case(seed=0):
    rng = np.random.default_rng(seed)
    m = gemini_xk7(dims=(8, 4, 4), cores_per_node=4)
    alloc = sfc_allocation(m, 256, nfragments=4, seed=3)
    from repro.hier import aggregate_tasks, router_view
    g = stencil_graph((8, 8, 4))
    rc, _, _ = router_view(alloc)
    agg = aggregate_tasks(g, len(rc))
    c2r = rng.permutation(len(rc))[:agg.nclusters]
    return m, agg.coarse, rc, c2r


def test_refine_qap_monotone_and_improves_random_start():
    m, coarse, rc, c2r = _cluster_case()
    out, stats = refine_qap(m, coarse, rc, c2r, rounds=4)
    hist = [h[0] for h in stats["refine_history"]]
    assert all(x >= y - 1e-9 for x, y in zip(hist, hist[1:]))
    assert stats["refine_final"] < stats["refine_initial"]
    assert sorted(out) == sorted(c2r)  # same units, re-ordered


def test_polish_groups_monotone_and_group_preserving():
    m, coarse, rc, c2r = _cluster_case(seed=1)
    member, _ = group_units(rc, len(rc) // 4)
    out, stats = polish_groups(m, coarse, rc, c2r, member, rounds=6)
    assert stats["polish_final"] <= stats["polish_initial"] + 1e-9
    assert stats["polish_accepted"] > 0  # random start: plenty to fix
    # polish NEVER moves a cluster out of its group
    assert np.array_equal(member[out], member[c2r])
    hist = [h[0] for h in stats["polish_history"]]
    assert all(x >= y - 1e-9 for x, y in zip(hist, hist[1:]))


def test_polish_zero_rounds_is_identity():
    m, coarse, rc, c2r = _cluster_case(seed=2)
    member, _ = group_units(rc, len(rc) // 4)
    out, stats = polish_groups(m, coarse, rc, c2r, member, rounds=0)
    assert np.array_equal(out, c2r)
    assert stats["polish_rounds_run"] == 0


# ---------------------------------------------------------------------------
# signature stability
# ---------------------------------------------------------------------------

def test_signature_stable_across_construction_paths():
    paths = [
        HierarchySpec.node(),
        HierarchySpec.with_depth(2),
        HierarchySpec.from_string("node"),
        HierarchySpec.from_string("depth2"),
        HierarchySpec((Level("node"),)),
    ]
    sigs = {config_signature(PipelineConfig(hierarchy=s)) for s in paths}
    assert len(sigs) == 1


def test_signature_distinguishes_depths_and_budgets():
    sigs = [config_signature(PipelineConfig(hierarchy=s)) for s in (
        HierarchySpec.flat(), HierarchySpec.node(),
        HierarchySpec.with_depth(3), HierarchySpec.with_depth(4),
        HierarchySpec.with_depth(3).with_refine(rounds=0, polish=0))]
    assert len(set(sigs)) == len(sigs)
