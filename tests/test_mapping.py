"""Algorithm 1 mapping tests + transforms + machines."""

import numpy as np
import pytest

pytest.importorskip("hypothesis")  # optional dev dep (requirements-dev.txt)
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import (block_allocation, closest_subset,
                        cube_sphere_graph, evaluate, identity_mapping,
                        make_machine, sfc_allocation, shift_torus,
                        stencil_graph, tpu_v5e_multipod)
from repro.core.transforms import box_lift, scale_by_bandwidth
from repro.mapping import MappingPipeline, PipelineConfig


def _grid_coords(shape):
    ix = np.indices(shape)
    return np.stack([c.ravel() for c in ix], axis=1).astype(float)


def _algorithm1(task_coords, proc_coords, **kw):
    """Algorithm 1 for the identity rotation."""
    return MappingPipeline(PipelineConfig(**kw)).map_candidate(
        task_coords, proc_coords)


def _map(g, alloc, **kw):
    return MappingPipeline(PipelineConfig(**kw)).map(g, alloc)


# ---------------------------------------------------------------------------
# Algorithm 1 (one rotation)
# ---------------------------------------------------------------------------

def test_one_to_one_same_coords_is_identity():
    coords = _grid_coords((4, 4))
    res = _algorithm1(coords, coords, sfc="FZ")
    assert np.array_equal(res.task_to_proc, np.arange(16))


@pytest.mark.parametrize("sfc", ["Z", "FZ", "H"])
def test_one_to_one_is_bijection(sfc):
    rng = np.random.default_rng(0)
    tc = rng.normal(size=(64, 2))
    pc = rng.normal(size=(64, 3))
    res = _algorithm1(tc, pc, sfc=sfc)
    assert sorted(res.task_to_proc.tolist()) == list(range(64))


def test_more_tasks_than_procs_balanced():
    rng = np.random.default_rng(1)
    tc = rng.normal(size=(64, 2))
    pc = rng.normal(size=(16, 2))
    res = _algorithm1(tc, pc, sfc="FZ")
    counts = np.bincount(res.task_to_proc, minlength=16)
    assert (counts == 4).all()


def test_fewer_tasks_than_procs_uses_subset():
    rng = np.random.default_rng(2)
    tc = rng.normal(size=(8, 2))
    pc = np.concatenate([rng.normal(size=(8, 2)),
                         rng.normal(size=(24, 2)) + 100.0])
    res = _algorithm1(tc, pc, sfc="FZ")
    # chosen procs must form ONE compact cluster (either group works; the
    # selection must not straddle the 100-unit gap)
    chosen = res.task_to_proc
    assert len(set(chosen.tolist())) == 8
    spread = pc[chosen].max(axis=0) - pc[chosen].min(axis=0)
    assert (spread < 10.0).all()


def test_closest_subset_picks_cluster():
    rng = np.random.default_rng(3)
    tight = rng.normal(scale=0.1, size=(10, 2))
    far = rng.normal(scale=0.1, size=(30, 2)) + 50.0
    pts = np.concatenate([far[:15], tight, far[15:]])
    # 30 far points dominate the initial centroid, but iteration converges
    sel = closest_subset(pts, 10)
    assert len(sel) == 10


def test_mfz_auto_engages_only_when_pd_multiple_of_td():
    tc = _grid_coords((64,))  # td=1
    pc = _grid_coords((8, 8))  # pd=2
    res_mfz = _algorithm1(tc, pc, sfc="FZ", mfz="auto")
    res_fz = _algorithm1(tc, pc, sfc="FZ", mfz=False)
    assert not np.array_equal(res_mfz.task_to_proc, res_fz.task_to_proc)
    # pd == td: MFZ must be identical to FZ
    pc2 = _grid_coords((64,))
    a = _algorithm1(tc, pc2, sfc="FZ", mfz="auto")
    b = _algorithm1(tc, pc2, sfc="FZ", mfz=False)
    assert np.array_equal(a.task_to_proc, b.task_to_proc)


# ---------------------------------------------------------------------------
# Transforms
# ---------------------------------------------------------------------------

def test_shift_torus_closes_gap():
    m = make_machine((16,), wrap=True)
    # occupy two clusters split across the wrap: {14,15} and {0,1}
    coords = np.array([[14.0], [15.0], [0.0], [1.0]])
    alloc_like = shift_torus(coords, m)
    ext = alloc_like.max() - alloc_like.min()
    assert ext == 3.0  # contiguous after the shift


def test_shift_torus_skips_mesh_dims():
    m = make_machine((16,), wrap=False)
    coords = np.array([[14.0], [15.0], [0.0], [1.0]])
    assert np.array_equal(shift_torus(coords, m), coords)


def test_scale_by_bandwidth_stretches_slow_dims():
    m = make_machine((4, 4), wrap=False, bw=(100.0, 25.0))
    coords = _grid_coords((4, 4))
    out = scale_by_bandwidth(coords, m)
    # dim 1 links are 4x slower -> 4x the geometric distance
    assert np.isclose(out[:, 1].max() / coords[:, 1].max(), 4.0)
    assert np.isclose(out[:, 0].max(), coords[:, 0].max())


def test_box_lift_shape_and_weighting():
    coords = _grid_coords((8, 8))
    out = box_lift(coords, (2, 2), outer_weight=10.0, inner_weight=1.0)
    assert out.shape == (64, 4)
    assert out[:, :2].max() == 30.0  # 3 boxes * 10
    assert out[:, 2:].max() == 1.0


# ---------------------------------------------------------------------------
# The full pipeline end-to-end on machines
# ---------------------------------------------------------------------------

def test_mapper_beats_identity_on_sparse_allocation():
    """Sparse SFC allocation on a torus: the geometric mapping should cut
    total hops vs rank-order (the paper's headline result)."""
    m = make_machine((16, 16), wrap=True)
    alloc = sfc_allocation(m, 64, nfragments=4, seed=7)
    g = stencil_graph((8, 8))
    res = _map(g, alloc, sfc="FZ", shift=True)
    ours = evaluate(g, alloc, res)
    base = evaluate(g, alloc, identity_mapping(g, alloc))
    assert ours["total_hops"] <= base["total_hops"]


def test_mapper_rotation_search_not_worse():
    m = make_machine((8, 8, 8), wrap=True)
    alloc = sfc_allocation(m, 64, nfragments=2, seed=3)
    g = stencil_graph((8, 8))
    plain = _map(g, alloc, sfc="FZ", rotations=0)
    rot = _map(g, alloc, sfc="FZ", rotations=12)
    h_plain = evaluate(g, alloc, plain)["weighted_hops"]
    h_rot = evaluate(g, alloc, rot)["weighted_hops"]
    assert h_rot <= h_plain + 1e-9


def test_mapper_on_tpu_multipod():
    m = tpu_v5e_multipod(npods=2, side=4)
    alloc = block_allocation(m)
    g = stencil_graph((4, 8))
    res = _map(g, alloc, sfc="FZ")
    assert sorted(res.task_to_proc.tolist()) == list(range(32))


def test_cube_sphere_graph_degree():
    ne = 8
    g = cube_sphere_graph(ne)
    assert g.n == 6 * ne * ne
    # directed degree 4 everywhere on a cubed sphere
    deg = np.bincount(g.edges[:, 0], minlength=g.n)
    assert (deg == 4).all()
    assert len(g.edges) == 24 * ne * ne


def test_sfc_allocation_properties():
    m = make_machine((8, 8), wrap=True)
    a = sfc_allocation(m, 16, nfragments=1, seed=0)
    assert a.n == 16
    assert len(np.unique(a.coords, axis=0)) == 16
    b = sfc_allocation(m, 16, nfragments=4, seed=0)
    assert b.n == 16


@given(st.integers(2, 4), st.integers(2, 3), st.integers(0, 5))
@settings(max_examples=20, deadline=None)
def test_mapping_valid_any_machine(side, d, seed):
    """Property: mapping a matching-size stencil onto any torus block is a
    bijection."""
    m = make_machine((side,) * d, wrap=True)
    alloc = block_allocation(m)
    n = side ** d
    g = stencil_graph((n,))  # 1D chain of matching size
    res = _map(g, alloc, sfc="FZ")
    assert sorted(res.task_to_proc.tolist()) == list(range(n))
