"""ISSUE-4 coverage: the Pallas mapscore kernel and the bucketed
compile caches.

- the fused kernel (interpret mode on CPU) must match the naive
  per-message reference router of tests/test_batched.py across
  wrap/non-wrap dims, heterogeneous bandwidths, core dims, wrapped
  torus edges, zero-length messages and zero-weight padding;
- power-of-two message bucketing must be EXACT (adding explicit
  zero-weight self-edges changes no output bit), including the padded
  tail of non-power-of-two message counts;
- ``backend="pallas"`` winners through :class:`CandidateSearch` must be
  bit-identical to the numpy oracle's lexsort order;
- the fallback chain pallas -> jax -> numpy must degrade silently,
  including the VMEM-budget fallback for oversized machines;
- both bucketed compile caches (jax scorer, pallas kernel) must HIT
  when message counts share a bucket — the recompile-storm guard.
"""

import numpy as np
import pytest

from test_batched import MACHINES, _route_naive

from repro.core import (block_allocation, make_machine, stencil_graph,
                        tpu_v5e_multipod)
from repro.core import metrics as M
from repro.core import metrics_jax
from repro.core.metrics import evaluate_candidates, get_evaluator
from repro.kernels.mapscore import ops as mops
from repro.kernels.mapscore.ref import mapscore_ref
from repro.mapping import (CandidateSearch, HierarchySpec,
                           MappingPipeline, PipelineConfig)
from repro.mapping.candidates import rotation_candidates


def _random_problem(machine, seed, ntasks=40, ne=120, nb=4):
    rng = np.random.default_rng(seed)
    stack = np.stack([
        np.stack([rng.integers(0, machine.dims[j], size=ntasks)
                  for j in range(machine.ndim)], axis=1)
        for _ in range(nb)])
    edges = rng.integers(0, ntasks, size=(ne, 2))
    w = rng.uniform(0.5, 2.0, size=ne)
    return stack, edges, w


# ---------------------------------------------------------------------------
# kernel parity vs the naive reference router / numpy backend
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mi", range(len(MACHINES)))
def test_pallas_route_matches_naive_reference(mi):
    assert M._pallas_evaluator() is not None  # parity must not be vacuous
    machine = MACHINES[mi]
    stack, edges, w = _random_problem(machine, 31 * mi + 7, nb=1)
    ref_pos, ref_neg = _route_naive(
        machine, stack[0][edges[:, 0]], stack[0][edges[:, 1]], w)
    ev = evaluate_candidates(machine, edges, w, stack, traffic=True,
                             backend="pallas")
    nd = machine.ndim - machine.core_dims
    data_ref = max(float(a.max()) for k in range(nd)
                   for a in (ref_pos[k], ref_neg[k]))
    lat_ref = max(float((a / machine.bw_field(k)).max()) for k in range(nd)
                  for a in (ref_pos[k], ref_neg[k]))
    assert np.allclose(ev["data_max"][0], data_ref, rtol=1e-4)
    assert np.allclose(ev["latency_max"][0], lat_ref, rtol=1e-4)


@pytest.mark.parametrize("mi", range(len(MACHINES)))
def test_pallas_scoring_parity_all_keys(mi):
    machine = MACHINES[mi]
    stack, edges, w = _random_problem(machine, mi)
    a = evaluate_candidates(machine, edges, w, stack, traffic=True,
                            backend="numpy")
    b = evaluate_candidates(machine, edges, w, stack, traffic=True,
                            backend="pallas")
    assert set(a) == set(b)
    for key in a:
        assert np.allclose(a[key], b[key], rtol=1e-4, atol=1e-4), key
    # integer metrics cross the kernel exactly, not just within tolerance
    assert np.array_equal(a["total_hops"], b["total_hops"])
    assert np.array_equal(a["average_hops"], b["average_hops"])


@pytest.mark.parametrize("traffic", [False, True], ids=["hops", "traffic"])
def test_pallas_kernel_interpret_under_x64(traffic):
    """The kernel is traced with x64 off even when the process has it on
    (the device partitioner's setting): same numbers as the numpy
    reference, 32-bit (nb, 8, 128) output tiles."""
    import jax

    from repro.kernels.mapscore.kernel import OUT_TILE, mapscore_call

    machine = MACHINES[0]
    stack, edges, w = _random_problem(machine, 5, nb=3)
    ref = evaluate_candidates(machine, edges, w, stack, traffic=traffic,
                              backend="numpy")
    ncols = machine.ndim
    cs = np.swapaxes(stack[:, :, :ncols], 1, 2).astype(np.int32)
    ne_b = 128
    src = mops.pad_axis(cs[:, :, edges[:, 0]], ne_b, axis=2)
    dst = mops.pad_axis(cs[:, :, edges[:, 1]], ne_b, axis=2)
    wp = mops.pad_axis(w.astype(np.float32).reshape(1, -1), ne_b, axis=1)
    args = [src, dst, wp]
    if traffic:
        args.append(mops.inv_bandwidth(machine))
    with jax.enable_x64(True):
        outf, outi = mapscore_call(
            *args, dims=tuple(int(x) for x in machine.dims),
            wrap=tuple(bool(x) for x in machine.wrap),
            core_dims=machine.core_dims, traffic=traffic, tile=128,
            interpret=True)
    assert outf.shape == outi.shape == (3,) + OUT_TILE
    assert outf.dtype == np.float32 and outi.dtype == np.int32
    outf, outi = np.array(outf), np.array(outi)
    assert np.array_equal(outi[:, 0, 0], ref["total_hops"])
    assert np.allclose(outf[:, 0, 0], ref["weighted_hops"], rtol=1e-5)
    if traffic:
        assert np.allclose(outf[:, 0, 1], ref["data_max"], rtol=1e-4)
        assert np.allclose(outf[:, 0, 2], ref["latency_max"], rtol=1e-4)
    # only the metric lanes of row 0 carry values
    outf[:, 0, :3] = 0.0
    outi[:, 0, 0] = 0
    assert not outf.any() and not outi.any()


def test_pallas_wrapped_torus_edges():
    """Messages crossing the wraparound seam in both directions."""
    machine = make_machine((6, 5), wrap=(True, True))
    coords = np.array([[5, 4], [0, 0], [1, 1], [4, 3]])
    # 5->0 wraps +x; 0->5 wraps -x; 4->1 wraps +y via 4,0; long both ways
    edges = np.array([[0, 1], [1, 0], [3, 2], [0, 3]])
    w = np.array([2.0, 3.0, 1.5, 2.5])
    ref_pos, ref_neg = _route_naive(machine, coords[edges[:, 0]],
                                    coords[edges[:, 1]], w)
    ev = evaluate_candidates(machine, edges, w, coords[None], traffic=True,
                             backend="pallas")
    data_ref = max(float(a.max())
                   for arrs in (ref_pos, ref_neg) for a in arrs)
    assert np.allclose(ev["data_max"][0], data_ref, rtol=1e-5)


def test_pallas_zero_length_and_zero_weight():
    machine = make_machine((8, 8), wrap=True)
    rng = np.random.default_rng(3)
    stack = rng.integers(0, 8, size=(3, 30, 2))
    edges = np.array([[0, 0], [1, 1], [2, 5], [7, 7]])
    w = np.array([3.0, 0.0, 2.0, 0.0])
    a = evaluate_candidates(machine, edges, w, stack, traffic=True,
                            backend="numpy")
    b = evaluate_candidates(machine, edges, w, stack, traffic=True,
                            backend="pallas")
    for key in a:
        assert np.allclose(a[key], b[key], rtol=1e-4, atol=1e-4), key


def test_ref_matches_naive_reference():
    machine = MACHINES[3]  # gemini: core dims + heterogeneous bandwidth
    stack, edges, w = _random_problem(machine, 11, nb=2)
    src = stack[:, edges[:, 0]]
    dst = stack[:, edges[:, 1]]
    ref = mapscore_ref(machine, src, dst, w, traffic=True)
    for b in range(2):
        pos, neg = _route_naive(machine, src[b], dst[b], w)
        data = max(float(a.max()) for arrs in (pos, neg) for a in arrs)
        assert np.isclose(ref["data_max"][b], data)


# ---------------------------------------------------------------------------
# bucketed / padded shapes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("ne", [1, 31, 127, 128, 129])
@pytest.mark.parametrize("backend", ["jax", "pallas"])
def test_bucket_tail_parity(ne, backend):
    """Message counts straddling the padded power-of-two buckets."""
    machine = make_machine((4, 5, 3), wrap=(True, False, True),
                           bw=(2.0, 1.0, 4.0))
    stack, edges, w = _random_problem(machine, ne, ne=ne, nb=3)
    a = evaluate_candidates(machine, edges, w, stack, traffic=True,
                            backend="numpy")
    b = evaluate_candidates(machine, edges, w, stack, traffic=True,
                            backend=backend)
    for key in a:
        assert np.allclose(a[key], b[key], rtol=1e-4, atol=1e-4), key


@pytest.mark.parametrize("backend", ["jax", "pallas"])
def test_explicit_zero_weight_padding_is_exact(backend):
    """Hand-padding with zero-weight self-edges must change NO bit —
    the property the power-of-two bucketing relies on."""
    machine = make_machine((6, 6), wrap=True)
    stack, edges, w = _random_problem(machine, 5, ne=50, nb=2)
    pad = np.zeros((30, 2), dtype=edges.dtype)  # task 0 -> task 0
    a = evaluate_candidates(machine, edges, w, stack, traffic=True,
                            backend=backend)
    b = evaluate_candidates(machine, np.vstack([edges, pad]),
                            np.concatenate([w, np.zeros(30)]), stack,
                            traffic=True, backend=backend)
    for key in ("weighted_hops", "total_hops", "data_max", "latency_max"):
        assert np.array_equal(a[key], b[key]), key
    # averages differ only by the true-count denominator
    assert np.allclose(b["average_hops"] * 80 / 50, a["average_hops"])


# ---------------------------------------------------------------------------
# winner bit-identity through the candidate search
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("objective", [
    "weighted_hops", ("latency_max", "weighted_hops")],
    ids=["wh", "latency"])
def test_pallas_winner_bit_identical_to_numpy(objective):
    machine = tpu_v5e_multipod(2, 4)
    alloc = block_allocation(machine)
    g = stencil_graph((4, 8))
    pipe = MappingPipeline(PipelineConfig(sfc="FZ", rotations=10))
    pc = pipe.machine_coords(alloc)
    cands = rotation_candidates(2, pc.shape[1], 10)
    results = pipe.map_candidates(g.coords.astype(float), pc, cands)
    ref = CandidateSearch(objective, backend="numpy")
    best_np, i_np, _ = ref.best(g, alloc, results)
    best_pl, i_pl, _ = CandidateSearch(objective, backend="pallas").best(
        g, alloc, results)
    assert i_pl == i_np
    assert np.array_equal(best_pl.task_to_proc, best_np.task_to_proc)


def test_pipeline_pallas_scoring_backend_end_to_end():
    machine = tpu_v5e_multipod(2, 4)
    alloc = block_allocation(machine)
    g = stencil_graph((4, 8))
    res_np = MappingPipeline(PipelineConfig(
        sfc="FZ", rotations=10, score_backend="numpy")).map(g, alloc)
    res_pl = MappingPipeline(PipelineConfig(
        sfc="FZ", rotations=10, score_backend="pallas")).map(g, alloc)
    assert np.array_equal(res_np.task_to_proc, res_pl.task_to_proc)
    assert np.isclose(res_np.score, res_pl.score, rtol=1e-4)


def test_hier_refine_pallas_backend_monotone():
    """The hier swap refinement accepts the pallas scorer and stays
    monotone (same contract as numpy)."""
    machine = make_machine((4, 4, 4), wrap=True, core_dims=1,
                           name="m", bw=1.0)
    alloc = block_allocation(machine)
    g = stencil_graph((8, 8))
    res = MappingPipeline(PipelineConfig(
        hierarchy=HierarchySpec.node(refine_rounds=2), rotations=4,
        score_backend="pallas")).map(g, alloc)
    hist = res.stats["refine_history"]
    for earlier, later in zip(hist, hist[1:]):
        assert later[0] <= earlier[0] + 1e-9


# ---------------------------------------------------------------------------
# fallback chain
# ---------------------------------------------------------------------------

def test_pallas_falls_back_to_jax_then_numpy(monkeypatch):
    machine = make_machine((4, 4), wrap=True)
    rng = np.random.default_rng(0)
    stack = rng.integers(0, 4, size=(2, 10, 2))
    edges = rng.integers(0, 10, size=(20, 2))
    ref = evaluate_candidates(machine, edges, None, stack, traffic=True,
                              backend="numpy")
    monkeypatch.setattr(M, "_PALLAS_EVAL", None)  # kernel import failed
    assert get_evaluator("pallas")[0] == "jax"
    a = evaluate_candidates(machine, edges, None, stack, traffic=True,
                            backend="pallas")
    for key in ref:
        assert np.allclose(a[key], ref[key], rtol=1e-4), key
    monkeypatch.setattr(M, "_JAX_EVAL", None)  # jax gone too
    assert get_evaluator("pallas")[0] == "numpy"
    b = evaluate_candidates(machine, edges, None, stack, traffic=True,
                            backend="pallas")
    for key in ref:
        assert np.array_equal(b[key], ref[key]), key


def test_oversized_machine_falls_back_to_jax():
    machine = make_machine((128, 128, 64), wrap=True)
    assert mops.vmem_accumulator_bytes(machine) > mops.VMEM_ACC_BUDGET
    rng = np.random.default_rng(1)
    stack = np.stack([np.stack([rng.integers(0, machine.dims[j], size=12)
                                for j in range(3)], axis=1)])
    edges = rng.integers(0, 12, size=(20, 2))
    before = mops.scorer_cache_stats()
    a = evaluate_candidates(machine, edges, None, stack, traffic=True,
                            backend="pallas")
    after = mops.scorer_cache_stats()
    assert after["misses"] == before["misses"]  # no kernel was launched
    ref = evaluate_candidates(machine, edges, None, stack, traffic=True,
                              backend="numpy")
    for key in ref:
        assert np.allclose(a[key], ref[key], rtol=1e-4), key


def test_unknown_backend_rejected_by_resolver():
    with pytest.raises(ValueError):
        get_evaluator("torch")


# ---------------------------------------------------------------------------
# compile caches: shared buckets must HIT, not recompile
# ---------------------------------------------------------------------------

def test_jax_scorer_cache_hits_across_message_counts():
    machine = make_machine((5, 4), wrap=False)
    metrics_jax.reset_scorer_cache()
    for ne in (100, 120, 97):  # one bucket (128): ONE compile for all
        stack, edges, w = _random_problem(machine, ne, ne=ne, nb=4)
        evaluate_candidates(machine, edges, w, stack, traffic=True,
                            backend="jax")
    stats = metrics_jax.scorer_cache_stats()
    assert stats["misses"] == 1 and stats["hits"] == 2, stats
    # the underlying jitted program compiled exactly once
    fn = metrics_jax._scorer(
        tuple(machine.dims), tuple(machine.wrap), machine.core_dims,
        True, 128, 4)
    if hasattr(fn, "_cache_size"):
        assert fn._cache_size() == 1


def test_pallas_kernel_cache_hits_across_message_counts():
    machine = make_machine((6,), wrap=True)
    mops.reset_scorer_cache()
    for ne in (60, 90, 128):
        stack, edges, w = _random_problem(machine, ne, ne=ne, nb=2)
        evaluate_candidates(machine, edges, w, stack, traffic=True,
                            backend="pallas")
    stats = mops.scorer_cache_stats()
    assert stats["misses"] == 1 and stats["hits"] == 2, stats


def test_bucket_size_properties():
    assert metrics_jax.bucket_size(1) == metrics_jax.MSG_BUCKET_MIN
    assert metrics_jax.bucket_size(128) == 128
    assert metrics_jax.bucket_size(129) == 256
    assert metrics_jax.bucket_size(3, lo=1) == 4
    assert metrics_jax.bucket_size(1, lo=1) == 1
