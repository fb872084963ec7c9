"""Bit-identity suite for the device (JAX) partition backend.

The numpy engines (``partition.vectorized_order*``, whose lexsort tie
order is the oracle) define the contract: the jax backend must return
IDENTICAL permutations for every configuration — random dims, weights,
duplicate coordinates, uneven prime part counts, padded-bucket tails —
plus the device Hilbert kernel (Skilling's transpose, bit-identical to
``orderings.hilbert_index`` + stable lexsort), the resolved-once
fallback chain, truthful compile-cache counters, and the fused
whole-pipeline program (partition + match + score + select as ONE
jitted program).  Property-style via seeded numpy RNG (no
hypothesis dependency, matching tests/test_partition.py)."""

import numpy as np
import pytest

pytest.importorskip("jax")

from repro.core import orderings
from repro.core import partition_jax
from repro.core.orderings import (order_points, order_points_batched,
                                  resolve_partition_backend)

SFCS = ("Z", "Gray", "FZ", "FZlow")


def _assert_jax_equiv(coords, nparts, sfc, **kw):
    a = order_points(coords, nparts, sfc, backend="vectorized", **kw)
    b = order_points(coords, nparts, sfc, backend="jax", **kw)
    assert np.array_equal(a, b), (
        f"jax backend mismatch: sfc={sfc} nparts={nparts} kw={kw} "
        f"ndiff={(a != b).sum()}/{len(a)}")
    return a


# ---------------------------------------------------------------------------
# property-style bit-identity across every knob
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", range(16))
def test_random_points_all_knobs(seed):
    rng = np.random.default_rng(seed)
    d = int(rng.integers(1, 5))
    n = int(rng.integers(2, 400))
    nparts = int(rng.integers(1, 70))
    sfc = SFCS[seed % 4]
    weights = rng.random(n) if seed % 3 == 0 else None
    uneven = bool(seed % 2)
    longest = seed % 5 != 0
    dim_order = rng.permutation(d) if seed % 4 == 0 else None
    coords = rng.normal(size=(n, d))
    if seed % 6 == 0:  # duplicate-heavy: exercises the tie lexsort order
        coords = np.repeat(coords[: max(n // 5, 1)], 5, axis=0)
        if weights is not None:
            weights = rng.random(len(coords))
    _assert_jax_equiv(coords, nparts, sfc, weights=weights,
                      uneven_prime=uneven, longest_dim=longest,
                      dim_order=dim_order)


@pytest.mark.parametrize("seed", range(8))
def test_batched_bit_identity(seed):
    rng = np.random.default_rng(100 + seed)
    d = int(rng.integers(2, 4))
    n = int(rng.integers(8, 300))
    nparts = int(rng.integers(2, 48))
    sfc = SFCS[seed % 4]
    B = int(rng.integers(1, 5))
    dim_orders = np.stack([rng.permutation(d) for _ in range(B)])
    weights = rng.random(n) if seed % 2 else None
    coords = rng.normal(size=(n, d))
    kw = dict(dim_orders=dim_orders, weights=weights,
              uneven_prime=bool(seed % 3 == 0), longest_dim=seed % 4 != 1)
    a = order_points_batched(coords, nparts, sfc, backend="vectorized",
                             **kw)
    b = order_points_batched(coords, nparts, sfc, backend="jax", **kw)
    assert np.array_equal(a, b)


@pytest.mark.parametrize("nparts", [7, 97])
def test_uneven_prime_parts_on_grids(nparts):
    ix = np.indices((16, 16))
    coords = np.stack([c.ravel() for c in ix], axis=1).astype(float)
    _assert_jax_equiv(coords, nparts, "FZ", uneven_prime=True)


def test_zero_weight_points_and_more_parts_than_points():
    rng = np.random.default_rng(7)
    coords = rng.normal(size=(40, 2))
    w = rng.random(40)
    w[::3] = 0.0
    _assert_jax_equiv(coords, 8, "Gray", weights=w)
    _assert_jax_equiv(rng.normal(size=(5, 2)), 16, "FZ")


def test_padded_bucket_tails():
    """Point counts straddling the pow2 bucket boundary: the padded
    tail slots must never leak into the result."""
    rng = np.random.default_rng(11)
    for n in (partition_jax.PART_BUCKET_MIN - 1,
              partition_jax.PART_BUCKET_MIN,
              partition_jax.PART_BUCKET_MIN + 1, 511, 513):
        coords = rng.normal(size=(n, 3))
        _assert_jax_equiv(coords, 32, "FZ", weights=rng.random(n))


@pytest.mark.parametrize("sfc", SFCS)
def test_signed_zeros_tie_like_numpy(sfc):
    """Coordinates enter as dense ranks: -0.0 and 0.0 share a rank, so
    they tie exactly as numpy's comparisons make them, flips or not."""
    rng = np.random.default_rng(41)
    coords = rng.integers(-2, 3, size=(120, 2)).astype(float)
    coords[rng.random(coords.shape) < 0.3] = -0.0
    coords[rng.random(coords.shape) < 0.3] = 0.0
    _assert_jax_equiv(coords, 12, sfc)


@pytest.mark.parametrize("n,nparts,uneven", [(1000, 37, False),
                                             (1000, 37, True),
                                             (32856, 32856, False),
                                             (7, 16, False)])
def test_cut_table_matches_oracle_recursion(n, nparts, uneven):
    """The host cut table holds exactly the oracle's unit-weight cuts
    for every (size, parts) segment the recursion reaches."""
    from repro.core.orderings import _split_counts
    from repro.core.partition import _uniform_cuts

    tab = partition_jax._cut_table(n, nparts, uneven)
    rows = {(int(s_), int(p_)): int(k) for s_, p_, k in tab}
    todo = [(n, nparts)]
    seen = set()
    while todo:
        size, p = todo.pop()
        if size <= 1 or p <= 1 or (size, p) in seen:
            continue
        seen.add((size, p))
        npl = _split_counts(p, uneven)[0]
        k = int(_uniform_cuts(np.array([size]), np.array([npl / p]))[0])
        assert rows[(size, p)] == k
        todo += [(k, npl), (size - k, p - npl)]
    assert set(rows) == seen


# ---------------------------------------------------------------------------
# device Hilbert: Skilling's transpose as a batched jitted kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", range(12))
def test_hilbert_device_bit_identity(seed):
    """The device Hilbert state machine must reproduce the host
    ``hilbert_index`` + stable-lexsort split exactly: random clouds and
    duplicate-heavy integer grids (all-ties quantisation), dims 1-3,
    weighted and unweighted cuts."""
    rng = np.random.default_rng(500 + seed)
    d = int(rng.integers(1, 4))
    n = int(rng.integers(2, 400))
    nparts = int(rng.integers(1, 48))
    weights = rng.random(n) if seed % 3 == 0 else None
    coords = rng.normal(size=(n, d))
    if seed % 4 == 0:  # duplicate-heavy grid: the tie order is the test
        coords = rng.integers(0, 4, size=(n, d)).astype(float)
    a = order_points(coords, nparts, "H", backend="vectorized",
                     weights=weights)
    b = order_points(coords, nparts, "H", backend="jax", weights=weights)
    assert np.array_equal(a, b), (
        f"device Hilbert mismatch: d={d} n={n} nparts={nparts} "
        f"weighted={weights is not None}")


def test_hilbert_padded_bucket_tails():
    """Hilbert shares the pow2 point buckets: counts straddling the
    bucket boundary must keep padded tail slots out of the result."""
    rng = np.random.default_rng(13)
    for n in (partition_jax.PART_BUCKET_MIN - 1,
              partition_jax.PART_BUCKET_MIN,
              partition_jax.PART_BUCKET_MIN + 1, 511, 513):
        coords = rng.normal(size=(n, 3))
        w = rng.random(n)
        a = order_points(coords, 16, "H", backend="vectorized", weights=w)
        b = order_points(coords, 16, "H", backend="jax", weights=w)
        assert np.array_equal(a, b), n


def test_hilbert_batched_dim_order_candidates():
    """Batched H folds the dim-order into per-candidate gathers (no host
    pre-permutation): every row must equal the column-permuted
    per-candidate oracle, all 3! permutations at once."""
    import itertools

    rng = np.random.default_rng(21)
    coords = rng.normal(size=(200, 3))
    dos = np.array(list(itertools.permutations(range(3))))
    for weights in (None, rng.random(200)):
        a = order_points_batched(coords, 12, "H", dim_orders=dos,
                                 weights=weights, backend="vectorized")
        b = order_points_batched(coords, 12, "H", dim_orders=dos,
                                 weights=weights, backend="jax")
        assert np.array_equal(a, b)
        for i, p in enumerate(dos):
            ref = order_points(coords[:, list(p)], 12, "H",
                               weights=weights)
            assert np.array_equal(b[i], ref), tuple(p)


def test_hilbert_compile_cache_counters():
    """H shares the keyed compile cache: one compile per (d, bits,
    weighted, bucket); a second cloud in the same bucket must hit."""
    partition_jax.reset_partition_cache()
    rng = np.random.default_rng(17)
    order_points(rng.normal(size=(100, 3)), 8, "H", backend="jax")
    stats = partition_jax.partition_cache_stats()
    assert stats == {"hits": 0, "misses": 1, "entries": 1}
    order_points(rng.normal(size=(90, 3)), 12, "H", backend="jax")
    stats = partition_jax.partition_cache_stats()
    assert stats["misses"] == 1 and stats["hits"] == 1


# ---------------------------------------------------------------------------
# scenario registry: every machine x workload partitions identically
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("workload", ["minighost", "homme", "random"])
@pytest.mark.parametrize("allocation",
                         ["xk7_sparse", "bgq_block", "tpu_mesh",
                          "fat_tree"])
def test_scenario_registry_bit_identity(workload, allocation):
    from repro.mapping.pipeline import MappingPipeline, PipelineConfig
    from repro.serve.scenarios import Scenario

    sc = Scenario(workload, allocation, scale=192)
    graph = sc.graph()
    alloc = sc.alloc_for(graph)
    pipe = MappingPipeline(PipelineConfig(sfc="FZ", shift=True))
    pc = pipe.machine_coords(alloc)
    for coords, w in ((graph.coords.astype(float), None),
                      (pc, None)):
        d = coords.shape[1]
        dim_orders = np.stack([np.arange(d), np.arange(d)[::-1]])
        nparts = min(len(coords), len(pc))
        a = order_points_batched(coords, nparts, "FZ",
                                 dim_orders=dim_orders, weights=w,
                                 backend="vectorized")
        b = order_points_batched(coords, nparts, "FZ",
                                 dim_orders=dim_orders, weights=w,
                                 backend="jax")
        assert np.array_equal(a, b)


# ---------------------------------------------------------------------------
# fallback chain + compile-cache counters
# ---------------------------------------------------------------------------

def test_resolve_partition_backend():
    assert resolve_partition_backend("numpy") == "numpy"
    assert resolve_partition_backend("jax") == "jax"  # jax importable here
    with pytest.raises(ValueError):
        resolve_partition_backend("pallas")


def test_fallback_when_jax_absent(monkeypatch):
    """With the import sentinel pinned to 'unavailable' the jax backend
    silently produces the numpy result and the pipeline resolves
    numpy."""
    from repro.mapping.pipeline import MappingPipeline, PipelineConfig

    monkeypatch.setattr(orderings, "_JAX_PART", None)
    assert resolve_partition_backend("jax") == "numpy"
    rng = np.random.default_rng(3)
    coords = rng.normal(size=(64, 2))
    a = order_points(coords, 8, "FZ", backend="jax")
    b = order_points(coords, 8, "FZ", backend="vectorized")
    assert np.array_equal(a, b)
    pipe = MappingPipeline(PipelineConfig(partition_backend="jax"))
    assert pipe.partition_backend == "numpy"
    assert pipe.order_backend == "vectorized"
    assert pipe._fused is None


def test_compile_cache_counters():
    """One compile per (knobs, bucket); repeat shapes must hit."""
    partition_jax.reset_partition_cache()
    rng = np.random.default_rng(5)
    coords = rng.normal(size=(100, 3))
    order_points(coords, 8, "FZ", backend="jax")
    stats = partition_jax.partition_cache_stats()
    assert stats == {"hits": 0, "misses": 1, "entries": 1}
    order_points(rng.normal(size=(90, 3)), 12, "FZ", backend="jax")
    stats = partition_jax.partition_cache_stats()
    assert stats["misses"] == 1 and stats["hits"] == 1


def test_split_table_matches_reference():
    from repro.core.orderings import _split_counts
    for uneven in (False, True):
        tab = partition_jax._split_table(97, uneven)
        for v in range(2, 98):
            assert tab[v] == _split_counts(v, uneven)[0], (v, uneven)


# ---------------------------------------------------------------------------
# fused whole-pipeline program
# ---------------------------------------------------------------------------

def _mesh_problem():
    from repro.core import (block_allocation, logical_mesh_graph,
                            tpu_v5e_pod)
    machine = tpu_v5e_pod(side=8)
    alloc = block_allocation(machine)
    graph = logical_mesh_graph((8, 8), (8.0, 64.0), ("data", "model"))
    return graph, alloc


@pytest.mark.parametrize("score_backend", ["jax", "pallas"])
@pytest.mark.parametrize("objective",
                         ["weighted_hops",
                          ("latency_max", "weighted_hops")])
def test_fused_pipeline_matches_numpy(score_backend, objective):
    """partition=jax + score=jax/pallas runs the sweep as ONE compiled
    program and returns the same winner as the all-numpy pipeline."""
    from repro.mapping.pipeline import MappingPipeline, PipelineConfig

    graph, alloc = _mesh_problem()
    base = MappingPipeline(PipelineConfig(rotations=4, objective=objective)
                           ).map(graph, alloc)
    pipe = MappingPipeline(PipelineConfig(
        rotations=4, objective=objective, score_backend=score_backend,
        partition_backend="jax"))
    assert pipe._fused is not None
    fused = pipe.map(graph, alloc)
    assert fused.stats.get("fused") is True
    assert "fused_s" in fused.stats["timings"]
    assert np.array_equal(base.task_to_proc, fused.task_to_proc)
    assert base.rotation == fused.rotation
    assert np.isclose(base.score, fused.score, rtol=1e-5)


def test_fused_pipeline_hilbert_matches_numpy():
    """sfc="H" engages the SAME fused program path: the device Hilbert
    sweep feeds the inlined scorer and the winner is bit-identical to
    the all-numpy pipeline."""
    from repro.mapping.pipeline import MappingPipeline, PipelineConfig

    graph, alloc = _mesh_problem()
    base = MappingPipeline(PipelineConfig(sfc="H", rotations=4)
                           ).map(graph, alloc)
    pipe = MappingPipeline(PipelineConfig(
        sfc="H", rotations=4, score_backend="jax",
        partition_backend="jax"))
    assert pipe._fused is not None
    fused = pipe.map(graph, alloc)
    assert fused.stats.get("fused") is True
    assert np.array_equal(base.task_to_proc, fused.task_to_proc)
    assert base.rotation == fused.rotation


def test_fused_program_compiles_once():
    """Repeat map() calls on the same shapes reuse ONE fused program
    (zero host<->device transfers between stages: the whole chain is a
    single cache entry)."""
    from repro.mapping import fused as fused_mod
    from repro.mapping.pipeline import MappingPipeline, PipelineConfig

    graph, alloc = _mesh_problem()
    pipe = MappingPipeline(PipelineConfig(
        rotations=4, score_backend="jax", partition_backend="jax"))
    fused_mod.reset_fused_cache()
    r1 = pipe.map(graph, alloc)
    stats = fused_mod.fused_cache_stats()
    assert stats["misses"] == 1 and stats["entries"] == 1
    r2 = pipe.map(graph, alloc)
    stats = fused_mod.fused_cache_stats()
    assert stats["misses"] == 1 and stats["hits"] == 1
    assert np.array_equal(r1.task_to_proc, r2.task_to_proc)


def test_fused_hierarchical_matches_numpy():
    from repro.mapping.pipeline import MappingPipeline, PipelineConfig

    graph, alloc = _mesh_problem()
    from repro.hier import HierarchySpec
    base = MappingPipeline(PipelineConfig(
        rotations=4, hierarchy=HierarchySpec.node())).map(graph, alloc)
    fused = MappingPipeline(PipelineConfig(
        rotations=4, hierarchy=HierarchySpec.node(), score_backend="jax",
        partition_backend="jax")).map(graph, alloc)
    assert np.array_equal(base.task_to_proc, fused.task_to_proc)
    assert "refine_s" in fused.stats["timings"]
    assert fused.stats["partition_backend"] == "jax"


def test_unfused_jax_partition_stage_timings():
    """partition=jax with the numpy scorer: no fused program, but the
    per-stage timings and backend attribution must still be recorded."""
    from repro.mapping.pipeline import MappingPipeline, PipelineConfig

    graph, alloc = _mesh_problem()
    pipe = MappingPipeline(PipelineConfig(rotations=4,
                                          partition_backend="jax"))
    assert pipe._fused is None and pipe.order_backend == "jax"
    base = MappingPipeline(PipelineConfig(rotations=4)).map(graph, alloc)
    res = pipe.map(graph, alloc)
    assert np.array_equal(base.task_to_proc, res.task_to_proc)
    t = res.stats["timings"]
    assert {"partition_s", "score_s", "total_s"} <= set(t)
    assert res.stats["partition_backend"] == "jax"
