"""ISSUE-2 coverage: the batched rotation sweep and the routing/scoring
backends.

- ``_batched_route`` (numpy and jax) must agree with a naive
  per-message reference router that literally walks every hop, across
  wrap/non-wrap dims, core dims and weighted edges;
- ``order_points_batched`` must match per-candidate ``order_points``
  (both the ``dim_order`` form and the column-permuted-cloud form, and
  the recursive backend) across SFC kinds — including the Hilbert
  curve, whose batched rows are column permutations — weights and
  ``uneven_prime``;
- the jax scoring backend must match numpy within fp tolerance on every
  metric key and fall back to numpy cleanly when jax is unavailable;
- ``MappingPipeline``'s batched sweep must return mappings and winners
  bit-identical to the per-candidate oracle (``map_candidate`` for every
  rotation, then the search's winner).

Property-style via seeded numpy RNG (no hypothesis dependency)."""

import numpy as np
import pytest

from repro.core import (block_allocation, make_machine, sfc_allocation,
                        stencil_graph, tpu_v5e_multipod)
from repro.core import metrics as M
# Imported directly (not via the silent-fallback dispatcher) so a broken
# jax backend fails the suite loudly instead of letting the parity tests
# compare numpy against numpy.
from repro.core import metrics_jax  # noqa: F401
from repro.core.machine import gemini_xk7
from repro.core.metrics import _batched_route, evaluate_candidates
from repro.core.orderings import (order_points, order_points_batched,
                                  order_points_recursive)
from repro.mapping import CandidateSearch, MappingPipeline, PipelineConfig
from repro.mapping.candidates import rotation_candidates

SFCS = ("Z", "Gray", "FZ", "FZlow")


# ---------------------------------------------------------------------------
# reference router: walk every message link by link
# ---------------------------------------------------------------------------

def _route_naive(machine, src, dst, w):
    """Dimension-ordered routing, one hop at a time (the spec)."""
    nd = machine.ndim - machine.core_dims
    pos = [np.zeros(machine.dims) for _ in range(nd)]
    neg = [np.zeros(machine.dims) for _ in range(nd)]
    for s, t, wt in zip(src, dst, w):
        cur = list(s)
        for k in range(nd):
            size = machine.dims[k]
            a, b = int(cur[k]), int(t[k])
            if machine.wrap[k]:
                go_fwd = (b - a) % size <= (a - b) % size
            else:
                go_fwd = b >= a
            while cur[k] != b:
                if go_fwd:
                    pos[k][tuple(cur)] += wt
                    cur[k] = (cur[k] + 1) % size
                else:
                    nxt = (cur[k] - 1) % size
                    step = list(cur)
                    step[k] = nxt
                    neg[k][tuple(step)] += wt
                    cur[k] = nxt
    return pos, neg


MACHINES = [
    make_machine((6,), wrap=True),
    make_machine((5, 4), wrap=False),
    make_machine((4, 5, 3), wrap=(True, False, True), bw=(2.0, 1.0, 4.0)),
    gemini_xk7(dims=(4, 4, 8), cores_per_node=2),
    tpu_v5e_multipod(2, 4),
]


@pytest.mark.parametrize("mi", range(len(MACHINES)))
@pytest.mark.parametrize("seed", range(3))
def test_batched_route_matches_naive_reference(mi, seed):
    machine = MACHINES[mi]
    rng = np.random.default_rng(100 * mi + seed)
    nmsg = int(rng.integers(1, 40))
    src = np.stack([rng.integers(0, machine.dims[j], size=nmsg)
                    for j in range(machine.ndim)], axis=1)
    dst = np.stack([rng.integers(0, machine.dims[j], size=nmsg)
                    for j in range(machine.ndim)], axis=1)
    w = rng.uniform(0.5, 3.0, size=nmsg)
    ref_pos, ref_neg = _route_naive(machine, src, dst, w)
    pos, neg = _batched_route(machine, src[None], dst[None], w)
    nd = machine.ndim - machine.core_dims
    for k in range(nd):
        assert np.allclose(pos[k][0], ref_pos[k]), f"pos dim {k}"
        assert np.allclose(neg[k][0], ref_neg[k]), f"neg dim {k}"


@pytest.mark.parametrize("mi", range(len(MACHINES)))
def test_jax_route_matches_naive_reference(mi):
    assert M._jax_evaluator() is not None  # parity must not be vacuous
    machine = MACHINES[mi]
    rng = np.random.default_rng(7 * mi + 1)
    nmsg = 25
    tasks = nmsg + 5
    coords = np.stack([rng.integers(0, machine.dims[j], size=tasks)
                       for j in range(machine.ndim)], axis=1)
    edges = rng.integers(0, tasks, size=(nmsg, 2))
    w = rng.uniform(0.5, 3.0, size=nmsg)
    ref_pos, ref_neg = _route_naive(
        machine, coords[edges[:, 0]], coords[edges[:, 1]], w)
    ev = evaluate_candidates(machine, edges, w, coords[None],
                             traffic=True, backend="jax")
    nd = machine.ndim - machine.core_dims
    data_ref = max(float(a.max()) for k in range(nd)
                   for a in (ref_pos[k], ref_neg[k]))
    lat_ref = max(float((a / machine.bw_field(k)).max()) for k in range(nd)
                  for a in (ref_pos[k], ref_neg[k]))
    assert np.allclose(ev["data_max"][0], data_ref, rtol=1e-4)
    assert np.allclose(ev["latency_max"][0], lat_ref, rtol=1e-4)


# ---------------------------------------------------------------------------
# jax scoring backend parity + fallback
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mi", range(len(MACHINES)))
def test_jax_scoring_parity_all_keys(mi):
    assert M._jax_evaluator() is not None  # parity must not be vacuous
    machine = MACHINES[mi]
    rng = np.random.default_rng(mi)
    nb, ntasks, ne = 4, 40, 120
    stack = np.stack([
        np.stack([rng.integers(0, machine.dims[j], size=ntasks)
                  for j in range(machine.ndim)], axis=1)
        for _ in range(nb)])
    edges = rng.integers(0, ntasks, size=(ne, 2))
    w = rng.uniform(0.5, 2.0, size=ne)
    a = evaluate_candidates(machine, edges, w, stack, traffic=True,
                            backend="numpy")
    b = evaluate_candidates(machine, edges, w, stack, traffic=True,
                            backend="jax")
    assert set(a) == set(b)
    for key in a:
        assert np.allclose(a[key], b[key], rtol=1e-4, atol=1e-4), key


def test_jax_backend_falls_back_cleanly(monkeypatch):
    """backend="jax" must transparently use numpy when jax is absent."""
    monkeypatch.setattr(M, "_JAX_EVAL", None)  # simulate failed import
    machine = make_machine((4, 4), wrap=True)
    rng = np.random.default_rng(0)
    stack = rng.integers(0, 4, size=(2, 10, 2))
    edges = rng.integers(0, 10, size=(20, 2))
    a = evaluate_candidates(machine, edges, None, stack, traffic=True,
                            backend="jax")
    b = evaluate_candidates(machine, edges, None, stack, traffic=True,
                            backend="numpy")
    for key in b:
        assert np.array_equal(a[key], b[key]), key


def test_unknown_scoring_backend_rejected():
    machine = make_machine((4,))
    with pytest.raises(ValueError):
        evaluate_candidates(machine, np.zeros((0, 2), int), None,
                            np.zeros((1, 0, 1)), backend="torch")


# ---------------------------------------------------------------------------
# batched partitioner sweep vs the per-candidate loop
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", range(8))
def test_order_points_batched_matches_loop(seed):
    rng = np.random.default_rng(seed)
    d = int(rng.integers(1, 4))
    n = int(rng.integers(20, 400))
    nparts = int(rng.integers(2, 64))
    sfc = SFCS[seed % 4]
    uneven = bool(seed % 2)
    weights = rng.random(n) if seed % 3 == 0 else None
    coords = rng.normal(size=(n, d))
    perms = [tuple(rng.permutation(d)) for _ in range(4)]
    mu = order_points_batched(coords, nparts, sfc,
                              dim_orders=np.array(perms), weights=weights,
                              uneven_prime=uneven)
    for b, p in enumerate(perms):
        by_dim_order = order_points(coords, nparts, sfc, weights=weights,
                                    dim_order=np.array(p),
                                    uneven_prime=uneven)
        by_permuted = order_points(coords[:, list(p)], nparts, sfc,
                                   weights=weights, uneven_prime=uneven)
        assert np.array_equal(mu[b], by_dim_order), (sfc, p, "dim_order")
        assert np.array_equal(mu[b], by_permuted), (sfc, p, "permuted")


def test_order_points_batched_recursive_backend_agrees():
    rng = np.random.default_rng(5)
    coords = rng.normal(size=(120, 3))
    dos = np.array([(0, 1, 2), (2, 1, 0), (1, 0, 2)])
    a = order_points_batched(coords, 16, "FZ", dim_orders=dos)
    b = order_points_batched(coords, 16, "FZ", dim_orders=dos,
                             backend="recursive")
    assert np.array_equal(a, b)


def test_order_points_batched_tie_heavy_grid():
    """Structured grids (the rotation search's home turf) must stay
    bit-identical through the exact-engine fallback."""
    ix = np.indices((8, 8))
    coords = np.stack([c.ravel() for c in ix], axis=1).astype(float)
    dos = np.array([(0, 1), (1, 0)])
    for sfc in SFCS:
        mu = order_points_batched(coords, 16, sfc, dim_orders=dos)
        for b, p in enumerate(dos):
            ref = order_points_recursive(coords[:, list(p)], 16, sfc)
            assert np.array_equal(mu[b], ref), (sfc, tuple(p))


@pytest.mark.parametrize("seed", range(6))
def test_order_points_batched_hilbert_parity(seed):
    """Batched Hilbert rows are COLUMN permutations: row ``b`` must
    equal ``order_points(coords[:, dim_orders[b]], ..., "H")`` — the
    quantisation grid commutes with column permutation, so one memoised
    quantise pass serves every candidate."""
    rng = np.random.default_rng(300 + seed)
    d = int(rng.integers(1, 4))
    n = int(rng.integers(20, 300))
    nparts = int(rng.integers(2, 48))
    weights = rng.random(n) if seed % 2 else None
    coords = rng.normal(size=(n, d))
    if seed % 3 == 0:  # duplicate-heavy: exercises the stable tie order
        coords = np.repeat(coords[: max(n // 4, 1)], 4, axis=0)
        if weights is not None:
            weights = rng.random(len(coords))
    perms = [tuple(rng.permutation(d)) for _ in range(3)]
    dos = np.array(perms)
    mu = order_points_batched(coords, nparts, "H", dim_orders=dos,
                              weights=weights)
    rec = order_points_batched(coords, nparts, "H", dim_orders=dos,
                               weights=weights, backend="recursive")
    assert np.array_equal(mu, rec)
    for b, p in enumerate(perms):
        ref = order_points(coords[:, list(p)], nparts, "H",
                           weights=weights)
        assert np.array_equal(mu[b], ref), (p, "permuted")


# ---------------------------------------------------------------------------
# pipeline: batched sweep == loop oracle, end to end
# ---------------------------------------------------------------------------

def _loop_oracle(pipe, g, alloc):
    """Every rotation through ``map_candidate``, then the search's
    winner: the per-candidate oracle of the batched sweep."""
    pc = pipe.machine_coords(alloc)
    tc = g.coords.astype(float)
    cands = rotation_candidates(tc.shape[1], pc.shape[1],
                                pipe.config.rotations)
    results = [pipe.map_candidate(tc, pc, task_perm=c.task_perm,
                                  proc_perm=c.proc_perm) for c in cands]
    best, _, _ = pipe.search.best(g, alloc, results)
    return results, best


@pytest.mark.parametrize("cfg_kw", [
    dict(sfc="FZ", rotations=12),
    dict(sfc="FZ", rotations=12, uneven_prime=True, bandwidth_scale=True),
    dict(sfc="Gray", rotations=8, longest_dim=False),
    dict(sfc="FZ", rotations=10, objective=("latency_max", "weighted_hops")),
], ids=["fz", "uneven+bw", "gray-alt", "latency-objective"])
def test_pipeline_sweep_matches_loop(cfg_kw):
    m = make_machine((8, 8, 8), wrap=True)
    alloc = sfc_allocation(m, 64, nfragments=2, seed=3)
    g = stencil_graph((8, 8))
    pipe = MappingPipeline(PipelineConfig(**cfg_kw))
    pc = pipe.machine_coords(alloc)
    tc = g.coords.astype(float)
    cands = rotation_candidates(2, 3, cfg_kw["rotations"])
    rl, res_l = _loop_oracle(pipe, g, alloc)
    rb = pipe.map_candidates(tc, pc, cands)
    assert len(rl) == len(rb)
    for x, y in zip(rl, rb):
        assert np.array_equal(x.task_to_proc, y.task_to_proc)
    res_b = pipe.map(g, alloc)
    assert np.array_equal(res_l.task_to_proc, res_b.task_to_proc)
    assert res_l.rotation == res_b.rotation


def test_pipeline_sweep_matches_loop_tnum_gt_pnum():
    """Tasks sharing processors (tnum > pnum) through the batched part
    matching."""
    m = make_machine((8, 8, 8), wrap=True)
    alloc = sfc_allocation(m, 64, seed=1)
    g = stencil_graph((16, 16))
    pipe = MappingPipeline(PipelineConfig(sfc="FZ", rotations=8))
    _, res_l = _loop_oracle(pipe, g, alloc)
    res_b = pipe.map(g, alloc)
    assert np.array_equal(res_l.task_to_proc, res_b.task_to_proc)


def test_pipeline_jax_scoring_backend_end_to_end():
    m = tpu_v5e_multipod(2, 4)
    alloc = block_allocation(m)
    g = stencil_graph((4, 8))
    res_np = MappingPipeline(PipelineConfig(
        sfc="FZ", rotations=10, score_backend="numpy")).map(g, alloc)
    res_jx = MappingPipeline(PipelineConfig(
        sfc="FZ", rotations=10, score_backend="jax")).map(g, alloc)
    assert sorted(res_jx.task_to_proc.tolist()) == list(range(32))
    assert np.isclose(res_np.score, res_jx.score, rtol=1e-4)


# ---------------------------------------------------------------------------
# candidate search details
# ---------------------------------------------------------------------------

def test_best_lexsort_keeps_first_of_ties():
    class _FakeSearch(CandidateSearch):
        def __init__(self, scores):
            super().__init__(("a", "b"))
            self._scores = np.asarray(scores, dtype=float)

        def score(self, graph, alloc, results):
            return self._scores

    scores = [[2.0, 1.0], [1.0, 5.0], [1.0, 3.0], [1.0, 3.0], [3.0, 0.0]]
    s = _FakeSearch(scores)
    results = list(range(len(scores)))
    best, best_i, got = s.best(None, None, results)
    # lexicographic minimum is (1.0, 3.0); FIRST holder is index 2
    assert best_i == 2 and best == 2
    assert np.array_equal(got, np.asarray(scores))


def test_rotation_candidates_identity_first_and_distinct():
    cands = rotation_candidates(3, 3, 24)
    assert len(cands) == 24
    assert cands[0].task_perm == tuple(range(3))
    assert cands[0].proc_perm == tuple(range(3))
    pairs = {(c.task_perm, c.proc_perm) for c in cands}
    assert len(pairs) == 24  # no duplicate rotations in the budget
    # balanced design: the batched sweep partitions few unique perms
    assert len({c.task_perm for c in cands}) <= 5
    assert len({c.proc_perm for c in cands}) <= 5


def test_bw_field_matches_manual_broadcast():
    m = gemini_xk7(dims=(4, 4, 8), cores_per_node=2)
    for k in range(3):
        idx = np.arange(m.dims[k])
        bw = np.asarray(m.bw(k, idx), dtype=float)
        shape = [1] * m.ndim
        shape[k] = m.dims[k]
        manual = np.broadcast_to(bw.reshape(shape), m.dims)
        assert np.array_equal(m.bw_field(k), manual)
