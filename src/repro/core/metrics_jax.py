"""JAX scoring backend for the candidate search (accelerator path).

Mirrors :func:`repro.core.metrics.evaluate_candidates` — vectorised
hop metrics plus the batched dimension-ordered router — as one
jit-compiled function so candidate scoring can run on the accelerator
next to the repo's Pallas kernels:

- the circular difference-array range-add of the router becomes a flat
  ``jax.ops.segment_sum`` over ``row*(s+1)+col`` keys followed by a
  per-row prefix sum (the same scatter-free formulation the numpy
  backend uses through ``np.bincount``);
- candidates are ``jax.vmap``-ped over the leading axis of the
  coordinate stack, so one compiled program scores the whole sweep;
- machine structure (dims / wrap / core-dim count) is static, and BOTH
  dynamic shape axes are bucketed to padded power-of-two sizes — the
  message count (zero-weight self-edge padding is exact in the
  difference-array formulation) and the per-chunk candidate count
  (zero-coordinate rows, sliced away) — so one benchmark scenario set
  compiles O(1) times per machine instead of once per (machine, nmsg)
  pair.  :func:`scorer_cache_stats` exposes the hit/miss counters that
  ``benchmarks/run.py --json`` records and tests assert on.

Numbers match the numpy backend within floating-point tolerance (the
router sums in f32 on CPU/TPU defaults; tests/test_batched.py pins the
parity).  This module imports jax at module level — callers go through
``evaluate_candidates(backend="jax")``, which falls back to numpy when
the import fails.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from repro import obs

from .machine import Machine

MSG_BUCKET_MIN = 128  # smallest padded message-count bucket


def _profiler_annotation(name: str):
    """The jax profiler's host event for a ``repro.obs`` span while a
    profiler session runs; nothing otherwise."""
    if jax.profiler.TraceAnnotation.is_enabled():
        return jax.profiler.TraceAnnotation(name)
    return None


# every device path of the mapper imports this module: from here on
# each span also lands in any jax profiler trace, on its clock
obs.set_annotation(_profiler_annotation)


def bucket_size(n: int, lo: int = MSG_BUCKET_MIN) -> int:
    """Next power of two >= max(n, lo) — the padded shape every dynamic
    axis is bucketed to before entering jit (zero-weight padding is
    exact, see module docstring)."""
    return max(lo, 1 << max(int(n) - 1, 0).bit_length())


def _circular_range_add(row, start, length, w, nrows, s):
    """Segment-sum difference-array range-add: add ``w`` to the circular
    interval [start, start+length) of each row's length-``s`` lane.
    Column ``s`` is the dump bucket closing wrapped head intervals;
    zero-length messages contribute exact zeros (static shapes — no
    boolean compaction under jit)."""
    base = row * (s + 1)
    end = start + length
    wz = jnp.where(length > 0, w, 0.0)
    wrapped = end > s
    wwr = jnp.where(wrapped, wz, 0.0)
    idx = jnp.concatenate([
        base + start,                         # open [start, ...)
        base + jnp.minimum(end, s),           # close at end (or dump)
        base,                                 # wrapped tail opens at 0
        base + jnp.where(wrapped, end - s, 0),  # ... and closes at end-s
    ])
    val = jnp.concatenate([wz, -wz, wwr, -wwr])
    diff = jax.ops.segment_sum(val, idx, num_segments=nrows * (s + 1))
    return jnp.cumsum(diff.reshape(nrows, s + 1)[:, :s], axis=1)


def _route_one(src, dst, w, dims, wrap, nd):
    """Dimension-ordered routing of one candidate's messages; returns
    per network dim the (+, -) link-load arrays of full machine shape.
    Same traversal as ``metrics._batched_route`` (dim 0 first, shortest
    direction on tori, core coords held at the source's)."""
    pos, neg = [], []
    cur = src
    for k in range(nd):
        s = dims[k]
        a = cur[:, k]
        b = dst[:, k]
        if wrap[k]:
            fwd = (b - a) % s
            bwd = (a - b) % s
            use_fwd = fwd <= bwd
            len_f = jnp.where(use_fwd, fwd, 0)
            len_b = jnp.where(use_fwd, 0, bwd)
            start_b = (a - len_b) % s
        else:
            use_fwd = b >= a
            len_f = jnp.where(use_fwd, b - a, 0)
            len_b = jnp.where(use_fwd, 0, a - b)
            start_b = a - len_b
        row = jnp.zeros_like(a)
        row_dims = tuple(d for j, d in enumerate(dims) if j != k)
        for j in range(len(dims)):
            if j != k:
                row = row * dims[j] + cur[:, j]
        nrows = 1
        for d in row_dims:
            nrows *= d
        lane_p = _circular_range_add(row, a, len_f, w, nrows, s)
        lane_n = _circular_range_add(row, start_b, len_b, w, nrows, s)
        pos.append(jnp.moveaxis(lane_p.reshape(row_dims + (s,)), -1, k))
        neg.append(jnp.moveaxis(lane_n.reshape(row_dims + (s,)), -1, k))
        cur = cur.at[:, k].set(b)
    return tuple(pos), tuple(neg)


def _score_chunk(src, dst, w, bw_fields, *, dims, wrap, core_dims, traffic):
    """Sums-only scoring of one padded (nb_b, ne_b) chunk: averages are
    derived on the host from the TRUE message count (padded entries
    carry zero weight and zero length, so every sum is exact)."""
    nd = len(dims) - core_dims
    hops = jnp.zeros(src.shape[:-1], dtype=jnp.int32)
    for k in range(nd):
        s = dims[k]
        d = jnp.abs(src[..., k] - dst[..., k])
        if wrap[k]:
            d = jnp.minimum(d, s - d)
        hops = hops + d
    hf = hops.astype(jnp.float32)
    out = {
        "weighted_hops": (hf * w[None, :]).sum(axis=-1),
        "total_hops": hops.sum(axis=-1),
    }
    if traffic:
        pos, neg = jax.vmap(
            lambda s_, d_: _route_one(s_, d_, w, dims, wrap, nd))(src, dst)
        nb = src.shape[0]
        data = jnp.zeros(nb)
        lat = jnp.zeros(nb)
        for k in range(nd):
            inv_bw = (1.0 / bw_fields[k])[None]
            for arr in (pos[k], neg[k]):
                data = jnp.maximum(data, arr.reshape(nb, -1).max(axis=1))
                lat = jnp.maximum(
                    lat, (arr * inv_bw).reshape(nb, -1).max(axis=1))
        out["data_max"] = data
        out["latency_max"] = lat
    return out


@functools.lru_cache(maxsize=None)
def _scorer(dims, wrap, core_dims, traffic, ne_bucket, nb_bucket):
    """One jit-compiled scorer per (machine structure, shape bucket).

    ``ne_bucket`` / ``nb_bucket`` are part of the key even though the
    returned function never reads them: every cache entry then sees
    exactly ONE input shape, so jax compiles each entry once and the
    ``lru_cache`` hit/miss counters are a truthful compile-count proxy
    (:func:`scorer_cache_stats`).
    """
    del ne_bucket, nb_bucket  # shape part of the key only
    return jax.jit(functools.partial(_score_chunk, dims=dims, wrap=wrap,
                                     core_dims=core_dims, traffic=traffic))


# registry-backed stat/reset pair (repro.obs): ``misses`` is the number
# of distinct (machine, bucket) programs compiled this process, ``hits``
# the number of calls that reused one; auto-registers with
# ``obs.snapshot()`` under "scorer_jax"
scorer_cache_stats, reset_scorer_cache = obs.instrument_compile_cache(
    "scorer_jax", _scorer)


def pad_axis(arr, size, axis=0):
    """Zero-pad ``arr`` along ``axis`` to ``size`` entries (shared by
    the jax and pallas bucketing paths)."""
    pad = size - arr.shape[axis]
    if pad <= 0:
        return arr
    widths = [(0, 0)] * arr.ndim
    widths[axis] = (0, pad)
    return np.pad(arr, widths)


def evaluate_candidates_jax(machine: Machine, task_edges: np.ndarray,
                            edge_weights: np.ndarray | None,
                            coord_stack: np.ndarray, *,
                            traffic: bool = False,
                            chunk_elems: int = 1 << 24) -> dict:
    """JAX implementation of ``evaluate_candidates`` (same contract,
    results within fp tolerance of the numpy backend).

    Message counts are padded to the enclosing power-of-two bucket with
    zero-weight self-edges (task 0 -> task 0): zero length in every
    dimension and zero weight contribute exact zeros to every sum, max
    and link load, so bucketing changes no result bit while collapsing
    the compile count from O(distinct nmsg) to O(distinct buckets).
    Candidate chunks are likewise processed at power-of-two sizes
    (zero-coordinate padding rows, sliced away).
    """
    coord_stack = np.asarray(coord_stack)
    nb = len(coord_stack)
    ne = len(task_edges)
    out = {
        "weighted_hops": np.zeros(nb),
        "total_hops": np.zeros(nb, dtype=np.int64),
        "average_hops": np.zeros(nb),
    }
    if traffic:
        out["data_max"] = np.zeros(nb)
        out["latency_max"] = np.zeros(nb)
    if ne == 0 or nb == 0:
        return out
    nd = machine.ndim - machine.core_dims
    dims = tuple(int(x) for x in machine.dims)
    wrap = tuple(bool(x) for x in machine.wrap)
    bw_fields = tuple(jnp.asarray(machine.bw_field(k), dtype=jnp.float32)
                      for k in range(nd))

    ne_b = bucket_size(ne)
    edges = pad_axis(np.asarray(task_edges, dtype=np.int64), ne_b)
    w_np = np.ones(ne) if edge_weights is None else \
        np.asarray(edge_weights, dtype=np.float64)
    w = jnp.asarray(pad_axis(w_np, ne_b), dtype=jnp.float32)

    per_cand = max(ne_b * machine.ndim, 1)
    if traffic:
        per_cand += 2 * nd * machine.nnodes
    # power-of-two chunk, rounded DOWN so a chunk never exceeds the
    # caller's chunk_elems bound: full chunks run at one shape, the
    # tail at its enclosing bucket — O(log chunk) compiled shapes per
    # machine total
    chunk = 1 << (max(1, chunk_elems // per_cand).bit_length() - 1)
    c0 = 0
    while c0 < nb:
        n_here = min(chunk, nb - c0)
        nb_b = n_here if n_here == chunk else bucket_size(n_here, lo=1)
        cs = pad_axis(coord_stack[c0:c0 + n_here], nb_b)
        src = jnp.asarray(cs[:, edges[:, 0]], dtype=jnp.int32)
        dst = jnp.asarray(cs[:, edges[:, 1]], dtype=jnp.int32)
        misses0 = _scorer.cache_info().misses
        fn = _scorer(dims, wrap, machine.core_dims, traffic, ne_b, nb_b)
        obs.annotate(compile_cache=(
            "miss" if _scorer.cache_info().misses > misses0 else "hit"))
        ev = fn(src, dst, w, bw_fields)
        sl = slice(c0, c0 + n_here)
        for key in ev:
            if key in out:
                out[key][sl] = np.asarray(ev[key][:n_here],
                                          dtype=out[key].dtype)
        c0 += n_here
    # averages from the TRUE count (int sums are exact, so this is
    # bit-identical to numpy's h.mean over the unpadded edge list)
    out["average_hops"] = out["total_hops"] / ne
    return out
