"""Public wrapper for the mapscore kernel: the ``evaluate_candidates``
contract with shape bucketing and a keyed compile cache.

``evaluate_candidates_pallas`` is what
``repro.core.metrics.evaluate_candidates(backend="pallas")`` resolves
to.  It gathers the per-message coordinate stacks, buckets BOTH dynamic
axes to padded power-of-two shapes — message count (zero-weight
self-edge padding, exact in the difference-array formulation) and
candidate count (zero rows, sliced away) — and launches the fused
kernel once per (machine structure, bucket) via a compile cache whose
hit/miss counters land in ``benchmarks/run.py --json``.  On CPU the
kernel runs in Pallas interpret mode (the parity-tested path); machines
whose link accumulators would not fit the VMEM budget fall back
to the jax scorer, with a one-time warning.
"""

from __future__ import annotations

import functools
import warnings

import numpy as np

import jax
import jax.numpy as jnp

from repro import faults, obs
from repro.core.machine import Machine
# bucketing + padding rules shared with the jax backend: the two
# accelerator paths must agree on bucket boundaries or cache keys drift
from repro.core.metrics_jax import bucket_size, pad_axis

from .kernel import acc_shapes, mapscore_call

TILE_MAX = 512         # messages per VMEM tile
VMEM_ACC_BUDGET = 10 << 20  # link-accumulator bytes before jax fallback


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.lru_cache(maxsize=None)
def _compiled(dims, wrap, core_dims, traffic, ne_b, tile, nb_b, ncols,
              interpret):
    """One jitted kernel launcher per (machine structure, shape bucket).

    Every cache entry sees exactly one input shape, so the ``lru_cache``
    hit/miss counters are a truthful compile-count proxy (mirrors
    ``repro.core.metrics_jax._scorer``).
    """
    del ne_b, nb_b, ncols  # shape part of the key only
    return jax.jit(functools.partial(
        mapscore_call, dims=dims, wrap=wrap, core_dims=core_dims,
        traffic=traffic, tile=tile, interpret=interpret))


# registry-backed stat/reset pair (repro.obs); auto-registers with
# ``obs.snapshot()`` under "scorer_pallas"
scorer_cache_stats, reset_scorer_cache = obs.instrument_compile_cache(
    "scorer_pallas", _compiled)


_VMEM_WARNED: set = set()


def _warn_vmem_fallback(machine: Machine) -> None:
    """Once-per-machine-shape warning for the VMEM-budget jax fallback."""
    key = (tuple(int(x) for x in machine.dims), machine.core_dims)
    if key in _VMEM_WARNED:
        return
    _VMEM_WARNED.add(key)
    warnings.warn(
        f"mapscore link accumulators for machine dims {key[0]} exceed "
        f"the VMEM budget ({vmem_accumulator_bytes(machine)} > "
        f"{VMEM_ACC_BUDGET} bytes); scoring falls back to the jax "
        "backend", RuntimeWarning, stacklevel=3)


def inv_bandwidth(machine: Machine) -> np.ndarray:
    """(sum of accumulator sublanes, 1) f32 1/bandwidth per link column:
    one block per network dim, ``acc_shapes`` sublanes tall and zero
    past the dim's extent, so the kernel reads each dim's block at a
    sublane-aligned offset."""
    dims = tuple(int(x) for x in machine.dims)
    blocks = []
    for k, (sp, _) in enumerate(acc_shapes(dims, machine.core_dims)):
        col = np.zeros(sp, dtype=np.float64)
        col[:dims[k]] = 1.0 / np.asarray(machine.bw(k, np.arange(dims[k])),
                                         dtype=np.float64)
        blocks.append(col)
    inv = np.concatenate(blocks) if blocks else np.zeros(8)
    return inv.astype(np.float32).reshape(-1, 1)


def vmem_accumulator_bytes(machine: Machine) -> int:
    """Bytes of VMEM link-accumulator scratch the kernel would allocate
    for ``machine`` (two f32 buffers per network dim)."""
    dims = tuple(int(x) for x in machine.dims)
    return sum(2 * sp * rp * 4
               for sp, rp in acc_shapes(dims, machine.core_dims))


def evaluate_candidates_pallas(machine: Machine, task_edges: np.ndarray,
                               edge_weights: np.ndarray | None,
                               coord_stack: np.ndarray, *,
                               traffic: bool = False,
                               chunk_elems: int = 1 << 24,
                               interpret: bool | None = None) -> dict:
    """Pallas implementation of ``evaluate_candidates`` (same contract;
    results within fp tolerance of the numpy reference, winner
    orderings pinned bit-identical by tests/test_mapscore.py).

    A candidate stack typically goes down in ONE kernel launch: VMEM
    usage is bounded by the tile size and the machine's link
    accumulators, not by the stack.  The candidate axis is still
    chunked by ``chunk_elems`` message-coordinates — like the other
    backends — so the HOST-side src/dst gathers stay bounded for huge
    sweeps; chunks run at power-of-two sizes (rounded down, so the
    bound holds) to keep the compile-cache key set O(log) per machine.
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
    if traffic and vmem_accumulator_bytes(machine) > VMEM_ACC_BUDGET:
        # machine too large for on-chip link state: jax fallback (warned
        # once per process so the rung change is observable)
        _warn_vmem_fallback(machine)
        from repro.core import metrics
        _, fn = metrics.get_evaluator("jax")
        return fn(machine, task_edges, edge_weights, coord_stack,
                  traffic=traffic, chunk_elems=chunk_elems)
    # marker span at the fault hook: an injected kernel fault lands in
    # the trace error-annotated exactly where a real one would; the
    # enclosing score.pallas span carries the wall-clock
    with obs.span("kernel.mapscore", candidates=int(nb), edges=int(ne)):
        faults.fire("kernel.mapscore")
    if interpret is None:
        interpret = not _on_tpu()

    dims = tuple(int(x) for x in machine.dims)
    wrap = tuple(bool(x) for x in machine.wrap)
    ncols = machine.ndim if traffic else min(coord_stack.shape[-1],
                                             machine.ndim)
    if coord_stack.shape[-1] < ncols:  # hop-only stacks may omit core dims
        coord_stack = pad_axis(coord_stack, ncols, axis=2)

    edges = np.asarray(task_edges, dtype=np.int64)
    w = np.ones(ne) if edge_weights is None else \
        np.asarray(edge_weights, dtype=np.float64)

    ne_b = bucket_size(ne)
    tile = min(TILE_MAX, ne_b)
    w_p = jnp.asarray(
        pad_axis(w.astype(np.float32).reshape(1, -1), ne_b, axis=1))
    inv_bw = jnp.asarray(inv_bandwidth(machine)) if traffic else None

    # pow2 candidate chunks bounded by chunk_elems (rounded down so the
    # bound holds): the host-side src/dst gathers stay capped on huge
    # sweeps while the compile-cache key set stays O(log) per machine
    per_cand = max(1, 2 * ne_b * ncols)
    chunk = 1 << (max(1, chunk_elems // per_cand).bit_length() - 1)
    c0 = 0
    while c0 < nb:
        n_here = min(chunk, nb - c0)
        nb_b = n_here if n_here == chunk else bucket_size(n_here, lo=1)
        # (nb, ncols, E): messages on lanes
        cs = np.swapaxes(coord_stack[c0:c0 + n_here, :, :ncols], 1, 2
                         ).astype(np.int32)
        src = pad_axis(pad_axis(cs[:, :, edges[:, 0]], ne_b, axis=2),
                       nb_b, axis=0)
        dst = pad_axis(pad_axis(cs[:, :, edges[:, 1]], ne_b, axis=2),
                       nb_b, axis=0)
        args = [jnp.asarray(src), jnp.asarray(dst), w_p]
        if traffic:
            args.append(inv_bw)
        misses0 = _compiled.cache_info().misses
        fn = _compiled(dims, wrap, machine.core_dims, traffic, ne_b, tile,
                       nb_b, ncols, bool(interpret))
        miss = _compiled.cache_info().misses > misses0
        obs.annotate(compile_cache="miss" if miss else "hit")
        if miss and interpret:
            obs.counter("pallas.interpret_compiles")
        outf, outi = fn(*args)
        outf = np.asarray(outf)
        outi = np.asarray(outi)
        sl = slice(c0, c0 + n_here)
        out["weighted_hops"][sl] = outf[:n_here, 0, 0].astype(np.float64)
        out["total_hops"][sl] = outi[:n_here, 0, 0].astype(np.int64)
        if traffic:
            out["data_max"][sl] = outf[:n_here, 0, 1].astype(np.float64)
            out["latency_max"][sl] = outf[:n_here, 0, 2].astype(np.float64)
        c0 += n_here
    out["average_hops"] = out["total_hops"] / ne
    return out
