"""MiniGhost weak scaling on a sparse-allocation Cray XK7 (Figs. 13-15).

Reproduces the paper's experiment structure: a 3D 7-point stencil, one
task per core, weak scaling 8K -> 128K cores on ALPS-style (Hilbert SFC)
sparse allocations of a Titan-like Gemini torus.  Mappings compared:

- Default : task i -> core i of the allocation (MPI rank order).
- Group   : MiniGhost's application-specific 2x2x4 blocking per node.
- Z2_1    : geometric mapping, FZ ordering (paper Alg. 1).
- Z2_2    : + largest-prime uneven bisection + bandwidth-scaled coords.
- Z2_3    : + 2x2x8 box lift (3D -> 6D node coordinates).

We report AverageHops and Latency(M) (Eqn. 7).  The paper's findings to
match: Default's hops/latency GROW with core count; Z2_1/Z2_2 stay ~flat
(the scalability claim); Z2_3 trades higher hops for lower bottleneck
Latency; geometric mappings beat Default by large factors at 128K.

All Z2 variants run through ``MappingPipeline``, the unified
``repro.mapping`` pipeline (vectorised partitioner + shared candidate
search).
"""

from __future__ import annotations

import time

import numpy as np

from repro.core import (MappingResult, evaluate, gemini_xk7,
                        identity_mapping, sfc_allocation, stencil_graph)
from repro.mapping import MappingPipeline, PipelineConfig

TASK_GRIDS = {
    8192: (32, 16, 16),
    16384: (32, 32, 16),
    32768: (32, 32, 32),
    65536: (64, 32, 32),
    131072: (64, 64, 32),
}

CORES_PER_ROUTER = 32  # 2 nodes x 16 cores share a Gemini router on XK7

# §4.3 rotation-search budget per Z2 variant (the batched sweep makes a
# real search affordable; pre-batching this was 0 = identity only).
ROTATIONS = 8


def group_mapping(dims, alloc, block=(2, 2, 4)) -> MappingResult:
    """MiniGhost's Group reordering: tasks in 2x2x4 blocks fill a node."""
    dims = np.asarray(dims)
    blk = np.asarray(block)
    nb = dims // blk
    idx = np.arange(int(np.prod(dims))).reshape(dims)
    # order: iterate blocks row-major, then cells within a block
    order = (idx.reshape(nb[0], blk[0], nb[1], blk[1], nb[2], blk[2])
             .transpose(0, 2, 4, 1, 3, 5).reshape(-1))
    # rank r (allocation core r) executes task order[r]
    t2p = np.empty_like(order)
    t2p[order] = np.arange(len(order))
    return MappingResult(t2p)


def run_point(ncores: int, seed: int, *, nfragments: int = 8) -> dict:
    machine = gemini_xk7(dims=(25, 16, 24), cores_per_node=CORES_PER_ROUTER)
    alloc = sfc_allocation(machine, ncores, nfragments=nfragments,
                           seed=seed)
    dims = TASK_GRIDS[ncores]
    graph = stencil_graph(dims, torus=False, weight=1.0)

    mappers = {
        "Default": None,
        "Group": "group",
        "Z2_1": MappingPipeline(PipelineConfig(sfc="FZ", shift=True,
                                               rotations=ROTATIONS)),
        "Z2_2": MappingPipeline(PipelineConfig(sfc="FZ", shift=True,
                                               bandwidth_scale=True,
                                               uneven_prime=True,
                                               rotations=ROTATIONS)),
        "Z2_3": MappingPipeline(PipelineConfig(sfc="FZ", shift=True,
                                               bandwidth_scale=True,
                                               uneven_prime=True,
                                               box=(2, 2, 8),
                                               rotations=ROTATIONS)),
    }
    out = {}
    for name, mapper in mappers.items():
        if mapper is None:
            res = identity_mapping(graph, alloc)
        elif mapper == "group":
            res = group_mapping(dims, alloc)
        else:
            res = mapper.map(graph, alloc)
        m = evaluate(graph, alloc, res)
        out[name] = {"average_hops": m["average_hops"],
                     "latency_max": m["latency_max"],
                     "data_max": m["data_max"],
                     "weighted_hops": m["weighted_hops"]}
    return out


def run(core_counts=(8192, 16384, 32768, 65536, 131072), seeds=(0, 1),
        quiet=False) -> dict:
    results: dict = {}
    for n in core_counts:
        per_seed = [run_point(n, s) for s in seeds]
        agg = {}
        for name in per_seed[0]:
            agg[name] = {k: float(np.mean([p[name][k] for p in per_seed]))
                         for k in per_seed[0][name]}
        results[n] = agg
        if not quiet:
            msg = "  ".join(
                f"{m}: hops={v['average_hops']:.2f} lat={v['latency_max']:.1f}"
                for m, v in agg.items())
            print(f"[minighost] {n}: {msg}")
    return results


def headline(results) -> dict:
    """Paper-comparable summary: reduction vs Default / Group at top."""
    top = max(results)
    r = results[top]
    best_geo = min(r[k]["latency_max"] for k in ("Z2_1", "Z2_2", "Z2_3"))
    geo_hops_top = min(r[k]["average_hops"] for k in ("Z2_1", "Z2_2"))
    geo_hops_bot = min(results[min(results)][k]["average_hops"]
                       for k in ("Z2_1", "Z2_2"))
    return {
        "latency_reduction_vs_default": 1 - best_geo / r["Default"][
            "latency_max"],
        "latency_reduction_vs_group": 1 - best_geo / r["Group"][
            "latency_max"],
        "geo_hops_growth_weak_scaling": geo_hops_top / max(geo_hops_bot,
                                                           1e-9),
        "default_hops_growth": (r["Default"]["average_hops"] /
                                results[min(results)]["Default"][
                                    "average_hops"]),
    }


def main():
    t0 = time.perf_counter()
    results = run()
    h = headline(results)
    dt = (time.perf_counter() - t0) * 1e6 / max(len(results), 1)
    print(f"minighost,{dt:.0f},lat_red_vs_default={h['latency_reduction_vs_default']:.2f}"
          f";lat_red_vs_group={h['latency_reduction_vs_group']:.2f}"
          f";geo_growth={h['geo_hops_growth_weak_scaling']:.2f}"
          f";default_growth={h['default_hops_growth']:.2f}")


if __name__ == "__main__":
    main()
