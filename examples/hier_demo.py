"""Hierarchical mapping of a fragmented XK7 allocation, depth by depth.

A MiniGhost-style 3D stencil, one task per core, on a sparse Hilbert-
curve allocation of a Titan-like Gemini torus.  The flat pipeline
partitions one point per CORE; ``HierarchySpec.node()`` (depth 2)
coarsens the tasks into node-sized clusters and runs the same rotation
sweep at ROUTER granularity — ~cores_per_node x fewer points per engine
pass — then refines the node assignment with monotone greedy swaps and
expands clusters onto cores in intra-node SFC order.
``HierarchySpec.with_depth(3)`` adds a geometric grouping level above
the nodes: the sweep shrinks by another group-arity factor, the
grouping level runs the sparse-QAP local search, and each group
expansion is repaired by the exact-delta intra-group polish.

Run:  PYTHONPATH=src python examples/hier_demo.py
"""

import time

from repro.core import (evaluate, gemini_xk7, identity_mapping,
                        sfc_allocation, stencil_graph)
from repro.mapping import HierarchySpec, MappingPipeline, PipelineConfig


def main():
    # A Titan-like Gemini torus; the job gets 32768 cores (2048 nodes)
    # scattered across 8 fragments of the Hilbert-curve allocator.
    machine = gemini_xk7(dims=(25, 16, 24), cores_per_node=16)
    alloc = sfc_allocation(machine, 32768, nfragments=8, seed=0)
    app = stencil_graph((64, 32, 16))  # 32768 tasks, 7-point stencil

    base = evaluate(app, alloc, identity_mapping(app, alloc))
    specs = (("flat", HierarchySpec.flat()),
             ("depth2", HierarchySpec.node()),
             ("depth3", HierarchySpec.with_depth(3)))
    results = {}
    for name, spec in specs:
        mapper = MappingPipeline(PipelineConfig(
            sfc="FZ", shift=True, rotations=8, hierarchy=spec))
        t0 = time.perf_counter()
        res = mapper.map(app, alloc)
        dt = time.perf_counter() - t0
        results[name] = (dt, evaluate(app, alloc, res), res)

    cols = [name for name, _ in specs]
    print(f"{'metric':>18s} {'default':>12s} "
          + " ".join(f"{c:>12s}" for c in cols))
    for key in ("average_hops", "weighted_hops", "latency_max"):
        print(f"{key:>18s} {base[key]:12.2f} "
              + " ".join(f"{results[c][1][key]:12.2f}" for c in cols))

    tf, t2, t3 = (results[c][0] for c in cols)
    stats2 = results["depth2"][2].stats
    print(f"\nflat mapped in {tf:.2f}s, depth-2 in {t2:.2f}s "
          f"({tf / t2:.1f}x), depth-3 in {t3:.2f}s ({tf / t3:.1f}x) — "
          f"each depth-2 engine pass partitioned "
          f"{stats2['flat_sweep_points'] // stats2['sweep_points']}x "
          f"fewer points ({stats2['nclusters']} node clusters instead "
          f"of {app.n} cores); refinement accepted "
          f"{stats2['refine_accepted']} swaps "
          f"({stats2['refine_initial']:.0f} -> "
          f"{stats2['refine_final']:.0f} weighted hops).")

    # schema-v2 per-level breakdown of the depth-3 run
    print("\ndepth-3 levels (stats['levels']):")
    for lv in results["depth3"][2].stats["levels"]:
        extra = ""
        if "polish_accepted" in lv:
            extra += f", polish_accepted={lv['polish_accepted']}"
        if lv.get("refine_accepted"):
            extra += f", refine_accepted={lv['refine_accepted']}"
        print(f"  level {lv['level']} ({lv['name']:>7s}): "
              f"{lv['points']:>6d} sweep points, "
              f"{lv['clusters']} clusters on {lv['units']} units{extra}")


if __name__ == "__main__":
    main()
