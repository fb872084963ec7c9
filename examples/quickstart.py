"""Quickstart: the paper's geometric task mapping in 40 lines.

Maps a 2D stencil application onto a sparse allocation of a Cray-like
torus and prints the paper's §3 metrics for the default (rank-order)
mapping vs the geometric (MJ + Flipped-Z) mapping.

Run:  PYTHONPATH=src python examples/quickstart.py
"""

from repro.core import (evaluate, gemini_xk7, identity_mapping,
                        sfc_allocation, stencil_graph)
from repro.mapping import MappingPipeline, PipelineConfig


def main():
    # A Titan-like Gemini 3D torus; the job gets 4096 cores scattered
    # across 4 fragments of a Hilbert-curve allocator (sparse allocation).
    machine = gemini_xk7(dims=(25, 16, 24), cores_per_node=32)
    alloc = sfc_allocation(machine, 4096, nfragments=4, seed=0)

    # The application: a 64x64 grid of tasks, halo-exchange neighbours.
    app = stencil_graph((64, 64))

    # Default mapping: task i -> core i (MPI rank order).
    base = evaluate(app, alloc, identity_mapping(app, alloc))

    # Geometric mapping (paper Alg. 1): Multi-Jagged partitioning of task
    # and machine coordinates with Flipped-Z part numbering, torus
    # shifting, and bandwidth-scaled node coordinates.
    mapper = MappingPipeline(PipelineConfig(sfc="FZ", shift=True,
                                            bandwidth_scale=True))
    ours = evaluate(app, alloc, mapper.map(app, alloc))

    print(f"{'metric':>18s} {'default':>12s} {'geometric':>12s}")
    for key in ("average_hops", "weighted_hops", "data_max",
                "latency_max"):
        print(f"{key:>18s} {base[key]:12.2f} {ours[key]:12.2f}")
    red = 1 - ours["latency_max"] / base["latency_max"]
    print(f"\nbottleneck-link latency reduced by {red:.0%}")


if __name__ == "__main__":
    main()
