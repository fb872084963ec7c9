"""HOMME on BlueGene/Q (paper Table 2 + Figs. 8-9).

Cubed-sphere atmosphere mesh mapped onto a 5D-torus block allocation.
Compared mappings (paper §5.2):

- SFC      : HOMME's Hilbert SFC partition of cube faces + ABCDET rank
             order (the application default).
- SFC+Z2   : SFC partition, then OUR geometric mapping of the parts.
- Z2       : our one-step partition+map (tnum > pnum path of Alg. 1).

Each Z2 variant runs with Sphere / Cube / 2DFace task-coordinate
transforms (Fig. 7) and optionally the "+E" architecture optimisation
(drop the E dim from node coords so E-neighbour pairs stay together).

Wall-clock is not measurable here; we report the paper's §3 metrics.
Z2 variants run through the unified ``repro.mapping`` pipeline
(``MappingPipeline``).
Findings to match: per-dim Data — SFC overloads D/E links and starves
A/B/C; Z2 balances them and cuts max Data; improvements grow with rank
count (8K -> 32K).
"""

from __future__ import annotations

import time

import numpy as np

from repro.core import (MappingResult, bgq, block_allocation, cube_coords,
                        cube_sphere_graph, evaluate, face2d_coords)
from repro.core.orderings import hilbert_index
from repro.mapping import MappingPipeline, PipelineConfig


# allocation shapes used on Mira for 512/1024/2048 nodes (x16 ranks/node)
ALLOC_DIMS = {
    8192: (4, 4, 4, 4, 2),
    16384: (4, 4, 4, 8, 2),
    32768: (4, 4, 4, 16, 2),
}

NE = 128  # 6*128*128 = 98,304 elements (the paper's hybrid/MPI dataset)

# §4.3 rotation-search budget per Z2 mapping.  The batched sweep
# partitions all rotations in ~2 engine passes, so a real search is now
# affordable where the pre-batching default was 0 (identity only).
ROTATIONS = 8


def homme_sfc_parts(ne: int, nparts: int) -> np.ndarray:
    """HOMME's default partition: Hilbert SFC on each cube face,
    faces concatenated, split into equal contiguous chunks."""
    n = 6 * ne * ne
    rem = np.arange(n) % (ne * ne)
    fi, fj = rem // ne, rem % ne
    bits = int(np.ceil(np.log2(ne)))
    h = hilbert_index(np.stack([fi, fj], axis=1), bits)
    order = np.argsort(np.arange(n) // (ne * ne) * (4 ** bits + 1) + h,
                       kind="stable")
    parts = np.zeros(n, dtype=np.int64)
    bounds = (np.arange(1, nparts) * n) // nparts
    parts[order] = np.searchsorted(bounds, np.arange(n), side="right")
    return parts


def sfc_mapping(graph, alloc, nranks: int) -> MappingResult:
    """SFC partition + ABCDET rank order == part i -> core i."""
    parts = homme_sfc_parts(NE, nranks)
    return MappingResult(parts)  # core index == part index (ABCDET order)


def run_point(nranks: int, *, transforms=("sphere", "cube", "face2d"),
              plus_e=(False, True)) -> dict:
    machine = bgq(dims=ALLOC_DIMS[nranks], cores_per_node=16)
    alloc = block_allocation(machine)
    graph = cube_sphere_graph(NE)
    assert alloc.n == nranks

    coords_by_name = {
        "sphere": graph.coords,
        "cube": cube_coords(NE),
        "face2d": face2d_coords(NE),
    }
    out = {}
    base = evaluate(graph, alloc, sfc_mapping(graph, alloc, nranks))
    out["SFC"] = base

    for tname in transforms:
        tc = coords_by_name[tname]
        for pe in plus_e:
            drop = (4,) if pe else ()   # E is dim index 4 of (A,B,C,D,E)
            tag = f"Z2-{tname}" + ("+E" if pe else "")
            mapper = MappingPipeline(PipelineConfig(
                sfc="FZ", shift=True, drop=drop, rotations=ROTATIONS))
            res = mapper.map(graph, alloc, task_coords=tc)
            out[tag] = evaluate(graph, alloc, res)

            # SFC+Z2: map SFC parts (with centroid coords) via Z2
            parts = homme_sfc_parts(NE, nranks)
            cent = np.zeros((nranks, tc.shape[1]))
            np.add.at(cent, parts, tc)
            cent /= np.bincount(parts, minlength=nranks)[:, None]
            pres = mapper.map(
                _part_graph(graph, parts, nranks), alloc,
                task_coords=cent)
            # compose: element -> part -> core
            res2 = MappingResult(pres.task_to_proc[parts])
            out[f"SFC+Z2-{tname}" + ("+E" if pe else "")] = evaluate(
                graph, alloc, res2)
    return out


def _part_graph(graph, parts, nparts):
    """Quotient graph of parts (edges between parts, summed weights)."""
    from repro.core.taskgraph import TaskGraph
    pe = parts[graph.edges]
    m = pe[:, 0] != pe[:, 1]
    eid = pe[m]
    w = graph.weights[m]
    key = eid[:, 0] * nparts + eid[:, 1]
    uniq, inv = np.unique(key, return_inverse=True)
    ww = np.zeros(len(uniq))
    np.add.at(ww, inv, w)
    edges = np.stack([uniq // nparts, uniq % nparts], axis=1)
    coords = np.zeros((nparts, 1))
    return TaskGraph(coords, edges, ww)


def summarize(res: dict) -> dict:
    base = res["SFC"]
    out = {}
    for k, v in res.items():
        out[k] = {
            "weighted_hops": v["weighted_hops"],
            "data_max": v["data_max"],
            "latency_max": v["latency_max"],
            "wh_vs_sfc": v["weighted_hops"] / max(base["weighted_hops"], 1),
            "data_vs_sfc": v["data_max"] / max(base["data_max"], 1e-9),
        }
    return out


def per_dim_table(res: dict, keys=("SFC", "Z2-face2d+E")) -> dict:
    """Fig. 9 analogue: max Data per network dimension A..E."""
    dims = "ABCDE"
    table = {}
    for k in keys:
        if k not in res:
            continue
        per = res[k]["per_dim"]
        table[k] = {dims[i]: per[f"dim{i}+"]["data_max"] +
                    per[f"dim{i}-"]["data_max"] for i in range(5)}
    return table


def run(rank_counts=(8192, 16384, 32768), quiet=False):
    results = {}
    for n in rank_counts:
        r = run_point(n)
        results[n] = summarize(r)
        results[n]["_per_dim"] = per_dim_table(r)
        if not quiet:
            best = min((v["data_vs_sfc"], k) for k, v in results[n].items()
                       if not k.startswith("_"))
            print(f"[homme_bgq] {n} ranks: best Data(M) vs SFC = "
                  f"{best[0]:.2f} ({best[1]})")
    return results


def main():
    t0 = time.perf_counter()
    results = run()
    top = max(results)
    best = min((v["data_vs_sfc"], k) for k, v in results[top].items()
               if not k.startswith("_"))
    dt = (time.perf_counter() - t0) * 1e6 / len(results)
    print(f"homme_bgq,{dt:.0f},best_data_vs_sfc_at_{top}={best[0]:.3f}"
          f";variant={best[1]}")


if __name__ == "__main__":
    main()
