"""HOMME on Titan's Gemini network (paper Figs. 10-12).

Strong scaling of an ne=120 cubed sphere (86,400 elements) on sparse
SFC allocations of a Cray XK7.  Mappings (paper §5.3.1):

- SFC  : HOMME's Hilbert partition + Titan's default SFC rank order
         (locality-preserving on both sides — the hard-to-beat baseline).
- Z2_1 : plain geometric map+partition (FZ).
- Z2_2 : + largest-prime uneven bisection + bandwidth-scaled coords.
- Z2_3 : + 2x2x8 box lift to 6D node coordinates.

Findings to match (Figs. 10-12): Z2_1 HURTS (splits nodes); Z2_2 ~
matches SFC; Z2_3 cuts Latency(M) (up to ~18% at 86,400 ranks in the
paper) while RAISING WeightedHops ~25% — the bandwidth-aware trade.
Per-dim: SFC's worst latency sits on the slow Y cables; Z2_3 moves
traffic to fast X/Z links.  Z2 variants run through the unified
``repro.mapping`` pipeline (``MappingPipeline``).
"""

from __future__ import annotations

import time

import numpy as np

from repro.core import (MappingResult, cube_coords, cube_sphere_graph,
                        evaluate, gemini_xk7, sfc_allocation)
from repro.core.orderings import hilbert_index
from repro.mapping import MappingPipeline, PipelineConfig

NE = 120
CORES_PER_ROUTER = 32

# §4.3 rotation-search budget per Z2 variant (the batched sweep makes a
# real search affordable; pre-batching this was 0 = identity only).
ROTATIONS = 8


def homme_sfc_parts(ne: int, nparts: int) -> np.ndarray:
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


def cray_rank_order(alloc, box=(2, 2, 4)):
    """Titan's default rank ordering: traverse a*2*4 router boxes fully
    before crossing slow links (ALPS bandwidth-prioritised order)."""
    from repro.core.machine import Allocation
    c = alloc.coords
    b = np.asarray(box)
    outer = c[:, :3] // b
    inner = c[:, :3] % b
    order = np.lexsort((c[:, 3], inner[:, 2], inner[:, 1], inner[:, 0],
                        outer[:, 2], outer[:, 1], outer[:, 0]))
    return Allocation(alloc.machine, c[order])


def run_point(nranks: int, seed: int) -> dict:
    machine = gemini_xk7(dims=(25, 16, 24),
                         cores_per_node=CORES_PER_ROUTER)
    alloc_raw = sfc_allocation(machine, nranks, nfragments=4, seed=seed)
    # rank r runs on core r of the *Cray-ordered* allocation (the real
    # Titan default); "SFC-ideal" uses the allocator's own Hilbert order
    # (an idealised upper bound where both curves coincide exactly).
    alloc = cray_rank_order(alloc_raw)
    graph = cube_sphere_graph(NE)
    tc = cube_coords(NE)

    out = {}
    parts = homme_sfc_parts(NE, nranks)
    out["SFC"] = evaluate(graph, alloc, MappingResult(parts))
    out["SFC-ideal"] = evaluate(graph, alloc_raw, MappingResult(parts))
    variants = {
        "Z2_1": PipelineConfig(sfc="FZ", shift=True, rotations=ROTATIONS),
        "Z2_2": PipelineConfig(sfc="FZ", shift=True, uneven_prime=True,
                               bandwidth_scale=True, rotations=ROTATIONS),
        "Z2_3": PipelineConfig(sfc="FZ", shift=True, uneven_prime=True,
                               bandwidth_scale=True, box=(2, 2, 8),
                               rotations=ROTATIONS),
    }
    for name, mc in variants.items():
        res = MappingPipeline(mc).map(graph, alloc, task_coords=tc)
        out[name] = evaluate(graph, alloc, res)
    return out


def normalize(res: dict) -> dict:
    base = res["SFC"]
    table = {}
    for k, v in res.items():
        table[k] = {
            "WH": v["weighted_hops"] / max(base["weighted_hops"], 1e-9),
            "TM": v["num_offnode_messages"] / max(
                base["num_offnode_messages"], 1),
            "Data": v["data_max"] / max(base["data_max"], 1e-9),
            "Latency": v["latency_max"] / max(base["latency_max"], 1e-9),
        }
    return table


def per_dim(res: dict, keys=("SFC", "Z2_3")) -> dict:
    """Fig. 12: Data and Latency per (dim, direction), normalized to
    SFC X+."""
    base = res["SFC"]["per_dim"]["dim0+"]
    table = {}
    for k in keys:
        pd_ = res[k]["per_dim"]
        table[k] = {
            f"{'XYZ'[d]}{s}": {
                "data": pd_[f"dim{d}{s}"]["data_max"] /
                max(base["data_max"], 1e-9),
                "lat": pd_[f"dim{d}{s}"]["lat_max"] /
                max(base["lat_max"], 1e-9),
            }
            for d in range(3) for s in "+-"}
    return table


def run(rank_counts=(10800, 21600, 43200, 86400), seeds=(0, 1),
        quiet=False):
    results = {}
    for n in rank_counts:
        tabs = [normalize(run_point(n, s)) for s in seeds]
        agg = {}
        for k in tabs[0]:
            agg[k] = {m: float(np.mean([t[k][m] for t in tabs]))
                      for m in tabs[0][k]}
        results[n] = agg
        if not quiet:
            print(f"[homme_titan] {n}: " + "  ".join(
                f"{k}: lat={v['Latency']:.2f} wh={v['WH']:.2f}"
                for k, v in agg.items()))
    return results


def main():
    t0 = time.perf_counter()
    results = run()
    top = max(results)
    z3 = results[top]["Z2_3"]
    dt = (time.perf_counter() - t0) * 1e6 / len(results)
    print(f"homme_titan,{dt:.0f},z2_3_latency_vs_sfc_at_{top}="
          f"{z3['Latency']:.3f};z2_3_wh_vs_sfc={z3['WH']:.3f}")


if __name__ == "__main__":
    main()
