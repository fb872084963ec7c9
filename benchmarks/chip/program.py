"""The system under test, driven through its served entry point.

:class:`Program` turns the benchmark's plain arrays into the program's
own objects and serves each request through one
``repro.serve.MappingService``, the entry a job launcher calls.  It
reads back only what the service answers (the mapping and the stats it
reports) and the program's compile-cache counters.  Importing this
module imports the program; the harness does so only after it has
found the chip.
"""

from __future__ import annotations

import numpy as np

from repro import obs
from repro.core import Allocation, TaskGraph, make_machine
from repro.mapping import HierarchySpec, PipelineConfig
from repro.serve import MappingService
from repro.serve.engine import MappingRequest

OBJECTIVES = {"wh": "weighted_hops",
              "latency": ("latency_max", "weighted_hops")}


def pipeline_config(mix: dict) -> PipelineConfig:
    if mix["hierarchy"] == "flat":
        hierarchy = HierarchySpec.flat()
    elif mix["hierarchy"] == "node":
        hierarchy = HierarchySpec.node(
            refine_rounds=int(mix["refine_rounds"]),
            refine_top=int(mix["refine_top"]),
            refine_degree=int(mix["refine_degree"]))
    else:
        raise ValueError(f"unknown hierarchy {mix['hierarchy']!r}")
    return PipelineConfig(
        sfc=mix["sfc"], shift=bool(mix["shift"]),
        rotations=int(mix["rotations"]),
        objective=OBJECTIVES[mix["objective"]], hierarchy=hierarchy,
        partition_backend=mix["partition_backend"],
        score_backend=mix["score_backend"], fused=mix["fused"])


class Program:
    """One mapping service for a run, and the deployment's job and
    machine as the program's objects."""

    def __init__(self, dep, mix: dict):
        m = dep.machine
        self.machine = make_machine(
            m.dims, wrap=m.wrap + (False,), core_dims=1,
            bw_patterns=[np.asarray(p) for p in m.link_bw]
            + [np.array([np.inf])], name=m.name)
        job = dep.job
        self.graph = TaskGraph(job.coords, job.edges, job.weights)
        self.config = pipeline_config(mix)
        self.service = MappingService()

    def request(self, alloc: np.ndarray) -> MappingRequest:
        return MappingRequest(self.graph, Allocation(self.machine, alloc),
                              self.config)

    def serve(self, request: MappingRequest) -> dict:
        """Serve one request; the answer as plain data."""
        resp = self.service.map(request)
        res = resp.result
        stats = res.stats
        return {
            "status": resp.status,
            "task_to_core": np.asarray(res.task_to_proc),
            "objective": float(res.score),
            "rotation": res.rotation,
            "history": stats.get("refine_history"),
            "accepted": stats.get("refine_accepted"),
            "degraded": stats.get("degraded"),
            "fused": bool(stats.get("fused")),
        }

    @staticmethod
    def compile_misses() -> int:
        """Misses of the program's compile caches so far."""
        caches = obs.snapshot()["caches"]
        return int(sum(c.get("misses", 0) for c in caches.values()))
