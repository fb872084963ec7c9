"""Mapping results and part matching (paper §4, Alg. 1).

This module hosts the primitives the mapping pipeline builds on: the
:class:`MappingResult` record, :func:`match_parts` (equal part numbers
task <-> processor), the paper's metrics of a result and the identity
("default") mapping.  The pipeline itself — machine transforms,
partitioner, candidate search — is :mod:`repro.mapping`; it imports
this module, never the other way around.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from . import metrics as M
from .machine import Allocation
from .taskgraph import TaskGraph


@dataclasses.dataclass
class MappingResult:
    """task_to_proc[t] = index into the processor coordinate array.

    For tnum > pnum several tasks share a processor.  ``proc_to_tasks`` is
    a list of task-index arrays per processor.  ``rotation`` records the
    winning (task_perm, proc_perm) of the rotation search; ``score`` its
    objective value (WeightedHops for the classic search).  ``stats``
    carries pipeline-reported accounting (engine-pass point counts, the
    hierarchical coarsening/refinement summary, ...) for benchmarks and
    tests; it never affects the mapping itself.
    """

    task_to_proc: np.ndarray
    rotation: tuple = ((), ())
    score: float = float("nan")
    stats: dict = dataclasses.field(default_factory=dict)

    def proc_to_tasks(self, pnum: int) -> list:
        out = [[] for _ in range(pnum)]
        for t, p in enumerate(self.task_to_proc):
            out[p].append(t)
        return [np.array(x, dtype=np.int64) for x in out]


def match_parts(mu_task: np.ndarray, mu_proc: np.ndarray) -> np.ndarray:
    """task_to_proc from equal part numbers (paper GETMAPPINGARRAYS).

    When several tasks share a part (tnum > pnum) they all map to the
    processor holding that part.  When parts hold one task and one
    processor (tnum == pnum) this is the paper's 1:1 case.
    """
    nparts = int(mu_proc.max()) + 1
    part_to_proc = np.full(nparts, -1, dtype=np.int64)
    part_to_proc[mu_proc] = np.arange(len(mu_proc))
    if (part_to_proc < 0).any():
        # multiple procs per part (pnum > nparts): keep first occurrence
        missing = np.flatnonzero(part_to_proc < 0)
        raise ValueError(f"parts with no processor: {missing[:5]}")
    return part_to_proc[mu_task]


def evaluate(graph: TaskGraph, alloc: Allocation, res: MappingResult) -> dict:
    """All paper metrics (§3) for a mapping result."""
    task_to_coord = alloc.coords[res.task_to_proc]
    return M.evaluate_mapping(alloc.machine, graph.edges, graph.weights,
                              task_to_coord)


def identity_mapping(graph: TaskGraph, alloc: Allocation) -> MappingResult:
    """Task i -> core i in allocation order (the "default" mapping of the
    paper's applications: MPI rank order)."""
    n = graph.n
    return MappingResult(np.arange(n) % alloc.n)
