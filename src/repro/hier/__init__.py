"""repro.hier — the recursive hierarchical mapping subsystem.

The paper maps multicore machines at *node* granularity: intra-node
communication is free (§2), so partitioning one point per core only
multiplies the partitioner's work by cores_per_node without improving
the mapping.  This package generalises that optimisation to an N-level
recursive coarsen* -> map -> refine/expand* stack over the unified
mapping pipeline (the multilevel structure of Schulz & Woydt's
hierarchical process mapping):

1. :mod:`repro.hier.spec` — :class:`HierarchySpec`, the structured
   description of the hierarchy: ordered :class:`Level` entries
   (name, arity, per-level refinement budgets and mode), with
   ``flat()`` / ``node()`` / ``with_depth(n)`` / ``from_machine()`` /
   ``from_string()`` constructors;
2. :mod:`repro.hier.aggregate` — contract the task graph one level at
   a time into geometric clusters (weighted centroids + summed message
   volumes), with the same vectorised segment idioms as the
   partitioning engine;
3. :mod:`repro.hier.levels` — group the machine side to match
   (routers, then medoid-represented groups of routers), run the
   existing batched rotation-sweep pipeline ONCE at the top
   granularity, and expand downward level by level;
4. :mod:`repro.hier.refine` — per-level refinement (``refine_swaps``:
   the bounded monotone greedy pass, fused-foldable on device;
   ``refine_qap``: sparse-QAP local search with gain-bucket ordering),
   ``assign_cores`` expansion in intra-group SFC order, and
   ``polish_groups``: the exact-delta intra-group polish every group
   expansion runs before the level's bounded refinement.

Select it with ``PipelineConfig(hierarchy=HierarchySpec.node())`` /
``.with_depth(3)`` / ``.from_machine(machine, depth)`` (or the same
kwarg on ``select_mapping``).  The ``hier`` benchmark entry compares
flat, depth-2 and depth-3.
"""

from .aggregate import Aggregation, aggregate_tasks
from .levels import group_units, map_hierarchical, router_view
from .refine import (assign_cores, hilbert_key, polish_groups, refine_qap,
                     refine_swaps)
from .spec import HierarchySpec, Level

__all__ = [
    "Aggregation", "HierarchySpec", "Level", "aggregate_tasks",
    "assign_cores", "group_units", "hilbert_key", "map_hierarchical",
    "polish_groups", "refine_qap",
    "refine_swaps", "router_view",
]
