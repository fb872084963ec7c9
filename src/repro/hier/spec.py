"""Structured description of the mapping hierarchy (ISSUE 10).

A :class:`HierarchySpec` is the ``hierarchy`` of a ``PipelineConfig``:
an ordered tuple of :class:`Level` coarsening steps (fine -> coarse),
each carrying its own group arity and refinement budget, so the
N-level recursive hierarchy of Schulz & Woydt's shared-memory process
mapping — pod -> rack -> node -> socket -> core — becomes a
first-class, content-addressable config value.

Depth terminology (``spec.depth == 1 + len(spec.levels)``):

- depth 1 (``levels == ()``)  : the classic FLAT pipeline — one point
  per core, no coarsening.
- depth 2 (one level)         : PR 3's node-granularity map — coarsen
  tasks to node-sized clusters, sweep at router granularity, refine,
  expand.  ``HierarchySpec.node()`` reproduces it bit for bit.
- depth >= 3                  : additional geometric grouping levels
  above the node level; each level divides the top-sweep point count
  by its ``arity`` and gets its own refinement pass on the way down.

:meth:`HierarchySpec.from_string` parses the names the scenario
registry uses ("flat", "node", "depthN");
:meth:`HierarchySpec.from_machine` derives the node
arity from a :class:`repro.core.Machine`'s core dims.  Specs are frozen
dataclasses, so :func:`repro.core.signature.config_signature`
canonicalises them field by field — two equal specs built through
different constructors produce the SAME cache key (asserted in
tests/test_hierarchy_spec.py).
"""

from __future__ import annotations

import dataclasses
import re

__all__ = ["Level", "HierarchySpec", "HIERARCHY_NAMES"]

# arity of grouping levels ABOVE the node level when not given
# explicitly ("depth3"/"depth4" names, from_machine defaults).  4
# units per group still cuts the top sweep 4x per level, and smaller
# groups keep the medoid abstraction honest: the expansion error the
# intra-group polish must repair grows with group radius, and at arity
# 4 the polished depth-3 map lands within ~4% of depth-2 quality on
# the hier benchmark (arity 8 leaves ~6%, arity 16 ~13%).
DEFAULT_GROUP_ARITY = 4

# refinement-budget defaults of the node level (the classic two-level
# node map that ``HierarchySpec.node()`` builds).
DEFAULT_REFINE_ROUNDS = 2
DEFAULT_REFINE_TOP = 64
DEFAULT_REFINE_DEGREE = 4

# grouping-level default for the sparse-QAP pass of deep hierarchies:
# one round.  The intra-group polish below does the bulk repair after
# every group expansion, so the QAP search only needs to catch the
# cross-group moves the polish cannot make.
DEFAULT_QAP_ROUNDS = 1

# rounds of the intra-group polish pass run when a GROUP-level
# assignment is expanded one level down (depth >= 3 only; a depth-2
# spec has no group expansion, so the node map never sees it).  The
# per-round gain decays geometrically; 4 rounds capture ~90% of the
# converged improvement at a fraction of the cost.
DEFAULT_POLISH_ROUNDS = 4

HIERARCHY_NAMES = ("flat", "node", "depth<N>")


@dataclasses.dataclass(frozen=True)
class Level:
    """One coarsening step of the hierarchy (fine -> coarse).

    name          : label of the units this level groups INTO ("node",
                    "rack", "pod", ...) — report/span attribution only.
    arity         : children per group.  ``None`` derives it at map
                    time: the first level takes the machine's
                    cores-per-node (the paper's node granularity),
                    deeper levels take :data:`DEFAULT_GROUP_ARITY`.
    refine_rounds / refine_top / refine_degree :
                    budget of the swap-refinement pass run at THIS
                    level's granularity (rounds, hottest clusters per
                    round, nearest units proposed per cluster) — the
                    same bounds the old flat knobs applied to the
                    single node level.
    refine_mode   : "swap" — the bounded greedy network-nearest swap
                    pass (PR 3, fused-foldable, the bit-identity
                    baseline); "qap" — the sparse-QAP local search
                    (:func:`repro.hier.refine.refine_qap`): per-cluster
                    best-single-move + pairwise-swap neighbourhoods
                    over the sparse inter-cluster edge set, gain-bucket
                    ordered, monotone with full re-score verify.
    polish_rounds : rounds of the intra-group polish pass
                    (:func:`repro.hier.refine.polish_groups`) run when
                    THIS level's assignment is produced by expanding a
                    group level above it — exact KL-style swap deltas
                    inside every group at once, repairing the member
                    placements the medoid abstraction could not see.
                    Only reachable at depth >= 3 (depth 2 has no group
                    expansion), so it never perturbs the node map.
    """

    name: str = "node"
    arity: int | None = None
    refine_rounds: int = DEFAULT_REFINE_ROUNDS
    refine_top: int = DEFAULT_REFINE_TOP
    refine_degree: int = DEFAULT_REFINE_DEGREE
    refine_mode: str = "swap"
    polish_rounds: int = DEFAULT_POLISH_ROUNDS

    def __post_init__(self):
        if self.arity is not None and int(self.arity) < 2:
            raise ValueError(
                f"level {self.name!r}: arity must be >= 2 or None "
                f"(got {self.arity!r})")
        if self.refine_mode not in ("swap", "qap"):
            raise ValueError(
                f"level {self.name!r}: unknown refine_mode "
                f"{self.refine_mode!r}; accepted: 'swap', 'qap'")
        for f in ("refine_rounds", "refine_top", "refine_degree",
                  "polish_rounds"):
            if int(getattr(self, f)) < 0:
                raise ValueError(
                    f"level {self.name!r}: {f} must be >= 0")


_DEPTH_RE = re.compile(r"^depth([0-9]+)$")

# default names for derived grouping levels, innermost first (level 1
# is always the node level; deeper levels walk outward)
_LEVEL_NAMES = ("node", "socket", "rack", "pod")


def _level_name(i: int) -> str:
    return _LEVEL_NAMES[i] if i < len(_LEVEL_NAMES) else f"level{i + 1}"


def _int_prod(seq) -> int:
    out = 1
    for x in seq:
        out *= int(x)
    return out


@dataclasses.dataclass(frozen=True)
class HierarchySpec:
    """Ordered coarsening levels of the mapping hierarchy.

    ``levels`` runs fine -> coarse: ``levels[0]`` groups tasks/cores
    into nodes, ``levels[1]`` groups nodes, and so on.  An empty tuple
    is the flat pipeline.  Instances are frozen and hashable — they are
    config values and cache-key material.
    """

    levels: tuple = ()

    def __post_init__(self):
        levels = tuple(self.levels)
        if not all(isinstance(lv, Level) for lv in levels):
            raise TypeError("HierarchySpec.levels must contain Level "
                            "instances")
        object.__setattr__(self, "levels", levels)

    # -- identity -------------------------------------------------------

    @property
    def depth(self) -> int:
        """Number of granularities mapped (1 = flat, 2 = node, ...)."""
        return 1 + len(self.levels)

    @property
    def kind(self) -> str:
        """Short label for spans/stats: "flat", "node" or "depthN"."""
        if not self.levels:
            return "flat"
        if len(self.levels) == 1:
            return "node"
        return f"depth{self.depth}"

    @property
    def is_flat(self) -> bool:
        return not self.levels

    # -- constructors ---------------------------------------------------

    @classmethod
    def flat(cls) -> "HierarchySpec":
        """The classic flat pipeline (depth 1, no coarsening)."""
        return cls(())

    @classmethod
    def node(cls, *, refine_rounds: int = DEFAULT_REFINE_ROUNDS,
             refine_top: int = DEFAULT_REFINE_TOP,
             refine_degree: int = DEFAULT_REFINE_DEGREE,
             refine_mode: str = "swap") -> "HierarchySpec":
        """PR 3's two-level node-granularity hierarchy (depth 2), with
        the node arity derived from the machine at map time."""
        return cls((Level("node", None, refine_rounds, refine_top,
                          refine_degree, refine_mode),))

    @classmethod
    def with_depth(cls, n: int, *,
                   group_arity: int = DEFAULT_GROUP_ARITY,
                   refine_rounds: int | None = None,
                   refine_top: int = DEFAULT_REFINE_TOP,
                   refine_degree: int = DEFAULT_REFINE_DEGREE,
                   polish_rounds: int = DEFAULT_POLISH_ROUNDS
                   ) -> "HierarchySpec":
        """An ``n``-granularity hierarchy: the node level plus ``n - 2``
        grouping levels of ``group_arity`` units per group.  Grouping
        levels (depth >= 3) refine with the sparse-QAP local search;
        the node level keeps the fused-foldable swap pass.

        ``refine_rounds=None`` picks per-level defaults: at depth 2 the
        node level keeps :data:`DEFAULT_REFINE_ROUNDS` (bit-identical
        to :meth:`node`); at depth >= 3 the node level's bounded swap
        pass is OFF (``polish_rounds`` of intra-group polish at every
        group expansion supersede it — measured on the hier benchmark,
        two swap rounds after the polish buy < 0.1% quality for ~40%
        of depth-2's whole runtime) and grouping levels run
        :data:`DEFAULT_QAP_ROUNDS` of the QAP search.  An explicit
        ``refine_rounds`` applies to every level."""
        if n < 1:
            raise ValueError(f"depth must be >= 1, got {n}")
        if n == 1:
            return cls.flat()
        node_rounds = refine_rounds if refine_rounds is not None else (
            DEFAULT_REFINE_ROUNDS if n == 2 else 0)
        qap_rounds = (refine_rounds if refine_rounds is not None
                      else DEFAULT_QAP_ROUNDS)
        kw = dict(refine_top=refine_top, refine_degree=refine_degree,
                  polish_rounds=polish_rounds)
        levels = [Level("node", None, refine_mode="swap",
                        refine_rounds=node_rounds, **kw)]
        levels += [Level(_level_name(i), group_arity,
                         refine_mode="qap", refine_rounds=qap_rounds,
                         **kw)
                   for i in range(1, n - 1)]
        return cls(tuple(levels))

    @classmethod
    def from_string(cls, name: str) -> "HierarchySpec":
        """Parse a hierarchy name of the scenario registry:
        ``"flat"``, ``"node"`` or ``"depthN"`` (N >= 1)."""
        if name == "flat":
            return cls.flat()
        if name == "node":
            return cls.node()
        m = _DEPTH_RE.match(name)
        if m:
            return cls.with_depth(int(m.group(1)))
        raise ValueError(
            f"unknown hierarchy {name!r}; accepted: "
            f"{', '.join(repr(a) for a in HIERARCHY_NAMES)}")

    @classmethod
    def from_machine(cls, machine, depth: int = 2, *,
                     group_arity: int = DEFAULT_GROUP_ARITY,
                     refine_rounds: int = DEFAULT_REFINE_ROUNDS,
                     refine_top: int = DEFAULT_REFINE_TOP,
                     refine_degree: int = DEFAULT_REFINE_DEGREE
                     ) -> "HierarchySpec":
        """Derive a spec from a :class:`repro.core.Machine`: the node
        level's arity is the machine's cores-per-node (product of its
        core dims), deeper levels use ``group_arity``.  Machines
        without core dims keep ``arity=None`` (one cluster per router,
        like :meth:`node`)."""
        spec = cls.with_depth(depth, group_arity=group_arity,
                              refine_rounds=refine_rounds,
                              refine_top=refine_top,
                              refine_degree=refine_degree)
        if not spec.levels or not machine.core_dims:
            return spec
        nd = machine.ndim - machine.core_dims
        cores = _int_prod(machine.dims[nd:])
        if cores >= 2:
            levels = (dataclasses.replace(spec.levels[0], arity=cores),
                      ) + spec.levels[1:]
            spec = cls(levels)
        return spec

    # -- derived specs (resilience ladder) -------------------------------

    def truncated(self, depth: int) -> "HierarchySpec":
        """The same spec cut down to ``depth`` granularities (the
        resilience ladder's depth-degradation rung)."""
        if depth < 1:
            raise ValueError(f"depth must be >= 1, got {depth}")
        return HierarchySpec(self.levels[: depth - 1])

    def with_refine(self, *, rounds: int | None = None,
                    top: int | None = None,
                    degree: int | None = None,
                    polish: int | None = None) -> "HierarchySpec":
        """Every level's refinement budget overridden (``refine_0`` on
        the degradation ladder uses ``rounds=0, polish=0``)."""
        changes = {k: v for k, v in (("refine_rounds", rounds),
                                     ("refine_top", top),
                                     ("refine_degree", degree),
                                     ("polish_rounds", polish))
                   if v is not None}
        return HierarchySpec(tuple(dataclasses.replace(lv, **changes)
                                   for lv in self.levels))

    @property
    def refine_rounds_total(self) -> int:
        return sum(lv.refine_rounds for lv in self.levels)

    @property
    def polish_rounds_total(self) -> int:
        """Total polish budget — nonzero only matters at depth >= 3."""
        return sum(lv.polish_rounds for lv in self.levels)

