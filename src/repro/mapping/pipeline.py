"""The mapping pipeline: machine transforms -> partitioner -> matching
-> candidate scoring (paper Alg. 1 + §4.3, one engine for every caller).

``MappingPipeline`` owns the full Z2-style flow (the paper's mapper;
``meshmap.select_mapping`` and the serve layer call it too):

- :meth:`machine_coords` applies the machine-side transforms (core-dim
  drop, torus shift, bandwidth scaling, "+E" dim drops, box lift);
- :meth:`map_candidate` runs one geometric mapping (Algorithm 1) for a
  single rotation/scaling candidate through the level-synchronous
  vectorised partitioner (on device when ``partition_backend``
  resolves to "jax");
- :meth:`map_candidates` runs a WHOLE rotation sweep in ~2 batched
  engine passes: the unique task and processor dimension permutations
  become outermost segments of one ``order_points_batched`` call per
  side, bit-identical to calling :meth:`map_candidate` per candidate
  (the oracle the tests compare against);
- :meth:`map` enumerates the rotation candidates and scores them with
  the batched :class:`repro.mapping.candidates.CandidateSearch`
  (``score_backend`` selects numpy or the jit-compiled JAX scorer).

:func:`fused_engages` is the one rule deciding whether a config runs
the fused whole-pipeline program (:mod:`repro.mapping.fused`).
"""

from __future__ import annotations

import dataclasses
import threading

import numpy as np

from repro import obs
from repro.core.kmeans import closest_subset
from repro.core.machine import Allocation
from repro.core.mapping import MappingResult, match_parts
from repro.core.metrics import get_evaluator
from repro.core.orderings import (order_points, order_points_batched,
                                  resolve_partition_backend)
from repro.core.transforms import (apply_permutation, box_lift, drop_dims,
                                   scale_by_bandwidth, shift_torus)
from repro.hier.spec import HierarchySpec, Level
from repro.mapping.candidates import CandidateSearch, rotation_candidates


@dataclasses.dataclass
class PipelineConfig:
    """Configuration of the unified mapping pipeline.

    Partitioner stage:
      sfc          : part-numbering ordering ("FZ" is the paper's winner).
      mfz          : use the MFZ task-side variant when pd % td == 0.
      longest_dim  : cut the longest dimension (False = strict
                     alternation).
      uneven_prime : Z2_2 — largest-prime-divisor uneven bisection.
      partition_backend : partition engine — "numpy" (the host
                     vectorised engine) or "jax" (device
                     ``partition_jax`` engine, bit-identical
                     permutations, resolved ONCE per pipeline down the
                     silent jax -> numpy chain).  With a jax/pallas
                     score backend the whole partition -> match ->
                     score -> select chain fuses into ONE compiled
                     program per candidate stack
                     (:mod:`repro.mapping.fused`, :func:`fused_engages`).
      fused        : "auto" (default) engages the fused whole-pipeline
                     program whenever the backends allow it; "off"
                     forces the unfused staged path — the first rung of
                     the serve layer's degradation ladder
                     (:mod:`repro.serve.resilience`).

    Machine-transform stage:
      shift           : torus wrap-around shifting of machine coords.
      bandwidth_scale : Z2_2 — scale distances by 1/link-bandwidth.
      box             : Z2_3 — lift machine coords by this box shape.
      box_outer_weight: scale of the between-box coordinates.
      drop            : dims to drop from machine coords (BG/Q "+E").

    Candidate-search stage:
      rotations : 0 = identity rotation only; otherwise max number of
                  (task_perm, proc_perm) pairs evaluated.
      objective : metric key (or tuple, lexicographic) minimised by the
                  search; "weighted_hops" is the paper's choice.
      score_backend : candidate scoring engine — "numpy" (default),
                  "jax" (jit-compiled, message counts bucketed to
                  power-of-two shapes so scenarios compile O(1) times)
                  or "pallas" (one fused kernel launch per stack,
                  :mod:`repro.kernels.mapscore`); silent fallback down
                  the pallas -> jax -> numpy chain.

    Hierarchy stage (:mod:`repro.hier`):
      hierarchy : a :class:`repro.hier.HierarchySpec` — ordered
                  coarsening levels (fine -> coarse), each with its own
                  arity and refinement budget.  ``HierarchySpec.flat()``
                  partitions one point per core (classic);
                  ``HierarchySpec.node()`` coarsens tasks into
                  node-sized clusters and sweeps at router granularity
                  (~cores_per_node x fewer points per engine pass);
                  ``HierarchySpec.with_depth(n)`` adds geometric
                  grouping levels above the node level, each dividing
                  the top-sweep point count by its arity and refining
                  on the way down.  Any other value raises
                  ``ValueError`` when the config is built, before any
                  mapping work or serve admission.
    """

    sfc: str = "FZ"
    mfz: str | bool = "auto"
    shift: bool = True
    bandwidth_scale: bool = False
    box: tuple | None = None
    box_outer_weight: float = 16.0
    drop: tuple = ()
    rotations: int = 0
    uneven_prime: bool = False
    longest_dim: bool = True
    partition_backend: str = "numpy"
    fused: str = "auto"
    objective: str | tuple = "weighted_hops"
    score_backend: str = "numpy"
    hierarchy: HierarchySpec = HierarchySpec.flat()

    def __post_init__(self):
        if not isinstance(self.hierarchy, HierarchySpec):
            raise ValueError(
                f"hierarchy must be a HierarchySpec, got "
                f"{self.hierarchy!r}; build one with HierarchySpec.flat()"
                f" / .node() / .with_depth(n), or parse a name with "
                f"HierarchySpec.from_string")


def fused_engages(config: PipelineConfig) -> bool:
    """Does ``config`` run the fused whole-pipeline program?

    True when ``fused`` is not "off", the partition backend RESOLVES to
    "jax" and the score backend to "jax" or "pallas" (so a machine
    without jax never engages it).  :class:`MappingPipeline` and the
    serve layer's degradation ladder both decide by this rule.
    """
    if config.fused == "off":
        return False
    if resolve_partition_backend(config.partition_backend) != "jax":
        return False
    return get_evaluator(config.score_backend)[0] in ("jax", "pallas")


# Process-wide pipeline registry: one MappingPipeline per distinct
# config.  Pipelines are stateless between map() calls but EXPENSIVE to
# warm (the jax/pallas scorers compile per (machine, bucket) and those
# compile caches are module-level), so every repeat-config caller —
# the serve layer, meshmap's per-call candidate grid, benchmarks —
# should resolve its pipeline here instead of constructing anew.
_SHARED_PIPELINES: dict = {}
_SHARED_LOCK = threading.Lock()


def shared_pipeline(config: PipelineConfig | None = None
                    ) -> "MappingPipeline":
    """The process-wide :class:`MappingPipeline` for ``config``.

    Keyed by content (:func:`repro.core.signature.config_signature`), so
    two independently-built equal configs share one pipeline instance
    and therefore one resolved evaluator — including under concurrent
    first calls (the serve layer maps from many threads).  The registry
    never evicts: distinct pipeline configs are few (they are small
    dataclasses of knobs, not per-request data).
    """
    from repro.core.signature import config_signature

    cfg = config or PipelineConfig()
    key = config_signature(cfg)
    with _SHARED_LOCK:
        pipe = _SHARED_PIPELINES.get(key)
        if pipe is None:
            pipe = _SHARED_PIPELINES[key] = MappingPipeline(cfg)
    return pipe


class MappingPipeline:
    """Maps a TaskGraph onto an Allocation through pluggable stages."""

    def __init__(self, config: PipelineConfig | None = None):
        self.config = config or PipelineConfig()
        cfg = self.config
        self.search = CandidateSearch(cfg.objective,
                                      backend=cfg.score_backend)
        # resolve the partition backend ONCE (silent jax -> numpy chain,
        # mirrors the score-backend discipline): hot paths then dispatch
        # on plain strings instead of re-probing the import per call
        self.partition_backend = resolve_partition_backend(
            cfg.partition_backend)
        self.order_backend = ("jax" if self.partition_backend == "jax"
                              else "vectorized")
        # fused whole-pipeline program: partition + match + score +
        # select as ONE compiled program per candidate stack
        self._fused = None
        if fused_engages(cfg):
            from repro.mapping.fused import FusedSweep
            self._fused = FusedSweep(
                self, get_evaluator(cfg.score_backend)[0])

    # -- stage 1: machine transforms ------------------------------------

    def machine_coords(self, alloc: Allocation) -> np.ndarray:
        """Apply the machine-side transforms of the pipeline.

        Core dims are dropped first: every core of a node carries its
        ROUTER's coordinates (paper §2 — coordinates come from the
        router; intra-node communication is free).  MJ then keeps a
        node's cores in consecutive parts automatically (equal
        coordinates are never separated before everything else is cut).
        """
        cfg = self.config
        machine = alloc.machine
        coords = alloc.coords.astype(np.float64)
        if machine.core_dims:
            nd = machine.ndim - machine.core_dims
            coords = coords[:, :nd]
        if cfg.shift:
            coords = shift_torus(coords, machine)
        if cfg.bandwidth_scale:
            coords = scale_by_bandwidth(coords, machine)
        if cfg.drop:
            coords = drop_dims(coords, cfg.drop)
        if cfg.box is not None:
            nd = coords.shape[1]
            box = tuple(cfg.box) + (1,) * (nd - len(cfg.box))
            coords = box_lift(coords, box, outer_weight=cfg.box_outer_weight)
        return coords

    # -- stages 2+3: partition + match for ONE candidate -----------------

    def _sfc_pair(self, td: int, pd: int) -> tuple[str, str]:
        """(task_sfc, proc_sfc) with the MFZ task-side variant when the
        processor dimensionality is a multiple of the task's."""
        cfg = self.config
        use_mfz = (cfg.mfz is True) or (
            cfg.mfz == "auto" and cfg.sfc == "FZ" and pd != td
            and pd % max(td, 1) == 0)
        if use_mfz:
            return "FZlow", "FZ"  # MFZ: flip the LOW half, smaller side
        return cfg.sfc, cfg.sfc

    def map_candidate(
        self,
        task_coords: np.ndarray,
        proc_coords: np.ndarray,
        *,
        task_weights: np.ndarray | None = None,
        task_perm=None,
        proc_perm=None,
    ) -> MappingResult:
        """Paper Algorithm 1 for one (task_perm, proc_perm) rotation."""
        cfg = self.config
        tc = np.asarray(task_coords, dtype=np.float64)
        pc = np.asarray(proc_coords, dtype=np.float64)
        if task_perm is not None:
            tc = apply_permutation(tc, task_perm)
        if proc_perm is not None:
            pc = apply_permutation(pc, proc_perm)
        tnum, td = tc.shape
        pnum, pd = pc.shape

        subset = None
        if tnum < pnum:
            subset = closest_subset(pc, tnum)
            pc = pc[subset]
            pnum = tnum
        np_parts = min(tnum, pnum)
        task_sfc, proc_sfc = self._sfc_pair(td, pd)

        mu_t = order_points(tc, np_parts, task_sfc, weights=task_weights,
                            longest_dim=cfg.longest_dim,
                            uneven_prime=cfg.uneven_prime,
                            backend=self.order_backend)
        mu_p = order_points(pc, np_parts, proc_sfc,
                            longest_dim=cfg.longest_dim,
                            uneven_prime=cfg.uneven_prime,
                            backend=self.order_backend)
        t2p = match_parts(mu_t, mu_p)
        if subset is not None:
            t2p = subset[t2p]
        return MappingResult(t2p, rotation=(tuple(task_perm or ()),
                                            tuple(proc_perm or ())))

    # -- stages 2+3 for a WHOLE rotation sweep ---------------------------

    def map_candidates(
        self,
        task_coords: np.ndarray,
        proc_coords: np.ndarray,
        cands,
        *,
        task_weights: np.ndarray | None = None,
    ) -> list:
        """Algorithm 1 for every rotation candidate of a sweep.

        A rotation only permutes the columns of one shared point cloud,
        which is equivalent to permuting the partitioner's cut-dimension
        priority (``dim_order``) over the un-permuted cloud.  The
        batched sweep therefore partitions the UNIQUE task-side and
        proc-side permutations in one ``order_points_batched`` call per
        side — ~2 engine passes for the whole sweep — and assembles the
        per-candidate ``task_to_proc`` arrays with one vectorised
        part-matching gather.  Results are bit-identical to calling
        :meth:`map_candidate` per candidate (guarded by the
        ``candidates`` benchmark and tests/test_batched.py).

        Takes that per-candidate path itself for a single candidate and
        for the tnum < pnum closest-subset case (the subset's centroid
        iteration sums coordinates in column order).  Hilbert batches
        too: ``order_points_batched`` treats each ``dim_orders`` row as
        a COLUMN permutation of the cloud (quantisation commutes with
        it), bit-identical to the per-candidate loop.
        """
        cfg = self.config
        tc = np.asarray(task_coords, dtype=np.float64)
        pc = np.asarray(proc_coords, dtype=np.float64)
        (tnum, td), (pnum, pd) = tc.shape, pc.shape
        if len(cands) == 1 or tnum < pnum:
            return [
                self.map_candidate(tc, pc, task_weights=task_weights,
                                   task_perm=c.task_perm,
                                   proc_perm=c.proc_perm)
                for c in cands
            ]

        np_parts = min(tnum, pnum)
        task_sfc, proc_sfc = self._sfc_pair(td, pd)

        # dedup each side: many (task_perm, proc_perm) pairs share a perm
        t_perms = [tuple(c.task_perm) if c.task_perm is not None
                   else tuple(range(td)) for c in cands]
        p_perms = [tuple(c.proc_perm) if c.proc_perm is not None
                   else tuple(range(pd)) for c in cands]
        ut = sorted(set(t_perms))
        up = sorted(set(p_perms))
        t_of = {p: i for i, p in enumerate(ut)}
        p_of = {p: i for i, p in enumerate(up)}

        common = dict(longest_dim=cfg.longest_dim,
                      uneven_prime=cfg.uneven_prime,
                      backend=self.order_backend)
        mu_t = order_points_batched(tc, np_parts, task_sfc,
                                    dim_orders=np.array(ut),
                                    weights=task_weights, **common)
        mu_p = order_points_batched(pc, np_parts, proc_sfc,
                                    dim_orders=np.array(up), **common)

        # vectorised GETMAPPINGARRAYS: part -> processor per proc rotation
        # (pnum == np_parts here, so every part holds exactly one proc);
        # int32 keeps the per-candidate assembly gathers cache-friendly
        part_to_proc = np.full((len(up), np_parts), -1, dtype=np.int32)
        part_to_proc[np.arange(len(up))[:, None], mu_p] = \
            np.arange(pnum, dtype=np.int32)[None, :]
        if (part_to_proc < 0).any():
            missing = np.flatnonzero((part_to_proc < 0).any(axis=0))
            raise ValueError(f"parts with no processor: {missing[:5]}")
        mu_t = mu_t.astype(np.int32)

        return [
            MappingResult(
                part_to_proc[p_of[pp]][mu_t[t_of[tp]]],
                rotation=(tuple(c.task_perm or ()),
                          tuple(c.proc_perm or ())))
            for c, tp, pp in zip(cands, t_perms, p_perms)
        ]

    # -- stage 4: candidate search ---------------------------------------

    def map(self, graph, alloc: Allocation,
            task_coords: np.ndarray | None = None,
            task_weights: np.ndarray | None = None) -> MappingResult:
        """Full pipeline: transforms, one batched rotation sweep through
        the partitioner, batched scoring; returns the best MappingResult
        (score = objective).

        A non-flat :class:`HierarchySpec` routes through
        :mod:`repro.hier` instead: coarsen tasks level by level, run
        the SAME rotation sweep at the TOP granularity, then expand
        downward one level at a time with a refinement pass per level.
        """
        cfg = self.config
        if not cfg.hierarchy.is_flat:
            from repro.hier.levels import map_hierarchical
            return map_hierarchical(self, graph, alloc,
                                    task_coords=task_coords,
                                    task_weights=task_weights)
        # span-derived stage timings (repro.obs): the spans ARE the
        # clocks — ``stats["timings"]`` keeps its exact legacy schema
        # ({fused_s | partition_s + score_s} + total_s), derived from
        # the span durations instead of a third ad-hoc timer dict
        timings = {}
        with obs.span("pipeline.map", hierarchy="flat",
                      partition_backend=self.partition_backend,
                      score_backend=cfg.score_backend) as root:
            pc = self.machine_coords(alloc)
            tc = np.asarray(task_coords if task_coords is not None
                            else graph.coords, dtype=np.float64)
            cands = rotation_candidates(tc.shape[1], pc.shape[1],
                                        cfg.rotations)
            sweep_points = int(len(tc) + alloc.n)
            root.annotate(sweep_points=sweep_points,
                          candidates=len(cands))
            best = None
            if self._fused is not None:
                with obs.span("pipeline.fused") as sp:
                    best = self._fused.run(graph, alloc, tc, pc, cands,
                                           task_weights=task_weights)
                if best is not None:
                    # partition + match + score ran as one device
                    # program; the stage split does not exist here
                    timings["fused_s"] = sp.duration_s
            if best is None:
                with obs.span("pipeline.partition",
                              points=sweep_points) as sp:
                    results = self.map_candidates(
                        tc, pc, cands, task_weights=task_weights)
                timings["partition_s"] = sp.duration_s
                with obs.span("pipeline.score",
                              candidates=len(cands)) as sp:
                    if len(results) == 1:
                        best = results[0]
                    else:
                        best, best_i, scores = self.search.best(
                            graph, alloc, results)
                        best.score = float(scores[best_i][0])
                timings["score_s"] = sp.duration_s
        timings["total_s"] = root.duration_s
        # stats schema v2: ONE per-level entry per mapped granularity
        # (flat = the single core level), replacing the ad-hoc
        # flat/hier key split; the legacy keys (hierarchy /
        # sweep_points / timings) stay derived for one release — see
        # README "MappingResult.stats schema"
        map_s = timings.get(
            "fused_s",
            timings.get("partition_s", 0.0) + timings.get("score_s", 0.0))
        best.stats.update(
            schema=2,
            hierarchy="flat",
            depth=1,
            levels=[{"level": 0, "name": "core",
                     "points": sweep_points,
                     "clusters": int(len(tc)), "units": int(alloc.n),
                     "coarsen_s": 0.0, "map_s": map_s, "refine_s": 0.0,
                     "refine_accepted": 0, "refine_evaluated": 0}],
            sweep_points=sweep_points,
            partition_backend=self.partition_backend,
            timings=timings,
            trace_id=root.trace_id)
        return best
