"""Topology-aware jax.sharding.Mesh construction — the paper's technique
as a first-class framework feature.

The logical mesh (e.g. ("pod","data","model")) is the *task graph*: jit
emits collectives per axis, with very different traffic weights (TP
all-reduces per layer on "model" >> FSDP gathers on "data" >> cross-DCN
on "pod").  The physical chips form the *machine graph* (ICI torus +
slow DCN dim).  geometric_map (MJ + FZ ordering, Alg. 1) consistently
orders both and hands us the device permutation that puts heavy-traffic
logical neighbours on adjacent chips.

On real TPU backends the chip coordinates come from device attributes;
on this CPU container they are synthesised from the machine model (the
512 fake host devices are assigned coords in allocation order).
"""

from __future__ import annotations

import numpy as np

import jax
from jax.sharding import Mesh

from repro.core import (Allocation, block_allocation, evaluate,
                        identity_mapping, logical_mesh_graph,
                        tpu_v5e_multipod, tpu_v5e_pod)
from repro.mapping import CandidateSearch, PipelineConfig, shared_pipeline

# Relative per-link traffic of one training step along each logical axis
# (bytes are arbitrary units; only ratios steer the mapper).
DEFAULT_AXIS_BYTES = {"pod": 1.0, "data": 8.0, "model": 64.0}


def machine_for(devices=None, *, pods: int = 1, side: int = 16):
    if pods > 1:
        return tpu_v5e_multipod(npods=pods, side=side)
    return tpu_v5e_pod(side=side)


def device_coords(devices, machine) -> np.ndarray:
    """Physical coordinates per device, inside ``machine``.

    Real TPUs expose ``device.coords`` (+ slice_index for multislice):
    the chip (x, y) is shifted so the slice starts at the origin of the
    machine's torus, and a chip that then falls outside the machine is
    an error.  Fake CPU devices get machine coordinates in enumeration
    order.
    """
    have_real = all(hasattr(d, "coords") and d.platform == "tpu"
                    for d in devices)
    if not have_real:
        return block_allocation(machine).coords[: len(devices)].astype(float)
    xy = np.asarray([list(d.coords)[:2] for d in devices], dtype=np.int64)
    xy -= xy.min(axis=0)
    if machine.ndim == 3:
        pod = np.asarray([getattr(d, "slice_index", 0) for d in devices])
        xy = np.concatenate([pod[:, None], xy], axis=1)
    if (xy >= np.asarray(machine.dims)).any():
        raise ValueError(f"device coordinates {xy.tolist()} fall outside "
                         f"the {tuple(machine.dims)} machine")
    return xy.astype(float)


def topology_mesh(axis_sizes: tuple[int, ...], axis_names: tuple[str, ...],
                  *, devices=None, machine=None, axis_bytes=None,
                  rotations: int = 16, return_report: bool = False,
                  score_backend: str = "numpy",
                  partition_backend: str = "numpy",
                  hierarchy="flat", sfc: str = "FZ", service=None):
    """Build a Mesh whose device order minimises modeled link traffic.

    Candidate-selection (the paper's §4.3 rotation search, generalised):
    we generate the default enumeration plus FZ geometric mappings under
    two task-coordinate scalings (raw indices and traffic-weighted
    1/bytes extents) x dimension rotations, score every candidate with
    the Latency(M)/WeightedHops model, and keep the winner.  The result
    is never worse than jax's enumeration order, and substantially
    better when the logical shape does not match the physical torus or
    the allocation is fragmented.

    Returns the Mesh (and optionally a report comparing the winner vs
    the default enumeration).
    """
    n = int(np.prod(axis_sizes))
    if devices is None:
        devices = jax.devices()[:n]
    if len(devices) < n:
        raise ValueError(f"need {n} devices, have {len(devices)}")
    devices = list(devices)[:n]
    if machine is None:
        pods = axis_sizes[axis_names.index("pod")] \
            if "pod" in axis_names else 1
        side = int(round((n // max(pods, 1)) ** 0.5))
        machine = machine_for(pods=pods, side=side)
    ab = axis_bytes or [DEFAULT_AXIS_BYTES.get(a, 8.0) for a in axis_names]
    graph = logical_mesh_graph(axis_sizes, tuple(ab), tuple(axis_names))
    alloc = Allocation(machine, device_coords(devices, machine).astype(int))
    best, best_metrics, base_metrics = select_mapping(
        graph, alloc, ab, rotations=rotations, score_backend=score_backend,
        partition_backend=partition_backend, hierarchy=hierarchy,
        sfc=sfc, service=service)
    order = best.task_to_proc  # logical flat index -> device index
    dev_array = np.array(devices, dtype=object)[order].reshape(axis_sizes)
    mesh = Mesh(dev_array, tuple(axis_names))
    if not return_report:
        return mesh
    return mesh, {"mapped": best_metrics, "default": base_metrics}


def select_mapping(graph, alloc, axis_bytes, *, rotations: int = 16,
                   score_backend: str = "numpy",
                   partition_backend: str = "numpy",
                   hierarchy="flat", sfc: str = "FZ",
                   service=None):
    """Candidate search: default order + SFC-geometric mappings (``sfc``
    picks the part numbering — "FZ" is the paper's winner, "H" the
    Hilbert curve; all five kinds run on-device under
    ``partition_backend="jax"``) under raw and traffic-scaled task
    coordinates x rotations; returns
    (best MappingResult, best metrics, default metrics).

    Candidate generation and scoring both run through the unified
    ``repro.mapping`` pipeline: each (scaling, rotation-budget) entry is
    one ``MappingPipeline.map`` call whose internal rotation search —
    the paper's WeightedHops objective — partitions the whole sweep in
    ~2 batched engine passes, and the outer selection scores every
    candidate in one batched (Latency(M), WeightedHops) pass
    (``score_backend="jax"`` routes it through the jit-compiled
    scorer; ``"pallas"`` through the fused on-chip kernel of
    :mod:`repro.kernels.mapscore`, falling back jax -> numpy when the
    kernel stack is unavailable).  The identity/default mapping is
    listed first, so on ties the search is never worse than jax's
    enumeration order.  ``partition_backend="jax"`` additionally moves
    the level-synchronous partitioner on device (silent jax -> numpy
    fallback, bit-identical permutations); combined with a jax/pallas
    score backend each pipeline pass's partition -> match -> score ->
    select chain runs as ONE compiled program per candidate stack
    (:mod:`repro.mapping.fused`) — the cold-path win the ``end2end``
    benchmark guards.

    ``hierarchy`` takes a :class:`repro.hier.HierarchySpec`
    (``HierarchySpec.node()``, ``.with_depth(3)``, ``.from_machine``;
    the strings "flat"/"node" remain deprecated aliases) and routes
    each pipeline call through the recursive coarsen* -> map ->
    refine/expand* subsystem (:mod:`repro.hier`) — worthwhile on
    machines with core dims or very large logical meshes; on a machine
    without core dims depth 2 degenerates to the router-granularity map
    plus the monotone swap refinement.

    Pipelines come from the process-wide :func:`shared_pipeline`
    registry (evaluator + compile caches resolved once per config, not
    once per mesh build).  Passing a :class:`repro.serve.MappingService`
    as ``service`` additionally serves each candidate pipeline pass
    through its content-addressed result cache, so REPEAT mesh builds —
    the same logical shape on the same allocation — skip the geometric
    search entirely (mapping-as-a-service for the mesh builder).
    """
    candidates = [identity_mapping(graph, alloc)]
    for scaled in (False, True):
        tc = graph.coords.astype(float)
        if scaled:
            tc = tc / np.asarray(axis_bytes, dtype=float)
        for rot in (0, rotations):
            config = PipelineConfig(
                sfc=sfc, shift=True, bandwidth_scale=True, rotations=rot,
                score_backend=score_backend,
                partition_backend=partition_backend, hierarchy=hierarchy)
            if service is not None:
                from repro.serve.engine import MappingRequest
                resp = service.map(MappingRequest(graph, alloc, config,
                                                  task_coords=tc))
                candidates.append(resp.result)
            else:
                candidates.append(shared_pipeline(config).map(
                    graph, alloc, task_coords=tc))
    search = CandidateSearch(objective=("latency_max", "weighted_hops"),
                             backend=score_backend)
    best, _, _ = search.best(graph, alloc, candidates)
    best_metrics = evaluate(graph, alloc, best)
    base_metrics = evaluate(graph, alloc, candidates[0])
    return best, best_metrics, base_metrics
