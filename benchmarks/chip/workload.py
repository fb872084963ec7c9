"""Cells, deployments and request generation of the chip benchmark.

Everything a run maps is made here from ``--seed``, by the benchmark's
own code: a later change to the program's graph, machine or allocation
constructors cannot change what the benchmark asks of it.  A cell is
found by name in ``BENCHMARK.json``; its deployment (job, machine,
allocation) is the JSON file that the configuration names, and its
traffic mix is ``mixes/<traffic>.json``.  Builders are chosen by the
``kind`` fields of those files, so a new deployment or mix of a known
kind is a data file and nothing else.

Every request of a run maps the deployment's job onto a fresh
allocation of the same size: the job's graph is built once, and request
``i`` draws its allocation from ``(seed, i)``.  Shapes never change
between requests, signatures always do, so every request is served
cold and none compiles.
"""

from __future__ import annotations

import dataclasses
import itertools
import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
REL = os.path.relpath(HERE, ROOT)  # this directory, from the checkout


# ---------------------------------------------------------------------------
# Cells
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Cell:
    """One entry of ``BENCHMARK.json``'s ``workloads``, resolved."""

    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: tuple
    per_layer: tuple


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def resolve_cell(bench: dict, name: str, root: str = ROOT) -> Cell:
    """The cell ``name`` with its configuration and mix files loaded,
    and the metrics that apply to it."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = _load_json(os.path.join(root, cfg["file"]))
    mix = _load_json(os.path.join(root, REL, "mixes",
                                  w["traffic"] + ".json"))

    def applies(m):
        return "workloads" not in m or name in m["workloads"]

    return Cell(name, int(w["chips"]), config, mix,
                tuple(m for m in bench["end_to_end"] if applies(m)),
                tuple(m for m in bench["per_layer"] if applies(m)))


# ---------------------------------------------------------------------------
# Seeds
# ---------------------------------------------------------------------------

def request_rng(seed: int, index: int, tag: str) -> np.random.Generator:
    """The generator of request ``index`` of a run seeded ``seed``.
    Any whole seed is accepted (reduced mod 2**64)."""
    tag_key = int.from_bytes(tag.encode()[:8].ljust(8, b"\0"), "little")
    return np.random.default_rng(
        [int(seed) % (1 << 64), int(index) % (1 << 64), tag_key])


# ---------------------------------------------------------------------------
# Hilbert order (Skilling's transpose algorithm)
# ---------------------------------------------------------------------------

def hilbert_index(points: np.ndarray, bits: int) -> np.ndarray:
    """Hilbert index of non-negative integer points ``(n, d)`` on a
    ``2**bits``-per-side grid."""
    x = np.asarray(points, dtype=np.int64).copy()
    n, d = x.shape
    if d == 1:
        return x[:, 0].copy()
    q = np.int64(1) << (bits - 1)
    while q > 1:
        p = q - 1
        for i in range(d):
            has = (x[:, i] & q) != 0
            x[:, 0] = np.where(has, x[:, 0] ^ p, x[:, 0])
            t = np.where(has, 0, (x[:, 0] ^ x[:, i]) & p)
            x[:, 0] ^= t
            x[:, i] ^= t
        q >>= 1
    for i in range(1, d):
        x[:, i] ^= x[:, i - 1]
    t = np.zeros(n, dtype=np.int64)
    q = np.int64(1) << (bits - 1)
    while q > 1:
        t = np.where((x[:, d - 1] & q) != 0, t ^ (q - 1), t)
        q >>= 1
    x ^= t[:, None]
    out = np.zeros(n, dtype=np.int64)
    for i in range(d):
        for b in range(bits):
            out |= ((x[:, i] >> b) & 1) << (b * d + (d - 1 - i))
    return out


# ---------------------------------------------------------------------------
# Jobs: task graphs as plain arrays
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Job:
    coords: np.ndarray   # (n, d) float64 task coordinates
    edges: np.ndarray    # (E, 2) int64 directed task pairs
    weights: np.ndarray  # (E,) float64 message volumes

    @property
    def n(self) -> int:
        return len(self.coords)


def _both_ways(src, dst):
    src, dst = np.concatenate(src), np.concatenate(dst)
    return np.stack([np.concatenate([src, dst]),
                     np.concatenate([dst, src])], axis=1)


def job_stencil3d(spec: dict) -> Job:
    """MiniGhost: a 3D grid of ranks, each exchanging halos with its
    +-1 neighbours along every dimension (both directions)."""
    dims = tuple(int(x) for x in spec["grid"])
    idx = np.arange(int(np.prod(dims))).reshape(dims)
    src, dst = [], []
    for k in range(len(dims)):
        a = np.moveaxis(idx, k, 0)
        src.append(a[:-1].ravel())
        dst.append(a[1:].ravel())
        if spec.get("periodic") and dims[k] > 2:
            src.append(a[-1:].ravel())
            dst.append(a[:1].ravel())
    edges = _both_ways(src, dst)
    coords = np.stack(np.unravel_index(np.arange(idx.size), dims),
                      axis=1).astype(np.float64)
    return Job(coords, edges,
               np.full(len(edges), float(spec.get("volume", 1.0))))


def _cell_id(f, i, j, ne):
    return f * ne * ne + np.asarray(i) * ne + np.asarray(j)


def job_cube_sphere(spec: dict) -> Job:
    """HOMME: the cubed-sphere element mesh, ``6 * ne**2`` elements,
    one rank each, exchanging with its four edge neighbours (across
    cube edges too); coordinates are element centres on the unit
    sphere (gnomonic equal-angle-free grid: centres of a uniform
    ``ne x ne`` grid on each cube face, projected)."""
    ne = int(spec["ne"])
    grid = np.arange(6 * ne * ne).reshape(6, ne, ne)
    src, dst = [], []
    for axis in (1, 2):
        a = np.moveaxis(grid, axis, 1)
        src.append(a[:, :-1].ravel())
        dst.append(a[:, 1:].ravel())
    r = np.arange(ne)
    rr = ne - 1 - r
    last = ne - 1
    for f in range(4):  # the equatorial ring of faces 0..3
        src.append(_cell_id(f, last, r, ne))
        dst.append(_cell_id((f + 1) % 4, 0, r, ne))
    # face 4 (+z) joins the top edges, face 5 (-z) the bottom edges
    for (fa, ia, ja), (fb, ib, jb) in (
            ((4, r, 0), (0, r, last)), ((4, r, last), (2, rr, last)),
            ((4, 0, r), (3, rr, last)), ((4, last, r), (1, r, last)),
            ((5, r, 0), (2, rr, 0)), ((5, r, last), (0, r, 0)),
            ((5, 0, r), (3, r, 0)), ((5, last, r), (1, rr, 0))):
        src.append(_cell_id(fa, ia, ja, ne))
        dst.append(_cell_id(fb, ib, jb, ne))
    edges = _both_ways(src, dst)
    u = (np.arange(ne) + 0.5) / ne * 2.0 - 1.0
    uu, vv = np.meshgrid(u, u, indexing="ij")
    u, v = uu.ravel(), vv.ravel()
    one = np.ones_like(u)
    faces = [np.stack(f, axis=1) for f in (
        (one, u, v), (-u, one, v), (-one, -u, v), (u, -one, v),
        (-v, u, one), (v, u, -one))]
    pts = np.concatenate(faces)
    coords = pts / np.linalg.norm(pts, axis=1, keepdims=True)
    return Job(coords, edges,
               np.full(len(edges), float(spec.get("volume", 1.0))))


JOBS = {"stencil3d": job_stencil3d, "cube_sphere": job_cube_sphere}


# ---------------------------------------------------------------------------
# Machines and allocations
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class MachineSpec:
    """A torus of routers with a trailing, free, core dimension: the
    cores of every node that shares a router."""

    router_dims: tuple
    wrap: tuple
    link_bw: tuple        # per router dim, the bandwidth pattern (GB/s)
    cores_per_router: int
    name: str

    @property
    def dims(self) -> tuple:
        return self.router_dims + (self.cores_per_router,)

    @property
    def nrouters(self) -> int:
        return int(np.prod(self.router_dims))

    def bw(self, k: int, index: np.ndarray) -> np.ndarray:
        pat = np.asarray(self.link_bw[k], dtype=np.float64)
        return pat[np.asarray(index) % len(pat)]


def machine_spec(spec: dict) -> MachineSpec:
    dims = tuple(int(x) for x in spec["router_dims"])
    wrap = tuple(bool(x) for x in spec["wrap"])
    bw = tuple(tuple(float(b) for b in p) for p in spec["link_bw"])
    if not len(dims) == len(wrap) == len(bw):
        raise ValueError("router_dims, wrap and link_bw differ in length")
    cores = int(spec["nodes_per_router"]) * int(spec["cores_per_node"])
    return MachineSpec(dims, wrap, bw, cores, spec["label"])


def _with_cores(machine: MachineSpec, routers: np.ndarray,
                ncores: int) -> np.ndarray:
    """Core rows of the chosen routers, in router order, every core of
    a router in turn, trimmed to ``ncores`` rows."""
    c = machine.cores_per_router
    rows = np.concatenate(
        [np.repeat(routers, c, axis=0),
         np.tile(np.arange(c)[:, None], (len(routers), 1))], axis=1)
    return rows[:ncores]


def alloc_sfc_fragments(machine: MachineSpec, spec: dict, seed: int,
                        index: int) -> np.ndarray:
    """An ALPS-style sparse allocation: the routers in Hilbert order,
    ``fragments`` runs of that order at random free offsets (other jobs
    hold the gaps), every core of each chosen router."""
    rng = request_rng(seed, index, "alloc")
    ncores = int(spec["cores"])
    nfrag = int(spec["fragments"])
    rd = machine.router_dims
    pts = np.stack([g.ravel() for g in np.indices(rd)], axis=1)
    bits = max(1, int(np.ceil(np.log2(max(max(rd), 2)))))
    order = np.argsort(hilbert_index(pts, bits), kind="stable")
    total = len(pts)
    nrouters = -(-ncores // machine.cores_per_router)
    if nrouters > total:
        raise ValueError("allocation larger than the machine")
    sizes = np.full(nfrag, nrouters // nfrag)
    sizes[: nrouters % nfrag] += 1
    occupied = np.zeros(total, dtype=bool)
    runs = []
    for sz in sizes:
        if sz == 0:
            continue
        for _ in range(64):
            s = int(rng.integers(0, total - sz + 1))
            if not occupied[s:s + sz].any():
                break
        else:  # the first free window that holds the fragment
            free = np.flatnonzero(np.convolve(
                ~occupied, np.ones(sz, dtype=int), "valid") == sz)
            if not len(free):
                raise ValueError("no free window for a fragment")
            s = int(free[0])
        occupied[s:s + sz] = True
        runs.append(order[s:s + sz])
    return _with_cores(machine, pts[np.concatenate(runs)], ncores)


def alloc_runjob_block(machine: MachineSpec, spec: dict, seed: int,
                       index: int) -> np.ndarray:
    """A job of ``cores`` ranks in a BG/Q block, placed as ``runjob
    --ranks-per-node <cores per node> --mapping <order>T`` places it:
    the ranks fill the block's nodes in the order of one permutation of
    the torus dimensions (the last fastest), every core of a node in
    turn, so the job holds the first nodes of that order.  The machine
    is the block itself: a BG/Q block is wired apart from the rest of
    the machine, so where it sits does not change the job's network.
    Each request takes another order; the orders of a run are a
    permutation of all of them drawn from the seed.  The job fills more
    of the block than any one slab across a dimension holds, so every
    dimension moves within it, each order gives another allocation, and
    no two requests of a run (up to that many) share one."""
    ncores = int(spec["cores"])
    nrouters = -(-ncores // machine.cores_per_router)
    if nrouters > machine.nrouters:
        raise ValueError("allocation larger than the machine")
    if nrouters * min(machine.router_dims) <= machine.nrouters:
        # some dimension never moves, and orders that differ only in
        # where it stands would give one allocation twice
        raise ValueError("the job fills too little of the block for its "
                         "orders to differ")
    nd = len(machine.router_dims)
    orders = list(itertools.permutations(range(nd)))
    pick = request_rng(seed, 0, "mapping").permutation(len(orders))
    order = orders[int(pick[index % len(orders)])]  # slowest first
    shape = tuple(machine.router_dims[k] for k in order)
    ids = np.arange(nrouters)
    routers = np.empty((nrouters, nd), dtype=np.int64)
    routers[:, list(order)] = np.stack(np.unravel_index(ids, shape), axis=1)
    return _with_cores(machine, routers, ncores)


ALLOCATIONS = {"sfc_fragments": alloc_sfc_fragments,
               "runjob_block": alloc_runjob_block}


# ---------------------------------------------------------------------------
# Deployments
# ---------------------------------------------------------------------------

class Deployment:
    """A configuration file made concrete: the job's graph (built once)
    and the allocation of any request, drawn from ``(seed, index)``."""

    def __init__(self, config: dict):
        self.config = config
        self.job = JOBS[config["job"]["kind"]](config["job"])
        self.machine = machine_spec(config["machine"])
        self._alloc = ALLOCATIONS[config["allocation"]["kind"]]
        if int(config["allocation"]["cores"]) != self.job.n:
            raise ValueError(
                f"{config['name']}: the allocation holds "
                f"{config['allocation']['cores']} cores, the job "
                f"{self.job.n} ranks")

    def allocation(self, seed: int, index: int) -> np.ndarray:
        """(ncores, router dims + 1) int64 core rows of request
        ``index``'s allocation."""
        return self._alloc(self.machine, self.config["allocation"], seed,
                           index)
