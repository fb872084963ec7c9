"""The plain reference mapper that decides ``correct``.

A straightforward implementation, in numpy and nothing of the program,
of what a mapping request asks for (arXiv:1804.09798, Alg. 1 and 2,
with the rotation search of its section 4.3):

- machine side: router coordinates, with the mix's ``shift`` each
  torus dimension cut at its largest gap between occupied coordinates;
- Multi-Jagged bisection with FZ part numbering (the right-hand half of
  every cut is mirrored along the cut dimension), cutting the longest
  dimension, ties to the rotation's priority, weights balanced by a
  sequential prefix sum per part, points of equal coordinate kept in
  the order the previous cuts left them;
- task part ``k`` goes to the core of part ``k``, for each rotation of
  the balanced factorial subsample of ``td! x pd!``; the rotation whose
  objective columns are lexicographically smallest wins, the earliest
  on ties;
- objectives: weighted hops (torus-shortest hop counts on the router
  dimensions, cores free), and the latency of dimension-ordered
  routing (each message walks dimension 0 first, the shorter way round
  a torus, forward on a tie; a link's latency is its load over its
  bandwidth; links are indexed by the full machine coordinate of the
  position a message is at, its core included);
- the ``node`` hierarchy: tasks cut into one cluster per allocated
  router, the sweep run on clusters (weighted centroids, summed
  volumes) against routers, then bounded greedy swap rounds over the
  hottest clusters and their network-nearest routers, then each
  cluster's tasks dealt onto its router's cores in Hilbert order.

The partition runs level by level instead of recursing part by part:
one stable sort per level keyed by (part, coordinate) keeps exactly the
order a per-part stable sort would.  ``ftype`` is the float type of
coordinates, weights and cut arithmetic, ``stype`` that of the score
sums and link loads; the benchmark's control runs the same code in
float32, one step below the float64 the configurations state.
"""

from __future__ import annotations

import itertools

import numpy as np

from workload import hilbert_index

F64 = np.float64

_OBJECTIVES = {"wh": ("weighted_hops",),
               "latency": ("latency_max", "weighted_hops")}


def objective_keys(name: str) -> tuple:
    return _OBJECTIVES[name]


# ---------------------------------------------------------------------------
# Machine side
# ---------------------------------------------------------------------------

def shift_torus(coords: np.ndarray, dims, wrap) -> np.ndarray:
    """Rotate each torus dimension so the largest gap between occupied
    coordinates lies across the wrap-around."""
    out = np.array(coords, dtype=F64)
    for k, s in enumerate(dims):
        if not wrap[k]:
            continue
        occ = np.unique(out[:, k].astype(np.int64))
        if len(occ) <= 1:
            continue
        gaps = np.diff(np.concatenate([occ, occ[:1] + s]))
        g = int(np.argmax(gaps))
        if gaps[g] <= 1:
            continue
        origin = (occ[g] + gaps[g]) % s
        out[:, k] = (out[:, k] - origin) % s
    return out


def rotations(td: int, pd: int, budget: int) -> list:
    """(task_perm, proc_perm) pairs: all of ``td! x pd!`` if they fit the
    budget, else the smallest, most balanced ``na x nb`` grid of evenly
    spaced permutations of each side that covers it."""
    ta = list(itertools.permutations(range(td)))
    pa = list(itertools.permutations(range(pd)))
    if not budget:
        return [(tuple(range(td)), tuple(range(pd)))]
    if len(ta) * len(pa) <= budget:
        return [(a, b) for a in ta for b in pa]
    best = None
    for na in range(1, len(ta) + 1):
        nb = min(-(-budget // na), len(pa))
        if na * nb < budget:
            continue
        key = (na + nb, abs(na - nb))
        if best is None or key < best[0]:
            best = (key, na, nb)
    _, na, nb = best
    sa = [ta[i] for i in np.linspace(0, len(ta) - 1, na).astype(int)]
    sb = [pa[i] for i in np.linspace(0, len(pa) - 1, nb).astype(int)]
    return [(a, b) for a in sa for b in sb][:budget]


# ---------------------------------------------------------------------------
# Multi-Jagged bisection
# ---------------------------------------------------------------------------

def mj_parts(coords, nparts: int, *, weights=None, dim_order=None,
             sfc: str = "FZ", ftype=F64) -> np.ndarray:
    """Part number of every point, ``nparts`` balanced parts."""
    x = np.array(coords, dtype=ftype)
    n, d = x.shape
    w = (np.ones(n, dtype=ftype) if weights is None
         else np.asarray(weights, dtype=ftype))
    pri = np.arange(d) if dim_order is None else np.asarray(dim_order)
    mu = np.zeros(n, dtype=np.int64)
    order = np.arange(n)
    # segments of ``order``: start, length, parts still to cut
    st = np.array([0])
    ln = np.array([n])
    npt = np.array([int(nparts)])
    while True:
        live = (npt > 1) & (ln > 1)
        st, ln, npt = st[live], ln[live], npt[live]
        if not len(st):
            return mu
        nseg = len(st)
        seg = np.repeat(np.arange(nseg), ln)
        offs = np.cumsum(ln) - ln
        rank = np.arange(len(seg)) - offs[seg]
        pos = st[seg] + rank
        pts = order[pos]
        xs = x[pts]
        ext = (np.maximum.reduceat(xs, offs, axis=0)
               - np.minimum.reduceat(xs, offs, axis=0))
        cut = np.full(nseg, pri[0])
        for dd in pri[1:]:
            better = ext[:, dd] > ext[np.arange(nseg), cut] + 1e-12
            cut[better] = dd
        perm = np.lexsort((x[pts, cut[seg]], seg))
        pts = pts[perm]
        order[pos] = pts
        # per-part sequential prefix sums of the weights
        pad = np.zeros((nseg, int(ln.max())), dtype=ftype)
        pad[seg, rank] = w[pts]
        cw = np.cumsum(pad, axis=1)
        total = cw[np.arange(nseg), ln - 1]
        npl = npt // 2
        npr = npt - npl
        target = total * (npl / npt).astype(ftype)
        below = cw[seg, rank] < target[seg]
        k = np.bincount(seg, weights=below, minlength=nseg).astype(
            np.int64) + 1
        k = np.minimum(np.maximum(k, 1), ln - 1)
        right = rank >= k[seg]
        rp = pts[right]
        rc = cut[seg[right]]
        if sfc == "FZ":
            x[rp, rc] = -x[rp, rc]
        elif sfc == "FZlow":
            lp = pts[~right]
            lc = cut[seg[~right]]
            x[lp, lc] = -x[lp, lc]
        else:
            raise ValueError(f"unsupported ordering {sfc!r}")
        mu[rp] += npl[seg[right]]
        st = np.concatenate([st, st + k])
        ln = np.concatenate([k, ln - k])
        npt = np.concatenate([npl, npr])


def sfc_pair(sfc: str, td: int, pd: int) -> tuple:
    """FZ on both sides, or MFZ (the task side mirrors its low half)
    when the machine's dimensionality is a multiple of the job's."""
    if sfc == "FZ" and pd != td and pd % max(td, 1) == 0:
        return "FZlow", "FZ"
    return sfc, sfc


# ---------------------------------------------------------------------------
# Objectives
# ---------------------------------------------------------------------------

def hops(machine, a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Torus-shortest hop count over the router dimensions."""
    tot = np.zeros(np.broadcast_shapes(a.shape[:-1], b.shape[:-1]),
                   dtype=np.int64)
    for k, s in enumerate(machine.router_dims):
        dk = np.abs(a[..., k].astype(np.int64) - b[..., k].astype(np.int64))
        if machine.wrap[k]:
            dk = np.minimum(dk, s - dk)
        tot += dk
    return tot


def link_loads(machine, src: np.ndarray, dst: np.ndarray, w, stype):
    """Per router dimension, the (+, -) load arrays over the full
    machine shape under dimension-ordered routing, each message adding
    its volume to every link it crosses."""
    dims = machine.dims
    nd = len(machine.router_dims)
    cur = np.array(src, dtype=np.int64)
    dst = np.asarray(dst, dtype=np.int64)
    w = np.asarray(w).astype(stype)
    loads = []
    for k in range(nd):
        s = dims[k]
        a, b = cur[:, k], dst[:, k]
        if machine.wrap[k]:
            fwd, bwd = (b - a) % s, (a - b) % s
            use_fwd = fwd <= bwd
        else:
            fwd, bwd = np.maximum(b - a, 0), np.maximum(a - b, 0)
            use_fwd = b >= a
        length = np.where(use_fwd, fwd, bwd)
        msg = np.repeat(np.arange(len(a)), length)
        step = np.arange(len(msg)) - np.repeat(np.cumsum(length) - length,
                                               length)
        pos_l = np.zeros(int(np.prod(dims)), dtype=stype)
        neg_l = np.zeros(int(np.prod(dims)), dtype=stype)
        fw = use_fwd[msg]
        coord = cur[msg].copy()
        coord[:, k] = np.where(fw, a[msg] + step, a[msg] - 1 - step) % s
        flat = np.ravel_multi_index(tuple(coord.T), dims)
        np.add.at(pos_l, flat[fw], w[msg[fw]])
        np.add.at(neg_l, flat[~fw], w[msg[~fw]])
        loads.append((pos_l.reshape(dims), neg_l.reshape(dims)))
        cur[:, k] = b
    return loads


def score(machine, edges, w, task_coords: np.ndarray, keys,
          stype=F64) -> np.ndarray:
    """Objective columns of one mapping (``task_coords``: the machine
    coordinate of every task)."""
    if task_coords.shape[1] < len(machine.dims):  # routers: core 0
        task_coords = np.concatenate([task_coords, np.zeros(
            (len(task_coords), len(machine.dims) - task_coords.shape[1]),
            dtype=task_coords.dtype)], axis=1)
    src = task_coords[edges[:, 0]]
    dst = task_coords[edges[:, 1]]
    h = hops(machine, src, dst)
    out = {"weighted_hops": np.sum(h.astype(stype) * np.asarray(
        w).astype(stype), dtype=stype)}
    if any(k in ("latency_max", "data_max") for k in keys):
        data = lat = 0.0
        for k, (pos_l, neg_l) in enumerate(
                link_loads(machine, src, dst, w, stype)):
            bw = machine.bw(k, np.arange(machine.dims[k]))
            shape = [1] * len(machine.dims)
            shape[k] = machine.dims[k]
            bw = bw.reshape(shape).astype(stype)
            for arr in (pos_l, neg_l):
                data = max(data, float(arr.max()))
                lat = max(lat, float((arr / bw).max()))
        out["data_max"], out["latency_max"] = data, lat
    return np.array([float(out[k]) for k in keys])


# ---------------------------------------------------------------------------
# The flat sweep
# ---------------------------------------------------------------------------

def sweep(machine, task_coords, task_weights, unit_coords, edges, w,
          keys, mix, ftype=F64, stype=F64):
    """Every rotation mapped and scored; returns (task -> unit, objective
    columns of the winner, the winning rotation, all columns).
    ``unit_coords`` are machine coordinates (router dimensions first)."""
    nd = len(machine.router_dims)
    pc = np.asarray(unit_coords[:, :nd], dtype=F64)
    if mix["shift"]:
        pc = shift_torus(pc, machine.router_dims, machine.wrap)
    tc = np.asarray(task_coords, dtype=F64)
    (tnum, td), (pnum, pd) = tc.shape, pc.shape
    if tnum != pnum:
        raise ValueError("the reference maps one task per unit")
    tsfc, psfc = sfc_pair(mix["sfc"], td, pd)
    cands = rotations(td, pd, int(mix["rotations"]))
    mu_t = {p: mj_parts(tc, tnum, weights=task_weights, dim_order=p,
                        sfc=tsfc, ftype=ftype)
            for p in sorted({c[0] for c in cands})}
    mu_p = {p: mj_parts(pc, pnum, dim_order=p, sfc=psfc, ftype=ftype)
            for p in sorted({c[1] for c in cands})}
    results, cols = [], []
    for tp, pp in cands:
        part_to_unit = np.empty(pnum, dtype=np.int64)
        part_to_unit[mu_p[pp]] = np.arange(pnum)
        t2u = part_to_unit[mu_t[tp]]
        results.append(t2u)
        cols.append(score(machine, edges, w, unit_coords[t2u], keys,
                          stype))
    cols = np.array(cols)
    best = int(np.lexsort(tuple(cols[:, j] for j in
                                reversed(range(cols.shape[1]))))[0])
    return results[best], cols[best], cands[best], cols


def map_flat(dep, alloc: np.ndarray, mix: dict, ftype=F64, stype=F64):
    job, machine = dep.job, dep.machine
    keys = objective_keys(mix["objective"])
    t2p, cols, rotation, _ = sweep(machine, job.coords, None, alloc,
                                   job.edges, job.weights, keys, mix,
                                   ftype, stype)
    return {"task_to_core": t2p, "objective": cols, "rotation": rotation}


# ---------------------------------------------------------------------------
# The node hierarchy
# ---------------------------------------------------------------------------

def _lex_less(a, b, tol=1e-12) -> bool:
    for x, y in zip(a, b):
        if x < y - tol:
            return True
        if x > y + tol:
            return False
    return False


def refine_swaps(machine, edges, w, router_coords, c2r, keys, rounds,
                 top, degree, stype=F64):
    """Bounded greedy swap rounds; returns the refined cluster -> router
    map, the objective history and the swaps accepted."""
    nclusters, nrouters = len(c2r), len(router_coords)
    c2r = c2r.copy()
    r2c = np.full(nrouters, -1, dtype=np.int64)
    r2c[c2r] = np.arange(nclusters)

    def full(c):
        return score(machine, edges, w, router_coords[c], keys, stype)

    base = full(c2r)
    history = [base]
    accepted = evaluated = 0
    for _ in range(rounds):
        cc = router_coords[c2r]
        h = hops(machine, cc[edges[:, 0]], cc[edges[:, 1]]) * w
        contrib = (np.bincount(edges[:, 0], weights=h, minlength=nclusters)
                   + np.bincount(edges[:, 1], weights=h,
                                 minlength=nclusters))
        hot = np.argsort(-contrib, kind="stable")[:top]
        hot = hot[contrib[hot] > 0]
        k = min(degree, nrouters - 1)
        if not len(hot) or k <= 0:
            break
        dist = hops(machine, cc[hot][:, None, :],
                    router_coords[None, :, :]).astype(F64)
        dist[np.arange(len(hot)), c2r[hot]] = np.inf
        near = np.argsort(dist, axis=1, kind="stable")[:, :k]
        seen, props = set(), []
        for i, a in enumerate(hot):
            ra = int(c2r[a])
            for rb in near[i]:
                rb = int(rb)
                key = (min(ra, rb), max(ra, rb))
                if key not in seen:
                    seen.add(key)
                    props.append((int(a), ra, int(r2c[rb]), rb))
        if not props:
            break
        evaluated += len(props)

        def apply(sel):
            nc, nr = c2r.copy(), r2c.copy()
            for i in sel:
                a, ra, b, rb = props[i]
                nc[a], nr[rb], nr[ra] = rb, a, b
                if b >= 0:
                    nc[b] = ra
            return nc, nr

        scores = np.array([full(apply([i])[0]) for i in range(len(props))])
        order = np.lexsort(tuple(scores[:, j] for j in
                                 reversed(range(scores.shape[1]))))
        touched, chosen = set(), []
        for i in order:
            if not _lex_less(scores[i], base):
                break
            _, ra, _, rb = props[i]
            if ra in touched or rb in touched:
                continue
            touched |= {ra, rb}
            chosen.append(int(i))
        if not chosen:
            break
        new_c2r, new_r2c = apply(chosen)
        combined = full(new_c2r)
        if len(chosen) > 1 and not _lex_less(combined, base):
            chosen = [int(order[0])]
            new_c2r, new_r2c = apply(chosen)
            combined = scores[chosen[0]]
        if not _lex_less(combined, base):
            break
        c2r, r2c, base = new_c2r, new_r2c, combined
        history.append(base)
        accepted += len(chosen)
    return c2r, history, accepted, evaluated


def hilbert_key(coords: np.ndarray) -> np.ndarray:
    """Hilbert index of float points quantised onto a grid of
    ``ceil(log2 n / d) + 2`` bits a side (at most 62 bits in all)."""
    n, d = coords.shape
    bits = max(1, min(62 // d, int(np.ceil(np.log2(max(n, 2)) / d)) + 2))
    side = 1 << bits
    lo = coords.min(axis=0)
    span = coords.max(axis=0) - lo
    span = np.where(span > 0, span, 1.0)
    q = np.clip(((coords - lo) / span * (side - 1)).round().astype(
        np.int64), 0, side - 1)
    return hilbert_index(q, bits)


def map_node(dep, alloc: np.ndarray, mix: dict, ftype=F64, stype=F64):
    job, machine = dep.job, dep.machine
    keys = objective_keys(mix["objective"])
    nd = len(machine.router_dims)
    tc = np.asarray(job.coords, dtype=F64)
    rkeys = np.ravel_multi_index(tuple(alloc[:, :nd].T), machine.router_dims)
    ukeys, core_router = np.unique(rkeys, return_inverse=True)
    routers = np.stack(np.unravel_index(ukeys, machine.router_dims), axis=1)
    nr = len(routers)
    # clusters: one per router, balanced MJ parts of the task coordinates
    labels = mj_parts(tc, nr, sfc=mix["sfc"], ftype=ftype)
    size = np.bincount(labels, minlength=nr).astype(F64)
    cents = np.stack([np.bincount(labels, weights=tc[:, j], minlength=nr)
                      for j in range(tc.shape[1])], axis=1) / size[:, None]
    ce = labels[job.edges]
    inter = ce[:, 0] != ce[:, 1]
    pair = ce[inter, 0] * nr + ce[inter, 1]
    upair, inv = np.unique(pair, return_inverse=True)
    vol = np.bincount(inv, weights=job.weights[inter], minlength=len(upair))
    cedges = np.stack([upair // nr, upair % nr], axis=1)
    c2r, _, rotation, _ = sweep(machine, cents, size, routers, cedges,
                                vol, keys, mix, ftype, stype)
    c2r, history, accepted, evaluated = refine_swaps(
        machine, cedges, vol, routers, c2r, keys,
        int(mix["refine_rounds"]), int(mix["refine_top"]),
        int(mix["refine_degree"]), stype)
    # each cluster's tasks onto its router's cores, in Hilbert order
    r_task = c2r[labels]
    order = np.lexsort((hilbert_key(tc), labels, r_task))
    per_router = np.bincount(r_task, minlength=nr)
    cores = np.bincount(core_router, minlength=nr)
    if not np.array_equal(per_router, cores):
        raise ValueError("the reference deals whole clusters onto routers "
                         "with as many cores")
    first = np.cumsum(cores) - cores
    r = r_task[order]
    rank = np.arange(len(order)) - first[r]
    core_order = np.argsort(core_router, kind="stable")
    t2p = np.empty(len(order), dtype=np.int64)
    t2p[order] = core_order[first[r] + rank]
    return {"task_to_core": t2p, "objective": history[-1],
            "rotation": rotation, "history": [list(h) for h in history],
            "accepted": accepted, "evaluated": evaluated}


def reference_map(dep, alloc: np.ndarray, mix: dict, ftype=F64,
                  stype=F64) -> dict:
    """The reference answer for one request."""
    fn = {"flat": map_flat, "node": map_node}[mix["hierarchy"]]
    return fn(dep, alloc, mix, ftype, stype)
