"""JAX backend for the level-synchronous Multi-Jagged partitioner.

Implements the ``vectorized_order`` / ``vectorized_order_batched``
contract on device: the recursion-level loop of paper Algorithm 2
runs as a ``lax.while_loop`` over fixed-shape state, with

- the per-level *segmented stable partition* expressed as one narrow
  ``lax.sort`` per candidate block over int32 ``(segment, coordinate
  key, position)`` keys, whose permutation then gathers the point ids,
- segment extents (longest-dimension selection) via
  ``jax.ops.segment_max/min`` of the coordinate keys, keyed by segment
  start,
- cut placement from a host-built table of the unit-weight recursion
  (or a sequential per-segment weight prefix scan, ``lax.scan``), and
- the SFC coordinate flips as per-segment sign bits.

The representation is *positional*: instead of a growing segment table,
every one of the ``nb_b * npts_b`` padded point slots carries its
segment's ``(start, size, nparts)`` and flip bits — constant within a
segment, so the within-segment sorts never have to move them and the
whole state is fixed-shape arrays (what ``while_loop`` requires).
Candidate ``b`` of a batched sweep owns slot block
``[b*npts_b, (b+1)*npts_b)``; padding tails are closed single-part
segments that the sweep never activates, so bucketing changes no result
bit.

Everything per dimension is a struct of arrays: d separate slot
vectors each for the extents, the flip bits and the candidates'
priority rows, and the ranks at the current point order are the d rows
of one dimension-major ``(d, N)`` gather; no array has the dimension as
its minor axis.  Picking a slot's value at its cut dimension (or at a
priority) is an unrolled select chain over the d vectors, elementwise
work where a gather across a minor axis of size d would pad it to the
TPU's 128 lanes.

Bit-identity with the numpy engines (the ``np.lexsort`` tie order of
``partition._exact_order`` is the oracle) holds on a TPU too, where
float64 is emulated with pairs of float32 and does not round like IEEE:

- coordinates enter as exact int32 sort keys, one row per dimension: a
  *lattice* dimension (every value an integer, spanning less than
  ``2**31``) is keyed by its value less its minimum, any other by its
  dense rank (``np.unique`` on the host).  Either way equal values tie,
  a flip negates the key, and ``-0.0`` ties with ``0.0`` as in numpy;
- a lattice dimension's longest-dimension extent is the difference of
  its keys, the oracle's IEEE ``max - min`` exactly, with no lookup
  into the value table, and integer extents need no tolerance;
- unit-weight cuts depend only on ``(segment size, part count)``, so the
  host runs the oracle's own ``_uniform_cuts`` arithmetic over the
  recursion once and ships the resulting cut table;
- the Hilbert grid is quantised on the host by the oracle's quantiser.

What stays in float64 on the device is the extent of a ranked
dimension (compared with the oracle's ``1e-12`` tolerance, far above
the error of the emulation) and the weighted cut arithmetic (a
sequential ``lax.scan``, exact on integer weights); on lattice input
the unweighted loop body holds no float64 at all.

Shape bucketing + the keyed compile cache mirror
:mod:`repro.core.metrics_jax`: point counts pad to power-of-two buckets
(``PART_BUCKET_MIN`` floor) with the real count passed as a *traced*
scalar, the ``uneven_prime`` split table and the cut table ship as
padded traced arrays, and :func:`partition_cache_stats` exposes the
truthful compile-count counters benchmarks assert on.

This module imports jax at module level — callers go through
``repro.core.orderings`` (``backend="jax"`` / the
``resolve_partition_backend`` chain), which resolves to the numpy
engine only when jax cannot be imported.
"""

from __future__ import annotations

import functools

import numpy as np

import jax

# The extents and weighted prefix sums are float64 in the oracle, and
# f32 rounds them differently, so this backend requires x64.
# Process-wide but safe: the score backends pin f32 and int32
# explicitly on every array they build.
jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp  # noqa: E402
from jax import lax  # noqa: E402

from repro import obs  # noqa: E402

from .metrics_jax import bucket_size, pad_axis  # noqa: E402
from .orderings import (_hilbert_quantise, _split_counts,  # noqa: E402
                        hilbert_bits)
from .partition import _uniform_cuts  # noqa: E402

__all__ = ["order_points_jax", "order_points_batched_jax",
           "partition_cache_stats", "reset_partition_cache"]

PART_BUCKET_MIN = 256  # smallest padded point-count bucket
TAB_MIN = 16           # smallest padded split-/cut-table bucket

_I32 = jnp.int32
_F64 = jnp.float64


# ---------------------------------------------------------------------------
# split-count table (host side, shipped as a traced array)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=64)
def _split_table(nparts: int, uneven_prime: bool) -> np.ndarray:
    """``tab[v] = npl`` of splitting ``v`` parts, for every v <= nparts
    (0 for v < 2).  Matches ``orderings._split_counts`` exactly; for
    ``uneven_prime`` a smallest-slice sieve supplies largest prime
    factors so Table-scale part counts stay cheap to tabulate."""
    m = int(nparts)
    tab = np.zeros(m + 1, dtype=np.int32)
    if m < 2:
        return tab
    v = np.arange(2, m + 1, dtype=np.int64)
    if not uneven_prime:
        tab[2:] = (v // 2).astype(np.int32)
        return tab
    lpf = np.zeros(m + 1, dtype=np.int64)
    for p in range(2, m + 1):
        if lpf[p] == 0:  # p is prime: overwrite multiples ascending
            lpf[p::p] = p
    p = lpf[2:]
    k = p // 2
    npl = np.where(p <= 2, v // 2, (k * v) // p)
    # cross-check the reference on a few rows (cheap, catches drift)
    for probe in {2, 3, m, _largest(m)} & set(range(2, m + 1)):
        assert int(npl[probe - 2]) == _split_counts(probe, True)[0]
    tab[2:] = npl.astype(np.int32)
    return tab


def _largest(m: int) -> int:
    return max(2, m - 1)


@functools.lru_cache(maxsize=64)
def _cut_table(n: int, nparts: int, uneven_prime: bool) -> np.ndarray:
    """``(rows, 3)`` int32 ``[size, nparts, k]``: the cut index ``k`` of
    every active segment of the unit-weight recursion of ``n`` points
    into ``nparts`` parts.

    Unit-weight cuts depend only on a segment's size and part count, so
    the recursion's segment tree is the same for every point cloud.  The
    host walks it once with the oracle's own ``_uniform_cuts`` (float64
    IEEE arithmetic), and the device looks the cuts up instead of
    recomputing ``size * npl / np``, which a TPU would round in its
    emulated float64.
    """
    rows = {}
    todo = {(int(n), int(nparts))}
    while todo:
        nxt = set()
        for size, p in sorted(todo):
            if size <= 1 or p <= 1 or (size, p) in rows:
                continue
            npl = _split_counts(p, uneven_prime)[0]
            k = int(_uniform_cuts(np.array([size]), np.array([npl / p]))[0])
            rows[(size, p)] = k
            nxt |= {(k, npl), (size - k, p - npl)}
        todo = nxt
    return np.array([(s_, p_, k) for (s_, p_), k in sorted(rows.items())],
                    dtype=np.int32).reshape(-1, 3)


# ---------------------------------------------------------------------------
# the device sweep
# ---------------------------------------------------------------------------

def _sweep(ranks, uvals, sdo, w, npl_tab, cut_tab, n, B, nparts, *, d,
           sfc, longest_dim, weighted, npts_b, nb_b, lat):
    """One whole batched partition on device.

    ranks   : (d, npts_b) i32 — each padded point's key per dimension:
              its value less the minimum where ``lat[j]``, else the
              dense rank of its value (equal values share a key).
    uvals   : (d, npts_b) f64 — the distinct coordinate values by rank
              (read only for the longest-dimension extents of ranked
              dimensions; ``(d, 1)`` when every dimension is lattice).
    sdo     : (nb_b, d) i32 — per-candidate cut-dimension priority rows,
              spread once into d loop-invariant ``(N,)`` vectors
              ``sdo_v[p][slot] = sdo[block(slot), p]``.
    w       : (npts_b,) f64 — point weights (ignored unless weighted).
    npl_tab : (tab_b,) i32 — npl lookup by current part count.
    cut_tab : (cut_b, 3) i32 — unit-weight cut table (:func:`_cut_table`;
              ignored when weighted).
    n, B, nparts : traced scalars (real points / candidates / parts), so
        they stay OUT of the compile key; only the buckets are static.
    lat     : static per-dimension booleans, the lattice dimensions.

    The loop body holds no array with the dimension as its minor axis:
    the ranks at the current point order are the rows of one ``(d, N)``
    gather, the extents and the flip bits d ``(N,)`` vectors each, and
    the cut dimension is picked from them by select chains (``pick``),
    so the choice stays bit-for-bit the oracle's (the first strictly
    longer extent in priority order).

    Returns (nb_b, npts_b) i32 part numbers in original point order.
    """
    N = nb_b * npts_b
    pos = jnp.arange(N, dtype=_I32)
    local = pos % npts_b
    block = pos // npts_b
    blk0 = block * npts_b
    real = (block < B) & (local < n)
    # segment layout: block b = [one real segment of n points][pad tail
    # as its own closed segment]; whole pad blocks are closed segments
    seg_start = jnp.where(real, blk0, jnp.where(block < B, blk0 + n, blk0)
                          ).astype(_I32)
    seg_size = jnp.where(real, n, jnp.where(block < B, npts_b - n, npts_b)
                         ).astype(_I32)
    seg_np = jnp.where(real, nparts, 1).astype(_I32)
    mu = jnp.zeros(N, dtype=_I32)
    pts = local  # block-local id of the point at each position
    # per-segment coordinate signs, one (N,) vector per dimension
    flip = tuple(jnp.zeros(N, dtype=bool) for _ in range(d))
    # loop-invariant priority rows, one (N,) vector per priority p:
    # sdo_v[p][slot] = sdo[block(slot), p]
    sdo_v = [jnp.repeat(sdo[:, p], npts_b) for p in range(d)]
    lpos = local.reshape(nb_b, npts_b)
    cut_size, cut_np, cut_k = cut_tab[:, 0], cut_tab[:, 1], cut_tab[:, 2]

    def pick(cut, vals):
        """``vals[cut]`` slot by slot, as a select chain over the d
        per-dimension vectors (no gather across dimensions)."""
        out = vals[-1]
        for j in range(d - 2, -1, -1):
            out = jnp.where(cut == j, vals[j], out)
        return out

    def cond(state):
        _, _, _, _, seg_size, seg_np, _ = state
        return jnp.any((seg_np > 1) & (seg_size > 1))

    def body(state):
        level, pts, mu, seg_start, seg_size, seg_np, flip = state
        act = (seg_np > 1) & (seg_size > 1)
        # one gather of d-element rows, sliced: on a TPU d gathers of
        # single ranks cost about d times as much
        R = list(jnp.take(ranks, pts, axis=1))

        # --- cut dimension (reference: _pick_cut_dims / alternation) ----
        if d == 1:
            cut = jnp.zeros(N, dtype=_I32)
        elif longest_dim:
            # a segment's flips are uniform, so its extent is that of
            # the unflipped values, exactly as IEEE subtracts the
            # flipped max and min in the oracle: a lattice key is the
            # value less a constant, so its max - min is the extent
            # itself; a ranked dimension looks both ends up by rank
            exact = all(lat)  # integer extents: int32, no tolerance
            exts = []
            for j in range(d):
                hi = jax.ops.segment_max(R[j], seg_start, num_segments=N,
                                         indices_are_sorted=True)
                lo = jax.ops.segment_min(R[j], seg_start, num_segments=N,
                                         indices_are_sorted=True)
                if lat[j]:
                    # an empty segment's entry wraps here, and is never
                    # read: every slot reads its own, non-empty segment
                    e = (hi - lo)[seg_start]
                    exts.append(e if exact else e.astype(_F64))
                else:
                    exts.append(uvals[j][hi[seg_start]]
                                - uvals[j][lo[seg_start]])
            # the first strictly longer extent in priority order wins
            cut = sdo_v[0]
            best_e = pick(sdo_v[0], exts)
            for p in range(1, d):
                pri = pick(sdo_v[p], exts)
                better = pri > (best_e if exact else best_e + 1e-12)
                cut = jnp.where(better, sdo_v[p], cut)
                best_e = jnp.where(better, pri, best_e)
        else:
            cut = pick(level % d, sdo_v)

        # --- segmented stable sort by the cut coordinate ----------------
        # the position is the last key, so the order is the stable one;
        # only three int32 operands ride the sort, the point ids follow
        # by one gather of the permutation
        rc = pick(cut, R)
        ckey = jnp.where(pick(cut, flip), -rc, rc)
        _, _, perm = lax.sort(
            ((seg_start - blk0).reshape(nb_b, npts_b),
             ckey.reshape(nb_b, npts_b), lpos),
            dimension=1, num_keys=3, is_stable=False)
        pts = jnp.take_along_axis(pts.reshape(nb_b, npts_b), perm,
                                  axis=1).reshape(N)
        # mu / seg_* / flip are constant within segments, so the
        # within-segment permutation leaves them correct
        rank = pos - seg_start

        # --- cut placement (reference: _uniform_cuts / _padded_cuts) ----
        npl = npl_tab[seg_np]
        if not weighted:
            hit = ((seg_size[:, None] == cut_size[None, :])
                   & (seg_np[:, None] == cut_np[None, :]))
            k = jnp.sum(jnp.where(hit, cut_k[None, :], 0), axis=1)
        else:
            ratio = npl.astype(_F64) / seg_np.astype(_F64)
            w_cur = w[pts]
            is_start = rank == 0

            def scan_f(c, xw):
                wi, st = xw
                c = jnp.where(st, wi, c + wi)
                return c, c

            _, cw = lax.scan(scan_f, jnp.float64(0.0), (w_cur, is_start),
                             unroll=8)
            last = seg_start + seg_size - 1
            below = cw < cw[last] * ratio
            k = jax.ops.segment_sum(below.astype(_I32), seg_start,
                                    num_segments=N,
                                    indices_are_sorted=True)[seg_start] + 1
            k = jnp.clip(k, 1, jnp.maximum(seg_size - 1, 1))
        k = k.astype(_I32)

        # --- flips + part numbers + child segments ----------------------
        right = rank >= k
        ar = act & right
        mu = mu + jnp.where(ar, npl, 0).astype(_I32)
        if sfc == "Gray":
            flip = tuple(f ^ ar for f in flip)
        elif sfc in ("FZ", "FZlow"):
            side = ar if sfc == "FZ" else act & ~right
            flip = tuple(f ^ ((cut == j) & side) for j, f in enumerate(flip))
        seg_np = jnp.where(act, jnp.where(right, seg_np - npl, npl),
                           seg_np).astype(_I32)
        seg_start = jnp.where(ar, seg_start + k, seg_start).astype(_I32)
        seg_size = jnp.where(act, jnp.where(right, seg_size - k, k),
                             seg_size).astype(_I32)
        return (level + 1, pts, mu, seg_start, seg_size, seg_np, flip)

    state = (jnp.int32(0), pts, mu, seg_start, seg_size, seg_np, flip)
    state = lax.while_loop(cond, body, state)
    _, pts, mu = state[0], state[1], state[2]
    out = jnp.zeros(N, dtype=_I32).at[blk0 + pts].set(mu,
                                                      unique_indices=True)
    return out.reshape(nb_b, npts_b)


_KEY_MASK = (1 << 31) - 1


def _hilbert_sweep(grid, uvals, sdo, w, npl_tab, cut_tab, n, B, nparts,
                   *, d, bits, weighted, npts_b, nb_b):
    """Batched Hilbert numbering on device (Skilling's transpose
    algorithm, mirroring ``orderings.hilbert_index`` /
    ``orderings._hilbert_split`` op for op).

    Same call signature as :func:`_sweep` so both share ``_engine``'s
    compile cache.  ``grid`` is the (d, npts_b) int32 Hilbert grid,
    quantised on the host by the oracle's ``_hilbert_quantise``.
    ``bits`` is static — the Skilling state machine and the bit
    interleave unroll over it.  The per-candidate dim-order is a
    *column gather on the quantised grid*: quantisation is
    per-dimension, so it commutes with column permutation; candidate
    ``b`` is then bit-identical to the host
    ``order_points(coords[:, dim_orders[b]], nparts, "H")``.
    """
    del uvals, npl_tab, cut_tab  # Hilbert splits need no MJ tables
    N = nb_b * npts_b
    q = grid.astype(jnp.int64)
    # --- fold the dim-order: Xc[i] = q[sdo[b, i]] per candidate --------
    sdo64 = sdo.astype(jnp.int64)
    Xc = [jnp.take(q, sdo64[:, i], axis=0).reshape(-1) for i in range(d)]
    # --- Skilling transpose (reference: orderings.hilbert_index) -------
    if d == 1:
        h = Xc[0]
    else:
        M = 1 << (bits - 1)
        Q = M
        while Q > 1:  # inverse undo excess work
            P = Q - 1
            for i in range(d):
                has = (Xc[i] & Q) != 0
                Xc[0] = jnp.where(has, Xc[0] ^ P, Xc[0])
                t = jnp.where(has, 0, (Xc[0] ^ Xc[i]) & P)
                Xc[0] = Xc[0] ^ t
                Xc[i] = Xc[i] ^ t
            Q >>= 1
        for i in range(1, d):  # Gray encode
            Xc[i] = Xc[i] ^ Xc[i - 1]
        t = jnp.zeros(N, dtype=jnp.int64)
        Q = M
        while Q > 1:
            has = (Xc[d - 1] & Q) != 0
            t = jnp.where(has, t ^ (Q - 1), t)
            Q >>= 1
        for i in range(d):
            Xc[i] = Xc[i] ^ t
        h = jnp.zeros(N, dtype=jnp.int64)
        for i in range(d):  # interleave: bit b of dim i -> b*d + (d-1-i)
            for b in range(bits):
                h = h | (((Xc[i] >> b) & 1) << (b * d + (d - 1 - i)))
    # --- per-block stable sort + split (ref: _hilbert_split) -----------
    # h < 2^(bits*d) <= 2^62 splits into two int32 keys; the pad key
    # (all ones) sorts no earlier than any real key, and the position
    # key puts the pad tail after the real points
    pos = jnp.arange(N, dtype=_I32)
    realN = ((pos % npts_b) < n) & ((pos // npts_b) < B)
    hhi = jnp.where(realN, (h >> 31).astype(_I32), _KEY_MASK)
    hlo = jnp.where(realN, (h & _KEY_MASK).astype(_I32), _KEY_MASK)
    lpos = (pos % npts_b).reshape(nb_b, npts_b)
    _, _, perm = lax.sort(
        (hhi.reshape(nb_b, npts_b), hlo.reshape(nb_b, npts_b), lpos),
        dimension=1, num_keys=3, is_stable=False)
    pts = perm.reshape(N)
    rank = (pos % npts_b).astype(jnp.int64)
    n64 = n.astype(jnp.int64)
    np64 = nparts.astype(jnp.int64)
    if not weighted:
        # closed form of searchsorted((arange(1,np)*n)//np, j, "right")
        part = jnp.minimum(((rank + 1) * np64 - 1) // n64, np64 - 1)
    else:
        w_srt = w[pts]
        is_start = rank == 0

        def scan_f(c, xw):
            wi, st = xw
            c = jnp.where(st, wi, c + wi)
            return c, c

        _, incl = lax.scan(scan_f, jnp.float64(0.0), (w_srt, is_start),
                           unroll=8)
        cwx = incl - w_srt  # EXCLUSIVE prefix, like cumsum(w) - w
        blk0 = (jnp.arange(N) // npts_b) * npts_b
        last = blk0 + n64 - 1
        w_last = w_srt[last]
        # total = cw[-1] + w[-1] with cw[-1] = incl[-1] - w[-1]: keep the
        # host's exact association (NOT plain incl[last])
        total = (incl[last] - w_last) + w_last
        part = jnp.minimum((cwx / total * np64.astype(_F64))
                           .astype(jnp.int64), np64 - 1)
    blk0 = (pos // npts_b) * npts_b
    out = jnp.zeros(N, dtype=_I32).at[blk0 + pts].set(
        part.astype(_I32), unique_indices=True)
    return out.reshape(nb_b, npts_b)


@functools.lru_cache(maxsize=None)
def _engine(d, sfc, longest_dim, weighted, npts_b, nb_b, tab_b, cut_b,
            bits, lat):
    """One jit-compiled sweep per (engine knobs, shape bucket).

    ``tab_b`` and ``cut_b`` are part of the key even though the function
    never reads them: every cache entry then sees exactly ONE input
    shape set, so the ``lru_cache`` hit/miss counters are a truthful
    compile-count proxy (mirrors ``metrics_jax._scorer``).  ``bits`` is
    the static Hilbert resolution (0 for the MJ sweeps) and ``lat``
    the MJ sweeps' lattice dimensions (``()`` for Hilbert), both read
    from the input by :func:`_prepare`; call sites MUST pass every key
    positionally — ``lru_cache`` keys keyword spellings separately and a
    split key would double-compile.  For ``sfc == "H"`` callers
    canonicalise ``longest_dim=True`` (Hilbert has no cut dimensions)
    so the knob cannot fragment the cache either.

    The jitted functions are named, so the device trace shows the
    engine as the modules ``jit_partition_mj`` and
    ``jit_partition_hilbert``; inside the fused program they are
    inlined, under its ``partition`` scope.
    """
    del tab_b, cut_b  # shape part of the key only
    if sfc == "H":
        def partition_hilbert(*args):
            return _hilbert_sweep(*args, d=d, bits=bits, weighted=weighted,
                                  npts_b=npts_b, nb_b=nb_b)
        return jax.jit(partition_hilbert)

    def partition_mj(*args):
        return _sweep(*args, d=d, sfc=sfc, longest_dim=longest_dim,
                      weighted=weighted, npts_b=npts_b, nb_b=nb_b, lat=lat)
    return jax.jit(partition_mj)


# registry-backed stat/reset pair (repro.obs); auto-registers with
# ``obs.snapshot()`` under "partition_jax"
partition_cache_stats, reset_partition_cache = \
    obs.instrument_compile_cache("partition_jax", _engine)


# ---------------------------------------------------------------------------
# host entry points (the orderings-module backend contract)
# ---------------------------------------------------------------------------

def _is_lattice(col: np.ndarray) -> bool:
    """Every value an integer, ``max - min`` below ``2**31``: the column
    is then keyed by ``value - min`` in int32, exactly (float64 holds
    every such difference)."""
    lo, hi = col.min(), col.max()
    return bool(np.isfinite(lo) and np.isfinite(hi) and hi - lo < 2.0**31
                and (col == np.floor(col)).all())


def _prepare(coords, nparts, sfc, dim_orders, weights, uneven_prime):
    """Padded device inputs of one batched call and the shape keys of
    :func:`_engine`: returns ``(args, (npts_b, nb_b, tab_b, cut_b,
    bits, lat))``, ``args`` being the engine's first six positional
    inputs (per-dim keys or the Hilbert grid, distinct values, dim
    orders, weights, split table, cut table).  ``lat`` marks the MJ
    sweep's lattice dimensions (:func:`_is_lattice`); it is read from
    the values, so one deployment's requests share it, and its count
    goes to the counter ``partition.lattice_dims``."""
    n, d = coords.shape
    B = len(dim_orders)
    npts_b = bucket_size(n, PART_BUCKET_MIN)
    nb_b = bucket_size(B, lo=1)
    if sfc == "H":
        bits, lat = hilbert_bits(n, d), ()
        keys = _hilbert_quantise(coords, bits).T.astype(np.int32)
        uvals = np.zeros((d, 1), dtype=np.float64)
    else:
        bits = 0
        lat = tuple(_is_lattice(coords[:, j]) for j in range(d))
        keys = np.empty((d, n), dtype=np.int32)
        # the value table is read only for ranked dimensions
        uvals = np.zeros((d, 1 if all(lat) else npts_b), dtype=np.float64)
        for j in range(d):
            col = coords[:, j]
            if lat[j]:
                keys[j] = col - col.min()
            else:
                u, inv = np.unique(col, return_inverse=True)
                keys[j] = inv.reshape(-1)
                uvals[j, :len(u)] = u
    obs.counter("partition.lattice_dims", sum(lat))
    keys = pad_axis(keys, npts_b, axis=1)
    sdo = np.tile(np.arange(d, dtype=np.int32), (nb_b, 1))
    sdo[:B] = dim_orders
    if weights is None:
        w = np.ones(npts_b, dtype=np.float64)
        cut = _cut_table(n, int(nparts), bool(uneven_prime))
    else:
        w = pad_axis(np.asarray(weights, dtype=np.float64), npts_b)
        cut = np.zeros((0, 3), dtype=np.int32)
    cut_b = bucket_size(len(cut), lo=TAB_MIN)
    cut = pad_axis(cut, cut_b)
    tab = _split_table(int(nparts), bool(uneven_prime))
    tab_b = bucket_size(len(tab), lo=TAB_MIN)
    tab = pad_axis(tab, tab_b)
    return (keys, uvals, sdo, w, tab, cut), (npts_b, nb_b, tab_b, cut_b,
                                             bits, lat)


def order_points_batched_jax(
    coords: np.ndarray,
    nparts: int,
    sfc: str,
    *,
    dim_orders: np.ndarray,
    weights: np.ndarray | None = None,
    longest_dim: bool = True,
    uneven_prime: bool = False,
) -> np.ndarray:
    """Device implementation of ``vectorized_order_batched`` (same
    contract; results bit-identical, asserted in
    tests/test_partition_jax.py)."""
    coords = np.asarray(coords, dtype=np.float64)
    dim_orders = np.atleast_2d(np.asarray(dim_orders, dtype=np.int64))
    B = len(dim_orders)
    if coords.ndim == 1:
        coords = coords[:, None]
    n, d = coords.shape
    if nparts <= 1 or n == 0:
        return np.zeros((B, n), dtype=np.int64)
    npts_b = bucket_size(n, PART_BUCKET_MIN)
    if bucket_size(B, lo=1) * npts_b >= 1 << 31:
        # int32 slot ids bound the device batch; no realistic input
        from .partition import vectorized_order_batched  # pragma: no cover
        return vectorized_order_batched(  # pragma: no cover
            coords, nparts, sfc, dim_orders=dim_orders, weights=weights,
            longest_dim=longest_dim, uneven_prime=uneven_prime)
    args, (npts_b, nb_b, tab_b, cut_b, bits, lat) = _prepare(
        coords, nparts, sfc, dim_orders, weights, uneven_prime)
    ld = True if sfc == "H" else bool(longest_dim)  # canonical H cache key
    misses0 = _engine.cache_info().misses
    fn = _engine(d, sfc, ld, weights is not None,
                 npts_b, nb_b, tab_b, cut_b, bits, lat)
    obs.annotate(compile_cache=(
        "miss" if _engine.cache_info().misses > misses0 else "hit"),
        lattice_dims=sum(lat))
    out = fn(*args, np.int32(n), np.int32(B), np.int32(nparts))
    return np.asarray(out)[:B, :n].astype(np.int64)


def order_points_jax(
    coords: np.ndarray,
    nparts: int,
    sfc: str,
    *,
    weights: np.ndarray | None = None,
    dim_order: np.ndarray | None = None,
    longest_dim: bool = True,
    uneven_prime: bool = False,
) -> np.ndarray:
    """Device implementation of ``vectorized_order`` (same contract)."""
    coords = np.asarray(coords, dtype=np.float64)
    d = coords.shape[1] if coords.ndim == 2 else 1
    dimo = (np.arange(d, dtype=np.int64) if dim_order is None
            else np.asarray(dim_order, dtype=np.int64))
    return order_points_batched_jax(
        coords, nparts, sfc, dim_orders=dimo[None], weights=weights,
        longest_dim=longest_dim, uneven_prime=uneven_prime)[0]
