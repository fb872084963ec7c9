"""Candidate-mapping scoring as a fused Pallas TPU kernel.

One launch scores a whole stack of candidate mappings: grid
``(ncandidates, message_tiles)`` with the candidate dimension parallel
and the tile dimension sequential.  Message tiles (src/dst coordinates
and weights) stream through VMEM; the circular difference-array
range-add of the dimension-ordered router accumulates into per-(dim,
direction) VMEM scratch link-load buffers that live across the tile
steps of a candidate — the same carried-in-VMEM state trick the repo's
SSD kernel uses instead of global-memory round trips.  On the last tile
the prefix sums, the weighted-hops accumulators and the link maxima
reduce on-chip, so only one (8, 128) metric tile per candidate ever
returns to HBM (no materialised ``(ncand, nlinks)`` load arrays).

TPU adaptation notes:

- Messages lie on lanes: a candidate's coordinates are ``(ncols, E)``
  and the weights ``(1, E)``, so a tile of ``T`` messages fills whole
  128-lane vregs instead of padding a 4-wide coordinate row to 128.
- Scatter-free scatter: TPUs have no fast scatter, so the range-add
  becomes a matmul.  For machine dim ``k`` each message contributes
  difference-array entries at (column ``c`` along dim k, row key ``r``
  over the remaining dims).  A tile builds ``A = sum_i onehot(c_i) *
  val_i`` (s+1, T) on the VPU and ``B = onehot(r)`` (rows, T) once per
  dim, then ``acc += A @ B^T`` lands every entry on the MXU — the pos
  and neg directions share ``B``.  The row axis is chunked so the
  one-hot never exceeds a fixed VMEM footprint.
- The accumulator layout is (s+1 sublanes, rows lanes): the dump column
  that closes wrapped intervals is sublane ``s``, and the prefix sum of
  the final reduction runs along sublanes as one more matmul with a
  lower-triangular ones matrix (Mosaic has no cumsum), so no irregular
  reshape is needed between scatter and reduction.
- Coordinates are int32 (1, T) lane vectors; all arithmetic (wrap
  direction choice, interval lengths, mixed-radix row keys) is
  elementwise VPU work.  Scalar accumulators (weighted/total hops)
  live in SMEM scratch across tiles.
- The kernel is traced with ``jax_enable_x64`` off, so every index and
  literal is 32-bit (Mosaic has no 64-bit types) even in a process that
  has x64 on for the device partitioner.

Zero-weight padded messages and zero-length (src == dst) messages
contribute exact zeros, which is what makes the power-of-two message
bucketing of :mod:`ops` exact rather than approximate.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROW_CHUNK = 512  # lanes of the row one-hot built per matmul
OUT_TILE = (8, 128)  # per-candidate metric block: one f32/i32 vreg
_HIGHEST = jax.lax.Precision.HIGHEST


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def acc_shapes(dims, core_dims):
    """Per network dim the (sublanes, lanes) link-accumulator shape:
    (s_k + 1 padded to 8, rows over the other dims padded to 128, or to
    a whole number of ``ROW_CHUNK`` chunks past one chunk)."""
    nd = len(dims) - core_dims
    shapes = []
    for k in range(nd):
        nrows = 1
        for j, d in enumerate(dims):
            if j != k:
                nrows *= d
        lanes = _round_up(nrows, 128 if nrows <= ROW_CHUNK else ROW_CHUNK)
        shapes.append((_round_up(dims[k] + 1, 8), lanes))
    return shapes


def _onehot(idx, width):
    """(1, T) int32 indices -> (width, T) f32 one-hot via a broadcast
    compare against a sublane iota (the TPU-native scatter primitive)."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (width, 1), 0)
    return (rows == idx).astype(jnp.float32)


def _interval_matrix(start, length, w, s, width):
    """Difference-array contributions of circular intervals
    [start, start+length) as a dense (width, T) column matrix.

    Four weighted one-hots per message: open at ``start``, close at
    ``min(end, s)`` (``s`` is the dump column), and for wrapped
    intervals open the tail at 0 and close it at ``end - s``.
    Zero-length messages get zero weight, so padding is exact.
    """
    end = start + length
    wz = jnp.where(length > 0, w, 0.0)
    wrapped = end > s
    wwr = jnp.where(wrapped, wz, 0.0)
    m = _onehot(start, width) * wz
    m = m - _onehot(jnp.minimum(end, s), width) * wz
    m = m + _onehot(jnp.zeros_like(start), width) * wwr
    m = m - _onehot(jnp.where(wrapped, end - s, 0), width) * wwr
    return m


def _wrap(x, s):
    """``x % s`` for ``x`` in ``(-s, s)``: coordinates lie in ``[0, s)``,
    so each difference wraps at most once."""
    return jnp.where(x < 0, x + s, x)


def _prefix_matrix(sp, s):
    """(sp, sp) f32 ``L[i, j] = 1`` for ``j <= i < s``: ``L @ acc`` is
    the inclusive prefix sum of the ``s`` real rows of ``acc``; the dump
    row ``s`` and the sublane padding come out as zero rows."""
    i = jax.lax.broadcasted_iota(jnp.int32, (sp, sp), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (sp, sp), 1)
    return ((j <= i) & (i < s)).astype(jnp.float32)


def _mapscore_kernel(*refs, dims, wrap, core_dims, traffic, sdims):
    """Kernel body.  ``refs`` (in order): src, dst, w, [inv_bw], outf,
    outi, wh_scr, th_scr, [acc_pos_0, acc_neg_0, acc_pos_1, ...]."""
    nd = len(dims) - core_dims
    if traffic:
        src_ref, dst_ref, w_ref, invbw_ref = refs[:4]
        outf_ref, outi_ref, wh_s, th_s = refs[4:8]
        accs = refs[8:]
    else:
        src_ref, dst_ref, w_ref = refs[:3]
        outf_ref, outi_ref, wh_s, th_s = refs[3:7]
        accs = ()
    ti = pl.program_id(1)
    ntiles = pl.num_programs(1)

    @pl.when(ti == 0)
    def _init():
        wh_s[0] = jnp.float32(0.0)
        th_s[0] = jnp.int32(0)
        for acc in accs:
            acc[...] = jnp.zeros_like(acc)

    src = src_ref[0]                       # (ncols, T) int32
    dst = dst_ref[0]
    w = w_ref[...].astype(jnp.float32)     # (1, T)

    # hop metrics: shortest per-dim distance, accumulated across tiles
    hops = jnp.zeros_like(src[:1, :])
    for k in range(nd):
        s = dims[k]
        d = jnp.abs(src[k:k + 1, :] - dst[k:k + 1, :])
        if wrap[k]:
            d = jnp.minimum(d, s - d)
        hops = hops + d
    wh_s[0] += jnp.sum(hops.astype(jnp.float32) * w)
    # Mosaic lowers an integer sum (and ``%``) by re-tracing jnp code at
    # lowering time, under the process's x64 setting: keep both out.  A
    # tile's hop total (< 2^24 for tiles <= 512 and < 2^15 hops per
    # message) is exact in f32.
    th_s[0] += jnp.sum(hops.astype(jnp.float32)).astype(jnp.int32)

    if traffic:
        for k in range(nd):
            s = dims[k]
            sp, rp = sdims[k]
            a = src[k:k + 1, :]
            b = dst[k:k + 1, :]
            if wrap[k]:
                fwd = _wrap(b - a, s)
                bwd = _wrap(a - b, s)
                use_fwd = fwd <= bwd
                len_f = jnp.where(use_fwd, fwd, 0)
                len_b = jnp.where(use_fwd, 0, bwd)
                start_b = _wrap(a - len_b, s)
            else:
                use_fwd = b >= a
                len_f = jnp.where(use_fwd, b - a, 0)
                len_b = jnp.where(use_fwd, 0, a - b)
                start_b = a - len_b
            # dimension-ordered routing: dims before k already sit at
            # the destination, dims after k (and core dims) at the src
            rkey = jnp.zeros_like(a)
            for j in range(len(dims)):
                if j == k:
                    continue
                col = dst[j:j + 1, :] if j < k else src[j:j + 1, :]
                rkey = rkey * dims[j] + col
            a_pos = _interval_matrix(a, len_f, w, s, sp)       # (sp, T)
            a_neg = _interval_matrix(start_b, len_b, w, s, sp)
            width = min(ROW_CHUNK, rp)
            pairs = ((accs[2 * k], a_pos), (accs[2 * k + 1], a_neg))

            def chunk(c, carry, rkey=rkey, width=width, pairs=pairs):
                off = pl.multiple_of(c * width, width)
                b_oh = _onehot(rkey - off, width)              # (width, T)
                for acc, amat in pairs:
                    acc[:, pl.ds(off, width)] += jax.lax.dot_general(
                        amat, b_oh, (((1,), (1,)), ((), ())),
                        precision=_HIGHEST,
                        preferred_element_type=jnp.float32)
                return carry

            # a loop, not an unrolled chunk list: compile time stays flat
            # in the machine size
            jax.lax.fori_loop(0, rp // width, chunk, 0)

    @pl.when(ti == ntiles - 1)
    def _finish():
        data = jnp.float32(0.0)
        lat = jnp.float32(0.0)
        if traffic:
            off_s = 0
            for k in range(nd):
                sp, _ = sdims[k]
                inv_bw = invbw_ref[off_s:off_s + sp, :]         # (sp, 1)
                off_s += sp
                tri = _prefix_matrix(sp, dims[k])
                for acc in (accs[2 * k], accs[2 * k + 1]):
                    cum = jax.lax.dot_general(                  # (sp, rp)
                        tri, acc[...], (((1,), (0,)), ((), ())),
                        precision=_HIGHEST,
                        preferred_element_type=jnp.float32)
                    data = jnp.maximum(data, jnp.max(cum))
                    per_c = jnp.max(cum, axis=1, keepdims=True)  # (sp, 1)
                    lat = jnp.maximum(lat, jnp.max(per_c * inv_bw))
        row = jax.lax.broadcasted_iota(jnp.int32, OUT_TILE, 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, OUT_TILE, 1)
        first = row == 0
        vf = jnp.where(first & (lane == 0), wh_s[0], jnp.float32(0.0))
        vf = jnp.where(first & (lane == 1), data, vf)
        vf = jnp.where(first & (lane == 2), lat, vf)
        outf_ref[0] = vf
        outi_ref[0] = jnp.where(first & (lane == 0), th_s[0],
                                jnp.int32(0))


def mapscore_call(src, dst, w, inv_bw=None, *, dims, wrap, core_dims,
                  traffic, tile, interpret=False):
    """Launch the scoring kernel over a padded candidate stack.

    src, dst : (nb, ncols, E) int32 message coordinates, messages on
               the last axis (E a multiple of ``tile``; ncols ==
               len(dims) when routing).
    w        : (1, E) f32 weights, shared across candidates.
    inv_bw   : (sum of accumulator sublanes, 1) f32 — 1/bandwidth per
               link column, one zero-padded block of ``acc_shapes``
               sublanes per network dim (``traffic`` only; see
               :func:`repro.kernels.mapscore.ops.inv_bandwidth`).

    Returns ``(outf, outi)``: (nb, 8, 128) f32 with [weighted_hops,
    data_max, latency_max] at ``[:, 0, :3]`` and (nb, 8, 128) i32 with
    total_hops at ``[:, 0, 0]``; every other entry is zero.
    """
    nb, ncols, e = src.shape
    ntiles = e // tile
    assert ntiles * tile == e, (e, tile)
    sdims = tuple(acc_shapes(dims, core_dims)) if traffic else ()
    kernel = functools.partial(
        _mapscore_kernel, dims=tuple(dims), wrap=tuple(wrap),
        core_dims=core_dims, traffic=traffic, sdims=sdims)
    in_specs = [
        pl.BlockSpec((1, ncols, tile), lambda bi, ti: (bi, 0, ti)),
        pl.BlockSpec((1, ncols, tile), lambda bi, ti: (bi, 0, ti)),
        pl.BlockSpec((1, tile), lambda bi, ti: (0, ti)),
    ]
    args = [src, dst, w]
    if traffic:
        in_specs.append(
            pl.BlockSpec(inv_bw.shape, lambda bi, ti: (0, 0)))
        args.append(inv_bw)
    scratch = [pltpu.SMEM((1,), jnp.float32), pltpu.SMEM((1,), jnp.int32)]
    for sp, rp in sdims:
        scratch.append(pltpu.VMEM((sp, rp), jnp.float32))
        scratch.append(pltpu.VMEM((sp, rp), jnp.float32))
    out_spec = pl.BlockSpec((1,) + OUT_TILE, lambda bi, ti: (bi, 0, 0))
    call = pl.pallas_call(
        kernel,
        grid=(nb, ntiles),
        in_specs=in_specs,
        out_specs=(out_spec, out_spec),
        out_shape=(jax.ShapeDtypeStruct((nb,) + OUT_TILE, jnp.float32),
                   jax.ShapeDtypeStruct((nb,) + OUT_TILE, jnp.int32)),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )
    # traced (body and index maps) with x64 off: its literals are then
    # 32-bit here and inside the fused program of an x64 process
    with jax.enable_x64(False):
        return call(*args)
