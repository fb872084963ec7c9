"""One-compiled-program rotation sweep: partition -> match -> score ->
select -> (optionally) refine, entirely on device.

When the pipeline resolves ``partition_backend="jax"`` AND a jax/pallas
scoring backend, the whole batched rotation sweep of
:meth:`MappingPipeline.map` collapses into a single jitted program per
candidate stack: both sides' level-synchronous partitions
(:mod:`repro.core.partition_jax`), the part->processor matching
gathers, the per-candidate coordinate-stack assembly, the metric
evaluation (the bucketed jax scorer or the fused Pallas kernel), and
the lexicographic winner selection.  Only the winning permutation, its
index and the score matrix return to host — zero host<->device
transfers between the partition and score stages.

Results are bit-identical to the unfused path by construction: the
partitioner is the bit-identity-tested jax engine (all five SFC kinds,
including the unrolled Skilling Hilbert state machine), the matching
gathers mirror ``map_candidates``'s ``part_to_proc``/``mu_t`` assembly
integer for integer, and the score columns are the same f32-derived
values the host :class:`CandidateSearch` lexsorts (f32->f64 casts are
exact).  With a ``refine`` spec (the hier path), the bounded greedy
swap-refinement loop of :func:`repro.hier.refine.refine_swaps` also
runs inside the program — a ``lax.while_loop`` over propose ->
delta-score -> monotone apply with early exit — so coarse sweep AND
refinement are one compile and only the final permutation lands on
host.

The compile cache mirrors ``metrics_jax._scorer`` /
``partition_jax._engine``: every entry is keyed by the full static
shape set (machine structure, both partition buckets, message/candidate
buckets, rotation selector tuples), so one scenario compiles O(1)
fused programs and :func:`fused_cache_stats` is a truthful
compile-count proxy.  This module imports jax at module level — the
pipeline only imports it after ``resolve_partition_backend`` returned
``"jax"``.
"""

from __future__ import annotations

import functools

import numpy as np

from repro import faults, obs
from repro.core import partition_jax as _pj  # noqa: F401  (enables x64)

import jax.numpy as jnp  # noqa: E402
from jax import lax  # noqa: E402

from repro.core import metrics_jax  # noqa: E402
from repro.core.mapping import MappingResult  # noqa: E402
from repro.core.metrics_jax import bucket_size, pad_axis  # noqa: E402
from repro.kernels.mapscore import ops as _mapscore  # noqa: E402

# metric keys the in-program column builder understands (== the full
# evaluate_candidates contract; anything else bails to the unfused path)
_KNOWN_KEYS = frozenset(("weighted_hops", "total_hops", "average_hops",
                         "data_max", "latency_max"))

# bail threshold on the in-program src/dst gather footprint (elements):
# beyond this the chunked host paths are the better memory citizens
MAX_FUSED_ELEMS = 1 << 27


def _build(*, d_t, task_sfc, d_p, proc_sfc, longest_dim, weighted,
           tnum, pnum, t_sel, p_sel, npts_bt, nbt_b, npts_bp, nbp_b,
           tab_b, cut_bt, cut_bp, bits_t, bits_p, dims, wrap, core_dims,
           objective, traffic, score_kind, ne, ne_b, nb_b, ncols, tile,
           interpret, refine):
    """The traced body of one fused program (all kwargs static).

    ``refine`` is ``None`` (sweep only) or a static ``(rounds, top,
    degree)`` triple: the hier swap-refinement loop then runs INSIDE
    the same program, after winner selection, as a ``lax.while_loop``
    over propose -> delta-score -> monotone apply (mirroring
    ``repro.hier.refine.refine_swaps`` decision for decision), and only
    the refined cluster -> router permutation returns to host.
    """
    # Hilbert has no cut dimensions: canonicalise longest_dim so the
    # knob cannot fragment the partition-engine compile cache
    ld_t = True if task_sfc == "H" else longest_dim
    ld_p = True if proc_sfc == "H" else longest_dim
    eng_t = _pj._engine(d_t, task_sfc, ld_t, weighted,
                        npts_bt, nbt_b, tab_b, cut_bt, bits_t)
    eng_p = _pj._engine(d_p, proc_sfc, ld_p, False,
                        npts_bp, nbp_b, tab_b, cut_bp, bits_p)
    if score_kind == "jax":
        score_fn = metrics_jax._scorer(dims, wrap, core_dims, traffic,
                                       ne_b, nb_b)
    else:
        score_fn = _mapscore._compiled(dims, wrap, core_dims, traffic,
                                       ne_b, tile, nb_b, ncols, interpret)
    ncand = len(t_sel)
    nut = max(t_sel) + 1   # unique task-side rotations (selector covers
    nup = max(p_sel) + 1   # 0..nut-1; likewise proc side)
    t_sel_a = np.asarray(t_sel, dtype=np.int32)
    p_sel_a = np.asarray(p_sel, dtype=np.int32)
    nobj = len(objective)
    nd = len(dims) - core_dims
    wrapped = tuple(bool(x) for x in wrap)

    # static refinement bounds (host: top/degree clamps + k <= 0 break)
    if refine is not None:
        rounds, top, degree = refine
        top_s = min(int(top), tnum)
        k_s = min(int(degree), pnum - 1)
        rounds_eff = int(rounds) if (rounds > 0 and top_s > 0
                                     and k_s > 0) else 0
        P = max(top_s * k_s, 1)
        separable = all(k in ("weighted_hops", "total_hops")
                        for k in objective)
        if not separable:
            nb2 = bucket_size(k_s, lo=1)  # one hot-row chunk per launch
            if score_kind == "jax":
                score_fn2 = metrics_jax._scorer(dims, wrap, core_dims,
                                                traffic, ne_b, nb2)
            else:
                score_fn2 = _mapscore._compiled(dims, wrap, core_dims,
                                                traffic, ne_b, tile, nb2,
                                                ncols, interpret)

    def _hops(x, y):
        """int64 network hop distance, mirrors ``metrics.pairwise_hops``
        (reads the first ``nd`` columns; wrap dims take the torus min)."""
        tot = jnp.zeros(jnp.broadcast_shapes(x.shape[:-1], y.shape[:-1]),
                        dtype=jnp.int64)
        for kk in range(nd):
            dk = jnp.abs(x[..., kk].astype(jnp.int64)
                         - y[..., kk].astype(jnp.int64))
            if wrapped[kk]:
                dk = jnp.minimum(dk, dims[kk] - dk)
            tot = tot + dk
        return tot

    def _lex_less1(s, t):
        """Vectorised mirror of ``hier.refine._lex_less`` (tol 1e-12):
        reversed fold so component 0 dominates."""
        res = jnp.bool_(False)
        for j in reversed(range(nobj)):
            res = jnp.where(s[j] < t[j] - 1e-12, True,
                            jnp.where(s[j] > t[j] + 1e-12, False, res))
        return res

    def run(args_t, args_p, edges, ew, ew64, acoords, bw):
        # --- stage 2: both partitions (inner jit calls inline) ---------
        mu_t = eng_t(*args_t, jnp.int32(tnum), jnp.int32(nut),
                     jnp.int32(pnum))[:, :tnum]
        mu_p = eng_p(*args_p, jnp.int32(pnum), jnp.int32(nup),
                     jnp.int32(pnum))[:nup, :pnum]

        # --- stage 3: vectorised GETMAPPINGARRAYS ----------------------
        # (mirrors map_candidates' part_to_proc / mu_t gathers)
        ptp = jnp.full((nup, pnum), -1, dtype=jnp.int32)
        ptp = ptp.at[jnp.arange(nup)[:, None], mu_p].set(
            jnp.arange(pnum, dtype=jnp.int32)[None, :])
        ok = jnp.min(ptp) >= 0
        t2p = jnp.take_along_axis(ptp[p_sel_a], mu_t[t_sel_a], axis=1)

        e0, e1 = edges[:, 0], edges[:, 1]

        def batch_cols(cs_padded, fn):
            """(B, nobj) f64 objective matrix for a padded stack batch
            (the same column builder the sweep's winner selection and
            the host CandidateSearch lexsort use)."""
            if score_kind == "jax":
                ev = fn(cs_padded[:, e0, :ncols], cs_padded[:, e1, :ncols],
                        ew, bw)
                wh, th = ev["weighted_hops"], ev["total_hops"]
                data, lat = ev.get("data_max"), ev.get("latency_max")
            else:
                # the kernel takes messages on lanes: (B, ncols, E)
                cst = jnp.swapaxes(cs_padded[:, :, :ncols], 1, 2)
                args = [cst[:, :, e0], cst[:, :, e1], ew.reshape(1, -1)]
                if traffic:
                    args.append(bw)
                outf, outi = fn(*args)
                wh, th = outf[:, 0, 0], outi[:, 0, 0]
                data = outf[:, 0, 1] if traffic else None
                lat = outf[:, 0, 2] if traffic else None

            def col(key):
                if key == "weighted_hops":
                    return wh.astype(jnp.float64)
                if key == "total_hops":
                    return th.astype(jnp.float64)
                if key == "average_hops":
                    return th.astype(jnp.float64) / ne
                return (data if key == "data_max"
                        else lat).astype(jnp.float64)

            return jnp.stack([col(k) for k in objective], axis=1)

        # --- stage 4: score + select -----------------------------------
        cs = acoords[t2p]                          # (ncand, tnum, ndim)
        cs = jnp.pad(cs, ((0, nb_b - ncand), (0, 0), (0, 0)))
        scores = batch_cols(cs, score_fn)[:ncand]
        keys = tuple(scores[:, j]
                     for j in reversed(range(scores.shape[1])))
        best_i = jnp.lexsort(keys)[0].astype(jnp.int32)
        if refine is None:
            return best_i, t2p[best_i], scores, ok

        # --- stage 5: fused swap refinement ----------------------------
        # (mirrors hier.refine.refine_swaps decision for decision; the
        # host pass is the oracle — see tests/test_hier.py)
        rc = acoords.astype(jnp.int64)            # (pnum, ncols) rows
        c2r0 = t2p[best_i]                        # cluster -> router
        r2c0 = jnp.full((pnum,), -1, jnp.int32).at[c2r0].set(
            jnp.arange(tnum, dtype=jnp.int32))

        def full_cols(c2r_vec):
            """Objective tuple of a whole assignment.  Separable keys
            sum exactly in f64 (bit-identical to the host's numpy
            evaluator for integer-valued volumes); otherwise the same
            f32 scorer the host jax/pallas evaluator runs."""
            if separable:
                st = rc[c2r_vec]
                h = _hops(st[e0], st[e1]).astype(jnp.float64)
                outc = []
                for kkey in objective:
                    outc.append(jnp.sum(ew64 * h)
                                if kkey == "weighted_hops"
                                else jnp.sum(h))
                return jnp.stack(outc)
            cs1 = jnp.pad(acoords[c2r_vec][None],
                          ((0, nb_b - 1), (0, 0), (0, 0)))
            return batch_cols(cs1, score_fn)[0]

        base0 = full_cols(c2r0)
        hist0 = jnp.zeros((rounds_eff + 1, nobj),
                          dtype=jnp.float64).at[0].set(base0)

        def body(state):
            rnd, done, c2r, r2c, base, hist, hist_len, acc_t, ev_t = state
            cc = rc[c2r]                          # (tnum, ncols) i64
            ho = _hops(cc[e0], cc[e1])            # pad edges (0,0) -> 0
            h_e = ho.astype(jnp.float64) * ew64
            contrib = (jnp.zeros(tnum, jnp.float64)
                       .at[e0].add(h_e).at[e1].add(h_e))
            _, hot = lax.sort(
                (-contrib, jnp.arange(tnum, dtype=jnp.int32)),
                num_keys=1, is_stable=True)       # == argsort(-contrib)
            hot = hot[:top_s]
            hot_valid = contrib[hot] > 0

            # network-nearest allocated routers (full stable argsort:
            # ties break on router id, matching the host after ISSUE 9)
            dm = _hops(cc[hot][:, None, :], rc[None, :, :]
                       ).astype(jnp.float64)
            dm = dm.at[jnp.arange(top_s), c2r[hot]].set(jnp.inf)
            colid = jnp.broadcast_to(jnp.arange(pnum, dtype=jnp.int32),
                                     (top_s, pnum))
            _, nearf = lax.sort((dm, colid), dimension=1, num_keys=1,
                                is_stable=True)
            near = nearf[:, :k_s]

            # proposals in host generation order (hot-major), deduped by
            # unordered router pair, first occurrence wins: valid hot
            # rows precede invalid ones, so padding can never steal a key
            a = jnp.repeat(hot, k_s)
            va = jnp.repeat(hot_valid, k_s)
            ra = c2r[a]
            rb = near.reshape(-1)
            b = r2c[rb]
            gen = jnp.arange(P, dtype=jnp.int32)
            dkey = (jnp.minimum(ra, rb).astype(jnp.int64) * pnum
                    + jnp.maximum(ra, rb))
            skey, sgen = lax.sort((dkey, gen), num_keys=1, is_stable=True)
            firstk = jnp.concatenate([jnp.ones(1, bool),
                                      skey[1:] != skey[:-1]])
            valid = va & jnp.zeros(P, bool).at[sgen].set(firstk)
            n_valid = jnp.sum(valid.astype(jnp.int32))

            def chunk_scores(args):
                """Scores of one hot row's k_s proposals (chunked so the
                edited-stack footprint stays k_s * ne_b, not P * ne_b)."""
                ac, bc, rac, rbc = args
                if separable:
                    # score = base + sum_incident w * (h_new - h_old):
                    # exactly the host's base - base_union + union value
                    # for integer-valued f64 volumes
                    pa, pb = rc[rbc], rc[rac]     # (k_s, ncols) new rows
                    isa0 = e0[None, :] == ac[:, None]
                    isb0 = (e0[None, :] == bc[:, None]) & \
                        (bc[:, None] >= 0)
                    isa1 = e1[None, :] == ac[:, None]
                    isb1 = (e1[None, :] == bc[:, None]) & \
                        (bc[:, None] >= 0)
                    hn = jnp.zeros((k_s, ne_b), jnp.int64)
                    for kk in range(nd):
                        x0 = jnp.where(
                            isa0, pa[:, kk][:, None],
                            jnp.where(isb0, pb[:, kk][:, None],
                                      cc[e0, kk][None, :]))
                        x1 = jnp.where(
                            isa1, pa[:, kk][:, None],
                            jnp.where(isb1, pb[:, kk][:, None],
                                      cc[e1, kk][None, :]))
                        dk = jnp.abs(x0 - x1)
                        if wrapped[kk]:
                            dk = jnp.minimum(dk, dims[kk] - dk)
                        hn = hn + dk
                    dh = (hn - ho[None, :]).astype(jnp.float64)
                    outc = []
                    for j, kkey in enumerate(objective):
                        dcol = (jnp.sum(ew64[None, :] * dh, axis=1)
                                if kkey == "weighted_hops"
                                else jnp.sum(dh, axis=1))
                        outc.append(base[j] + dcol)
                    return jnp.stack(outc, axis=1)
                stacks = jnp.broadcast_to(acoords[c2r][None],
                                          (k_s, tnum, ncols))
                rowb = jnp.where(bc >= 0, bc, tnum)
                stacks = stacks.at[jnp.arange(k_s), ac].set(acoords[rbc])
                stacks = stacks.at[jnp.arange(k_s), rowb].set(
                    acoords[rac], mode="drop")
                cs2 = jnp.pad(stacks, ((0, nb2 - k_s), (0, 0), (0, 0)))
                return batch_cols(cs2, score_fn2)[:k_s]

            pscores = lax.map(
                chunk_scores,
                (a.reshape(top_s, k_s), b.reshape(top_s, k_s),
                 ra.reshape(top_s, k_s), rb.reshape(top_s, k_s))
            ).reshape(P, nobj)
            pscores = jnp.where(valid[:, None], pscores, jnp.inf)

            # host np.lexsort order: primary = objective[0], ties by gen
            outs = lax.sort(
                tuple(pscores[:, j] + 0.0 for j in range(nobj))
                + (gen, a, ra, rb, b),
                num_keys=nobj, is_stable=True)
            s_srt = jnp.stack(outs[:nobj], axis=1)
            a_s, ra_s, rb_s, b_s = outs[nobj + 1:nobj + 5]

            # greedy disjoint accept, break at first non-improving
            def accept(carry, xs):
                touched, stop = carry
                s_i, ra_i, rb_i = xs
                improving = _lex_less1(s_i, base)
                take = improving & ~stop & ~(touched[ra_i] | touched[rb_i])
                stop = stop | ~improving
                touched = touched.at[ra_i].set(touched[ra_i] | take)
                touched = touched.at[rb_i].set(touched[rb_i] | take)
                return (touched, stop), take

            (_, _), take = lax.scan(
                accept, (jnp.zeros(pnum, bool), jnp.bool_(False)),
                (s_srt, ra_s, rb_s))

            def apply_take(tm):
                ia = jnp.where(tm, a_s, tnum)
                ib = jnp.where(tm & (b_s >= 0), b_s, tnum)
                nc = c2r.at[ia].set(rb_s, mode="drop")
                nc = nc.at[ib].set(ra_s, mode="drop")
                nr = r2c.at[jnp.where(tm, rb_s, pnum)].set(a_s,
                                                           mode="drop")
                nr = nr.at[jnp.where(tm, ra_s, pnum)].set(b_s,
                                                          mode="drop")
                return nc, nr

            nc, nr = apply_take(take)
            combined = full_cols(nc)
            nchosen = jnp.sum(take.astype(jnp.int32))
            # interacting swaps made it worse: fall back to the single
            # best proposal, whose exact score is known to improve
            use_single = (nchosen > 1) & ~_lex_less1(combined, base)
            take1 = (gen == 0) & _lex_less1(s_srt[0], base)
            nc1, nr1 = apply_take(take1)
            c2r_n = jnp.where(use_single, nc1, nc)
            r2c_n = jnp.where(use_single, nr1, nr)
            comb_f = jnp.where(use_single, s_srt[0], combined)
            nchosen_f = jnp.where(use_single, 1, nchosen)
            improved = (nchosen > 0) & _lex_less1(comb_f, base)

            hist_n = jnp.where(improved, hist.at[hist_len].set(comb_f),
                               hist)
            return (rnd + 1, ~improved,
                    jnp.where(improved, c2r_n, c2r),
                    jnp.where(improved, r2c_n, r2c),
                    jnp.where(improved, comb_f, base),
                    hist_n, hist_len + improved.astype(jnp.int32),
                    (acc_t + jnp.where(improved, nchosen_f, 0))
                    .astype(jnp.int32),
                    (ev_t + n_valid).astype(jnp.int32))

        state = (jnp.int32(0), jnp.bool_(False), c2r0, r2c0, base0,
                 hist0, jnp.int32(1), jnp.int32(0), jnp.int32(0))
        state = lax.while_loop(
            lambda st: (st[0] < rounds_eff) & ~st[1], body, state)
        _, _, c2r, _, _, hist, hist_len, acc_t, ev_t = state
        return (best_i, c2r, scores, ok, hist, hist_len, acc_t, ev_t)

    return run


@functools.lru_cache(maxsize=None)
def _program(d_t, task_sfc, d_p, proc_sfc, longest_dim, weighted,
             tnum, pnum, t_sel, p_sel, npts_bt, nbt_b, npts_bp, nbp_b,
             tab_b, cut_bt, cut_bp, bits_t, bits_p, dims, wrap, core_dims,
             objective, traffic, score_kind, ne, ne_b, nb_b, ncols, tile,
             interpret, refine):
    """One jitted fused program per (pipeline knobs, shape bucket).

    Every cache entry sees exactly one input shape set, so the
    ``lru_cache`` hit/miss counters are a truthful compile-count proxy
    (:func:`fused_cache_stats`)."""
    import jax
    return jax.jit(_build(
        d_t=d_t, task_sfc=task_sfc, d_p=d_p, proc_sfc=proc_sfc,
        longest_dim=longest_dim, weighted=weighted, tnum=tnum, pnum=pnum,
        t_sel=t_sel, p_sel=p_sel, npts_bt=npts_bt, nbt_b=nbt_b,
        npts_bp=npts_bp, nbp_b=nbp_b, tab_b=tab_b, cut_bt=cut_bt,
        cut_bp=cut_bp, bits_t=bits_t, bits_p=bits_p, dims=dims, wrap=wrap,
        core_dims=core_dims, objective=objective, traffic=traffic,
        score_kind=score_kind, ne=ne, ne_b=ne_b, nb_b=nb_b, ncols=ncols,
        tile=tile, interpret=interpret, refine=refine))


# registry-backed stat/reset pair (repro.obs); auto-registers with
# ``obs.snapshot()`` under "fused"
fused_cache_stats, reset_fused_cache = obs.instrument_compile_cache(
    "fused", _program)


class FusedSweep:
    """Runs a whole rotation sweep as one compiled device program.

    Constructed by :class:`MappingPipeline` only when the partition
    backend resolved to ``"jax"`` and the score backend resolved to
    ``"jax"``/``"pallas"`` with the batched vectorized sweep.
    :meth:`run` returns the winning :class:`MappingResult` (score and
    winner index filled in) or ``None`` when the stack is ineligible —
    the caller then takes the ordinary unfused path.
    """

    def __init__(self, pipe, score_kind: str):
        self.pipe = pipe
        self.score_kind = score_kind

    def run(self, graph, alloc, task_coords, proc_coords, cands,
            task_weights=None, refine=None):
        """``refine`` (hier only): a ``{"rounds", "top", "degree"}``
        dict folds the bounded swap-refinement loop into the same
        program; the returned result then carries the REFINED
        cluster -> router assignment plus the full ``refine_*`` stats
        the host :func:`repro.hier.refine.refine_swaps` would emit
        (``stats["fused_refine"]`` marks it for the caller)."""
        faults.fire("fused")
        pipe = self.pipe
        cfg = pipe.config
        tc = np.asarray(task_coords, dtype=np.float64)
        pc = np.asarray(proc_coords, dtype=np.float64)
        (tnum, td), (pnum, pd) = tc.shape, pc.shape
        if tnum < pnum or len(cands) < 2:
            return None
        objective = pipe.search.objective
        if not set(objective) <= _KNOWN_KEYS:
            return None
        ne = len(graph.edges)
        if ne == 0:
            return None
        machine = alloc.machine
        traffic = pipe.search.needs_traffic
        kind = self.score_kind
        if (kind == "pallas" and traffic
                and _mapscore.vmem_accumulator_bytes(machine)
                > _mapscore.VMEM_ACC_BUDGET):
            kind = "jax"  # same silent fallback the pallas wrapper takes

        ncand = len(cands)
        ne_b = bucket_size(ne)
        nb_b = bucket_size(ncand, lo=1)
        ncols = machine.ndim
        if 2 * nb_b * ne_b * ncols > MAX_FUSED_ELEMS:
            return None
        if bucket_size(tnum, _pj.PART_BUCKET_MIN) * 8 >= 1 << 31:
            return None  # pragma: no cover - int32 slot-id bound

        # dedup rotations exactly as map_candidates does
        t_perms = [tuple(c.task_perm) if c.task_perm is not None
                   else tuple(range(td)) for c in cands]
        p_perms = [tuple(c.proc_perm) if c.proc_perm is not None
                   else tuple(range(pd)) for c in cands]
        ut = sorted(set(t_perms))
        up = sorted(set(p_perms))
        t_of = {p: i for i, p in enumerate(ut)}
        p_of = {p: i for i, p in enumerate(up)}
        t_sel = tuple(t_of[p] for p in t_perms)
        p_sel = tuple(p_of[p] for p in p_perms)
        task_sfc, proc_sfc = pipe._sfc_pair(td, pd)

        args_t, (npts_bt, nbt_b, tab_b, cut_bt, bits_t) = _pj._prepare(
            tc, pnum, task_sfc, np.array(ut, dtype=np.int64), task_weights,
            cfg.uneven_prime)
        args_p, (npts_bp, nbp_b, _, cut_bp, bits_p) = _pj._prepare(
            pc, pnum, proc_sfc, np.array(up, dtype=np.int64), None,
            cfg.uneven_prime)

        edges = jnp.asarray(
            pad_axis(np.asarray(graph.edges, dtype=np.int32), ne_b))
        w_np = np.ones(ne) if graph.weights is None else \
            np.asarray(graph.weights, dtype=np.float64)
        ew = jnp.asarray(pad_axis(w_np.astype(np.float32), ne_b))
        ew64 = jnp.asarray(pad_axis(w_np, ne_b))  # exact refine deltas
        acoords = jnp.asarray(alloc.coords, dtype=jnp.int32)

        # refinement folds in only for the bijective cluster -> router
        # case (hier always has tnum == pnum here; anything else keeps
        # the host refine_swaps pass, which handles it loosely)
        refine_t = (int(refine["rounds"]), int(refine["top"]),
                    int(refine["degree"])) \
            if refine is not None and tnum == pnum else None

        nd = machine.ndim - machine.core_dims
        tile = min(_mapscore.TILE_MAX, ne_b)
        interpret = not _mapscore._on_tpu()
        if kind == "jax":
            bw = tuple(jnp.asarray(machine.bw_field(k), dtype=jnp.float32)
                       for k in range(nd)) if traffic else ()
        else:
            bw = (jnp.asarray(_mapscore.inv_bandwidth(machine))
                  if traffic else ())

        misses0 = _program.cache_info().misses
        fn = _program(td, task_sfc, pd, proc_sfc, bool(cfg.longest_dim),
                      task_weights is not None, tnum, pnum, t_sel, p_sel,
                      npts_bt, nbt_b, npts_bp, nbp_b, tab_b, cut_bt,
                      cut_bp, bits_t, bits_p,
                      tuple(int(x) for x in machine.dims),
                      tuple(bool(x) for x in machine.wrap),
                      machine.core_dims, tuple(objective), traffic, kind,
                      ne, ne_b, nb_b, ncols, tile, bool(interpret),
                      refine_t)
        miss = _program.cache_info().misses > misses0
        obs.annotate(score_backend=kind, candidates=ncand,
                     compile_cache="miss" if miss else "hit")
        if miss and interpret and kind == "pallas":
            obs.counter("pallas.interpret_compiles")
        out = fn(args_t, args_p, edges, ew, ew64, acoords, bw)
        best_i, t2p, scores, ok = out[:4]
        if not bool(ok):
            return None  # a part got no processor: unfused path raises
        best_i = int(best_i)
        c = cands[best_i]
        best = MappingResult(
            np.asarray(t2p, dtype=np.int32),
            rotation=(tuple(c.task_perm or ()), tuple(c.proc_perm or ())))
        best.score = float(np.asarray(scores)[best_i][0])
        best.stats.update(fused=True, fused_score_backend=kind,
                          winner_index=best_i)
        if refine_t is not None:
            hist, hist_len, acc_t, ev_t = out[4:]
            hist_len = int(hist_len)
            history = [tuple(float(x) for x in row)
                       for row in np.asarray(hist)[:hist_len]]
            best.stats.update(
                fused_refine=True,
                refine_rounds_run=hist_len - 1,
                refine_accepted=int(acc_t),
                refine_evaluated=int(ev_t),
                refine_history=history,
                refine_initial=history[0][0],
                refine_final=history[-1][0])
            best.score = history[-1][0]
            obs.annotate(refine_rounds=hist_len - 1,
                         refine_accepted=int(acc_t))
        return best
