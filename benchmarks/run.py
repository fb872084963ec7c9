"""Benchmark harness: one entry per paper table/figure + the roofline.

Prints ``name,us_per_call,derived`` CSV lines (one per benchmark); with
``--json PATH`` the same records are also written as machine-readable
JSON (name, us_per_call, parsed derived fields) for the bench
trajectory.

  partition        : vectorised vs recursive Multi-Jagged engine
                     (order_points at 2^18 points / 4096 parts) with a
                     bit-identity check and a speedup smoke guard
  candidates       : batched rotation sweep vs the per-candidate loop
                     oracle (2^16 tasks / 24 rotations) with a winner
                     bit-identity check, a speedup smoke guard, the
                     REPRO_SCORE_BACKEND winner-vs-numpy-oracle check
                     and the compile-once-per-(machine, bucket) guard
                     of the bucketed jax scorer
  mapscore         : fused Pallas candidate-scoring kernel vs the jax
                     scorer (ISSUE 4): parity + winner bit-identity vs
                     the numpy oracle (interpret mode on CPU), the
                     jax-vs-pallas wall-clock ratio for the bench
                     trajectory, and a >=5x floor where a TPU is
                     available
  end2end          : cold whole-pipeline ``select_mapping`` with the
                     numpy vs jax partition backend (ISSUE 6): with
                     ``partition_backend="jax"`` + a device scorer the
                     partition -> match -> score -> select chain runs
                     as ONE compiled program per candidate stack;
                     winner bit-identity oracle always, >=3x cold
                     speedup floor on TPU only
  fused_h          : Hilbert + fused refinement one-program cold path
                     (ISSUE 9): cold ``select_mapping`` with
                     ``sfc="H"`` and the device Hilbert state machine,
                     winner bit-identical to the numpy oracle; the
                     hierarchical path's swap refinement folded into
                     the same compiled program, trajectory identical
                     to the host ``refine_swaps``, one compile per
                     (machine, bucket) candidate stack
  serve            : mapping-as-a-service cold vs warm vs coalesced
                     throughput (ISSUE 5): scenario-registry requests
                     through one MappingService — warm responses must
                     be cache hits bit-identical to the cold pipeline
                     pass, coalesced duplicates must match a solo
                     request bit for bit, and the warm path must beat
                     the >=50x latency floor (skipped in --smoke)
  hier             : flat vs hierarchical (coarsen->map->refine) engine
                     on sparse XK7 scenarios — records the flat-vs-hier
                     wall-clock ratio, the ~cores_per_node x engine-pass
                     point reduction, and the quality ratios in the
                     JSON bench trajectory; asserts quality within 5%,
                     monotone refinement and the >=4x speedup floor
  table1_orderings : paper Table 1  (AverageHops of H/Z/FZ/MFZ)
  minighost        : paper Figs. 13-15 (weak scaling, sparse Gemini)
  homme_bgq        : paper Table 2 + Figs. 8-9 (BG/Q 5D torus)
  homme_titan      : paper Figs. 10-12 (sparse Gemini, Z2_1/2/3)
  mapping_tpu      : beyond-paper TPU v5e logical-mesh mapping
  roofline         : deliverable (g) from the dry-run artifacts

``--full`` runs the complete Table 1 (up to 2^20-point rows, ~4 min) and
all scaling points; the default caps sizes for a fast harness pass.
``--smoke`` shrinks the perf-guarded entries to tiny sizes and drops
their speedup floors (constant overheads dominate there) while still
executing every equivalence/quality oracle — the mode CI runs on every
PR for the ``hier`` and ``candidates`` entries.  ``--only`` accepts a
comma-separated list of entry names.
"""

import argparse
import contextlib
import io
import json
import os
import re
import sys
import time

_CSV_LINE = re.compile(r"^([A-Za-z0-9_]+),([0-9.]+),(.*)$")

# Scoring backend the perf-guarded entries run their candidate-search
# passes with ("numpy" | "jax" | "pallas"); CI's pallas smoke job sets
# this to "pallas" so winner-vs-oracle divergence fails the build.
SCORE_BACKEND = os.environ.get("REPRO_SCORE_BACKEND", "numpy")

# Partition backend ("numpy" | "jax") the pipeline-level entries run
# with; CI's pallas smoke job sets this to "jax" so the fused
# whole-pipeline program is exercised against the numpy oracles.
PARTITION_BACKEND = os.environ.get("REPRO_PARTITION_BACKEND", "numpy")


# record key -> obs cache-registry name: the legacy per-record keys the
# bench trajectory already carries, now read from the one telemetry
# registry (repro.obs) instead of four hand-written module imports
_CACHE_KEYS = {"jax": "scorer_jax", "pallas": "scorer_pallas",
               "partition": "partition_jax", "fused": "fused"}


def _cache_stats() -> dict:
    """Current compile-cache counters of the bucketed device engines
    (jax/pallas scorers, jax partitioner, fused whole-pipeline
    programs), for the per-benchmark attribution records.  Backed by
    ``obs.snapshot()`` — absent engines (no jax) are simply missing."""
    from repro import obs
    caches = obs.snapshot().get("caches", {})
    return {rec: caches[name] for rec, name in _CACHE_KEYS.items()
            if name in caches}


def _resolved_backend() -> str:
    try:
        from repro.core.metrics import get_evaluator
        return get_evaluator(SCORE_BACKEND)[0]
    except Exception:  # noqa: BLE001
        return "numpy"


def _resolved_partition() -> str:
    try:
        from repro.core.orderings import resolve_partition_backend
        return resolve_partition_backend(PARTITION_BACKEND)
    except Exception:  # noqa: BLE001
        return "numpy"


def _parse_derived(text: str) -> dict:
    """``k=v;k=v`` derived fields -> dict (floats where they parse;
    trailing speedup ``x`` suffixes stripped)."""
    out = {}
    for item in text.split(";"):
        if "=" not in item:
            if item:
                out[item] = True
            continue
        k, v = item.split("=", 1)
        try:
            out[k] = float(v[:-1] if v.endswith("x") else v)
        except ValueError:
            out[k] = v
    return out


def _run(name, fn, records):
    """Run one benchmark, echo its output, and collect its CSV records.

    Every record additionally carries the requested/resolved scoring
    backend and the compile-cache hit/miss deltas of the bucketed
    scorers accumulated while the benchmark ran, so cross-backend
    trajectory comparisons stay attributable (ISSUE 4) — plus the full
    process telemetry snapshot (``obs.snapshot()``) and a per-span-name
    rollup of every span the benchmark finished (ISSUE 8).  With
    ``REPRO_JAX_PROFILE`` set, each benchmark also runs under a
    ``jax.profiler`` trace named after it.
    """
    from repro import obs

    buf = io.StringIO()
    before = _cache_stats()
    done = obs.finished()
    mark = done[-1].span_id if done else 0  # span ids are monotonic
    t0 = time.perf_counter()
    try:
        with contextlib.redirect_stdout(buf), obs.jax_profile(name):
            fn()
        ok = True
    except Exception as e:  # noqa: BLE001
        dt = (time.perf_counter() - t0) * 1e6
        buf.write(f"{name},{dt:.0f},ERROR:{type(e).__name__}:{e}\n")
        ok = False
    spans = obs.span_rollup(
        s for s in obs.finished() if s.span_id > mark)
    snap = obs.snapshot()
    cache = {}
    for eng, after in _cache_stats().items():
        base = before.get(eng, {})
        # a benchmark may reset the cache mid-run (counters restart at
        # zero); the post-reset absolute count is then the best delta
        cache[eng] = {
            k: (after[k] - base.get(k, 0)
                if after[k] >= base.get(k, 0) else after[k])
            for k in ("hits", "misses")}
    text = buf.getvalue()
    sys.stdout.write(text)
    for line in text.splitlines():
        m = _CSV_LINE.match(line.strip())
        if not m:
            continue
        rec = {"name": m.group(1), "us_per_call": float(m.group(2)),
               "score_backend": SCORE_BACKEND,
               "resolved_backend": _resolved_backend(),
               "partition_backend": PARTITION_BACKEND,
               "resolved_partition": _resolved_partition(),
               "compile_cache": cache,
               "obs": {"snapshot": snap, "spans": spans}}
        derived = m.group(3)
        if derived.startswith("ERROR:"):
            rec["ok"] = False
            rec["error"] = derived[len("ERROR:"):]
        else:
            rec["ok"] = ok
            rec["derived"] = _parse_derived(derived)
        records.append(rec)
    return ok


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="run full-size Table 1 and all scaling points")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes, oracles only (no speedup floors)")
    ap.add_argument("--only", default=None,
                    help="comma-separated benchmark names to run")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write the results as machine-readable JSON")
    args, _ = ap.parse_known_args()
    only = set(args.only.split(",")) if args.only else None

    from repro.compile_cache import configure
    configure()

    from benchmarks import (faults, hier, homme_bgq, homme_titan,
                            mapping_tpu, minighost, roofline, serve,
                            table1_orderings)

    def partition_bench():
        """Vectorised level-synchronous engine vs recursive reference.

        Demonstrates the engine-swap speedup at the ISSUE's pinned size
        (2^18 points / 4096 parts) across task dimensionalities, checks
        the two backends stay bit-identical, and acts as a smoke guard:
        if the vectorised engine ever regresses below the floor the
        harness exits nonzero.
        """
        import numpy as np

        from repro.core.orderings import order_points, order_points_recursive

        n, parts = 1 << 18, 4096
        floor = 10.0 if args.full else 4.0
        fields = []
        best = 0.0
        t_best = 0.0
        for d in (1, 2, 3):
            coords = np.random.default_rng(1).normal(size=(n, d))
            order_points(coords[:4096], 64, "FZ")  # warm the engine path
            tv = min(_time_once(order_points, coords, parts)
                     for _ in range(2))
            t0 = time.perf_counter()
            mu_ref = order_points_recursive(coords, parts, "FZ")
            tr = time.perf_counter() - t0
            assert np.array_equal(
                order_points(coords, parts, "FZ"), mu_ref), \
                f"backends disagree at d={d}"
            speed = tr / max(tv, 1e-9)
            if speed > best:
                best, t_best = speed, tv
            fields.append(f"d{d}_speedup={speed:.1f}x")
        print(f"partition,{t_best*1e6:.0f},n={n};parts={parts};"
              + ";".join(fields) + f";best={best:.1f}x")
        assert best >= floor, (
            f"vectorised partitioner speedup {best:.1f}x below the "
            f"{floor:.0f}x smoke floor")

    def _time_once(fn, coords, parts):
        t0 = time.perf_counter()
        fn(coords, parts, "FZ")
        return time.perf_counter() - t0

    def candidates_bench():
        """Batched rotation sweep vs the per-candidate loop oracle.

        The paper's §4.3 rotation sweep at 2^16 tasks / 24 (task_perm,
        proc_perm) candidates, mapped onto a 4096-node (16,16,16) torus
        (16 tasks per processor) under strict dimension alternation —
        the setting where a rotation IS the cut order, so the sweep is
        the search.  ``map_candidates`` partitions the whole sweep in
        ~2 engine passes over the unique per-side permutations; a
        ``map_candidate`` call per candidate is the
        2-partitions-per-candidate oracle ("loop").  All
        24 mappings must be bit-identical between the paths (hence so
        is the scored winner, asserted too); the speedup floor guards
        the batched path against regression (ISSUE 2: >=5x here).
        """
        import numpy as np

        from repro.core import block_allocation, make_machine, stencil_graph
        from repro.mapping import MappingPipeline, PipelineConfig
        from repro.mapping.candidates import rotation_candidates

        # The ISSUE-2 claim (>=5x at this size) is asserted in --full;
        # the default floor is lowered to 4x purely for scheduling
        # noise, mirroring the partition bench's 4x/10x pattern.
        # --smoke shrinks to 2^12 tasks and drops the floor entirely
        # (constant overheads dominate): only the bit-identity oracles
        # between the batched sweep and the loop run there.
        if args.smoke:
            n, rotations, floor = 1 << 12, 24, None
            graph = stencil_graph((16, 16, 16), torus=False)
        else:
            n, rotations = 1 << 16, 24
            floor = 5.0 if args.full else 4.0
            graph = stencil_graph((64, 32, 32), torus=False)
        machine = make_machine((16, 16, 16), wrap=True)
        alloc = block_allocation(machine)
        tc = graph.coords.astype(np.float64)
        cands = rotation_candidates(3, 3, rotations)
        assert graph.n == n and len(cands) == rotations
        pipe = MappingPipeline(PipelineConfig(
            sfc="FZ", shift=True, rotations=rotations, longest_dim=False,
            score_backend=SCORE_BACKEND,
            partition_backend=PARTITION_BACKEND))
        pc = pipe.machine_coords(alloc)

        def sweep(mode):
            t0 = time.perf_counter()
            if mode == "loop":
                res = [pipe.map_candidate(tc, pc, task_perm=c.task_perm,
                                          proc_perm=c.proc_perm)
                       for c in cands]
            else:
                res = pipe.map_candidates(tc, pc, cands)
            return time.perf_counter() - t0, res

        sweep("batched")  # warm both engine paths at full size once
        t_loop, res_loop = min((sweep("loop") for _ in range(2)),
                               key=lambda tr: tr[0])
        # best-of-N with early stop: a single descheduled window must
        # not fail the floor, so keep sampling until the ISSUE-2 claim
        # (or a higher configured floor) holds or the budget runs out
        target = max(floor or 0.0, 5.0)
        t_bat, res_bat = sweep("batched")
        for _ in range(0 if floor is None else 5):
            if t_loop / t_bat >= target:
                break
            t2, r2 = sweep("batched")
            if t2 < t_bat:
                t_bat, res_bat = t2, r2
        for rl, rb in zip(res_loop, res_bat):
            assert np.array_equal(rl.task_to_proc, rb.task_to_proc), \
                "batched sweep mapping differs from the loop oracle"
        best_l, i_l, _ = pipe.search.best(graph, alloc, res_loop)
        best_b, i_b, _ = pipe.search.best(graph, alloc, res_bat)
        assert i_l == i_b and np.array_equal(best_l.task_to_proc,
                                             best_b.task_to_proc), \
            "scored winner differs between sweep modes"

        # REPRO_SCORE_BACKEND winner oracle: the searches above scored
        # with the env backend, so re-score with numpy and require the
        # SAME winner as its lexsort order (ISSUE 4 — CI's pallas smoke
        # job fails here on divergence)
        from repro.core.metrics import evaluate_candidates
        from repro.mapping import CandidateSearch
        if SCORE_BACKEND != "numpy":
            s_np = CandidateSearch("weighted_hops", backend="numpy")
            bn, i_np, _ = s_np.best(graph, alloc, res_bat)
            assert i_np == i_b and np.array_equal(bn.task_to_proc,
                                                  best_b.task_to_proc), \
                (f"{SCORE_BACKEND} winner rot{i_b} diverges from the "
                 f"numpy oracle rot{i_np}")

        # compile-once-per-(machine, bucket) guard: two message counts
        # sharing a bucket must reuse ONE compiled jax scorer — the
        # recompile-storm fix the bucketing exists for.  Skipped (not
        # failed) on numpy-only installs, where scoring falls back
        # silently and there is no compile cache to guard.
        try:
            from repro.core import metrics_jax
        except Exception:  # noqa: BLE001 - jax optional
            metrics_jax = None
        cst = {"misses": 0, "hits": 0}
        if metrics_jax is not None:
            stack4 = np.stack([alloc.coords[r.task_to_proc]
                               for r in res_bat[:4]])
            ne = len(graph.edges)
            ne2 = max(metrics_jax.bucket_size(ne) // 2 + 1, ne - 64)
            metrics_jax.reset_scorer_cache()
            for cut in (ne, ne2):
                evaluate_candidates(machine, graph.edges[:cut],
                                    graph.weights[:cut], stack4,
                                    backend="jax")
            cst = metrics_jax.scorer_cache_stats()
            assert cst["misses"] == 1 and cst["hits"] >= 1, (
                f"jax scorer recompiled within one (machine, bucket): "
                f"{cst}")

        speed = t_loop / max(t_bat, 1e-9)
        print(f"candidates,{t_bat*1e6:.0f},n={n};rotations={rotations};"
              f"loop_us={t_loop*1e6:.0f};speedup={speed:.1f}x;"
              f"winner=rot{i_b};winner_identical=1;"
              f"score_backend={_resolved_backend()};"
              f"jax_cache_misses={cst['misses']};"
              f"jax_cache_hits={cst['hits']}")
        assert floor is None or speed >= floor, (
            f"batched candidate sweep speedup {speed:.1f}x below the "
            f"{floor:.0f}x smoke floor")

    def mapscore_bench():
        """Fused Pallas scoring kernel vs the bucketed jax scorer.

        Scores the ``candidates``-style rotation sweep (traffic
        objective, so the dimension-ordered router runs) with both
        accelerator backends and the numpy oracle.  The pallas winners
        must be bit-identical to the numpy lexsort order and every
        metric must agree within fp tolerance.  The jax-vs-pallas
        wall-clock ratio lands in the JSON records for the bench
        trajectory; the >=5x floor (ISSUE 4, 2^16 tasks) is enforced
        only where a TPU is available — on CPU the kernel runs in
        interpret mode (``interpret=1`` in the record) on a capped
        subproblem purely as a parity/winner oracle.
        """
        import numpy as np

        try:  # accelerator-only entry: SKIP (not fail) on numpy-only
            import jax
            from repro.core import metrics_jax
            from repro.kernels.mapscore import ops as mapscore_ops
        except Exception:  # noqa: BLE001 - jax optional
            print("mapscore,0,skipped=no_jax")
            return
        from repro.core import block_allocation, make_machine, stencil_graph
        from repro.core.metrics import evaluate_candidates
        from repro.mapping import MappingPipeline, PipelineConfig
        from repro.mapping.candidates import rotation_candidates

        on_tpu = jax.default_backend() == "tpu"
        if args.smoke:
            shape, rotations = (16, 16, 16), 12            # 2^12 tasks
        elif args.full or on_tpu:
            shape, rotations = (64, 32, 32), 24            # 2^16 (ISSUE 4)
        else:
            shape, rotations = (32, 32, 16), 24            # 2^14 capped
        machine = make_machine((16, 16, 16), wrap=True)
        alloc = block_allocation(machine)
        graph = stencil_graph(shape, torus=False)
        pipe = MappingPipeline(PipelineConfig(
            sfc="FZ", shift=True, rotations=rotations, longest_dim=False))
        pc = pipe.machine_coords(alloc)
        cands = rotation_candidates(3, 3, rotations)
        res = pipe.map_candidates(graph.coords.astype(np.float64), pc,
                                  cands)
        stack = np.stack([alloc.coords[r.task_to_proc] for r in res])
        edges, w = graph.edges, graph.weights

        def timed(backend, stk, e, wt):
            t0 = time.perf_counter()
            ev = evaluate_candidates(machine, e, wt, stk, traffic=True,
                                     backend=backend)
            return time.perf_counter() - t0, ev

        # jax timing on the full problem (warm the compile first)
        timed("jax", stack, edges, w)
        t_jax = min(timed("jax", stack, edges, w)[0] for _ in range(2))

        # pallas: full problem on TPU; capped interpret-mode subproblem
        # on CPU (the parity/winner oracle, not a meaningful timing)
        if on_tpu:  # pragma: no cover - no TPU in this container
            p_stack, p_edges, p_w = stack, edges, w
        else:
            ncap, ecap = 8, 8192
            p_stack = stack[:ncap]
            p_edges, p_w = edges[:ecap], w[:ecap]
        timed("pallas", p_stack, p_edges, p_w)  # warm
        t_pal, ev_pal = timed("pallas", p_stack, p_edges, p_w)
        timed("jax", p_stack, p_edges, p_w)  # warm the capped shapes too
        t_jax_p, _ = timed("jax", p_stack, p_edges, p_w)
        _, ev_np = timed("numpy", p_stack, p_edges, p_w)

        for key in ev_np:
            assert np.allclose(ev_np[key], ev_pal[key], rtol=1e-4,
                               atol=1e-4), \
                f"pallas {key} diverges from the numpy oracle"
        for objective in (("weighted_hops",),
                          ("latency_max", "weighted_hops")):
            keys_np = tuple(ev_np[k] for k in reversed(objective))
            keys_pl = tuple(ev_pal[k] for k in reversed(objective))
            w_np = int(np.lexsort(keys_np)[0])
            w_pl = int(np.lexsort(keys_pl)[0])
            assert w_np == w_pl, (
                f"pallas winner {w_pl} != numpy oracle {w_np} for "
                f"{objective}")

        ratio = t_jax_p / max(t_pal, 1e-9)
        jst = metrics_jax.scorer_cache_stats()
        pst = mapscore_ops.scorer_cache_stats()
        print(f"mapscore,{t_pal*1e6:.0f},n={graph.n};"
              f"rotations={rotations};nmsg={len(edges)};"
              f"jax_full_us={t_jax*1e6:.0f};"
              f"pallas_nmsg={len(p_edges)};pallas_ncand={len(p_stack)};"
              f"jax_vs_pallas={ratio:.2f}x;"
              f"interpret={0 if on_tpu else 1};winner_identical=1;"
              f"score_backend={_resolved_backend()};"
              f"jax_cache_misses={jst['misses']};"
              f"jax_cache_hits={jst['hits']};"
              f"pallas_cache_misses={pst['misses']};"
              f"pallas_cache_hits={pst['hits']}")
        if on_tpu:  # pragma: no cover - floor only where it means something
            floor = 5.0 if args.full else 4.0
            assert ratio >= floor, (
                f"pallas scorer speedup {ratio:.1f}x below the "
                f"{floor:.0f}x floor vs the jax backend")

    def end2end_bench():
        """Cold whole-pipeline ``select_mapping``: numpy vs jax
        partition backend (ISSUE 6).

        Times the mesh builder's full candidate search — transforms,
        batched rotation sweeps, scoring, outer selection — with the
        host partitioner vs ``partition_backend="jax"``, where the
        partition -> match -> score -> select chain of every pipeline
        pass runs as ONE compiled program (:mod:`repro.mapping.fused`).
        Compile caches are warmed first, so "cold" is the honest
        no-result-cache request path the serve layer pays per new
        problem.  The winner must be bit-identical between backends
        (the jax partitioner's permutations equal numpy's bit for bit;
        the score columns are the same f32-derived values).  The >=3x
        speedup floor is enforced only on TPU — on CPU the entry is a
        correctness oracle and the ratio simply lands in the JSON
        trajectory.
        """
        import numpy as np

        try:  # accelerator-only entry: SKIP (not fail) on numpy-only
            import jax
            from repro.core import partition_jax
            from repro.mapping import fused as fused_mod
        except Exception:  # noqa: BLE001 - jax optional
            print("end2end,0,skipped=no_jax")
            return
        from repro.core import (block_allocation, logical_mesh_graph,
                                tpu_v5e_pod)
        from repro.meshmap.device_mesh import select_mapping

        on_tpu = jax.default_backend() == "tpu"
        if args.smoke:
            side = 64                      # 2^12 tasks
        elif args.full:
            side = 512                     # 2^18 (ISSUE 6 upper size)
        else:
            side = 256                     # 2^16 (ISSUE 6 default size)
        machine = tpu_v5e_pod(side=side)
        alloc = block_allocation(machine)
        graph = logical_mesh_graph((side, side), (8.0, 64.0),
                                   ("data", "model"))
        ab = [8.0, 64.0]
        rotations = 4
        # device scoring is what the fused program needs; respect the
        # env override, but never run interpret-mode pallas at full
        # sweep sizes off-TPU outside --smoke (hours, not seconds)
        sb = SCORE_BACKEND if SCORE_BACKEND != "numpy" else "jax"
        if sb == "pallas" and not on_tpu and not args.smoke:
            sb = "jax"

        def cold(pb, score):
            t0 = time.perf_counter()
            best, _, _ = select_mapping(graph, alloc, ab,
                                        rotations=rotations,
                                        partition_backend=pb,
                                        score_backend=score)
            return time.perf_counter() - t0, best

        cold("numpy", "numpy")  # warm the numpy pipelines
        cold("jax", sb)         # compile the fused programs once
        t_np, best_np = min((cold("numpy", "numpy") for _ in range(2)),
                            key=lambda tb: tb[0])
        t_jx, best_jx = min((cold("jax", sb) for _ in range(2)),
                            key=lambda tb: tb[0])
        identical = np.array_equal(best_np.task_to_proc,
                                   best_jx.task_to_proc)
        assert identical, (
            "jax-partition select_mapping winner differs from the "
            "numpy oracle")

        # tracing-overhead oracle (ISSUE 8): spans are always on, so
        # what can regress is the EXPORT path — re-run the numpy cold
        # path with the JSONL sink armed and bound the slowdown (the
        # compare.py ceiling is 2%); best-of-N with early stop so one
        # descheduled window cannot fail the oracle
        import tempfile

        from repro import obs
        fd, trace_path = tempfile.mkstemp(suffix=".jsonl")
        os.close(fd)
        sink = obs.JsonlSink(trace_path)

        def cold_traced():
            obs.add_sink(sink)
            try:
                return cold("numpy", "numpy")[0]
            finally:
                obs.remove_sink(sink)

        try:
            t_tr = min(cold_traced() for _ in range(2))
            overhead = t_tr / max(t_np, 1e-9)
            for _ in range(4):
                if overhead <= 1.02:
                    break
                t_np = min(t_np, cold("numpy", "numpy")[0])
                t_tr = min(t_tr, cold_traced())
                overhead = t_tr / max(t_np, 1e-9)
            with open(trace_path) as f:
                nevents = sum(1 for _ in f)
        finally:
            sink.close()
            os.unlink(trace_path)
        assert nevents > 0, "traced cold path exported no span events"

        pst = partition_jax.partition_cache_stats()
        fst = fused_mod.fused_cache_stats()
        speed = t_np / max(t_jx, 1e-9)
        print(f"end2end,{t_jx*1e6:.0f},n={graph.n};"
              f"rotations={rotations};numpy_us={t_np*1e6:.0f};"
              f"speedup={speed:.2f}x;winner_identical=1;"
              f"trace_overhead={overhead:.3f};trace_events={nevents};"
              f"partition_backend={_resolved_partition() if PARTITION_BACKEND != 'numpy' else 'jax'};"
              f"score_backend={sb};interpret={0 if on_tpu else 1};"
              f"partition_cache_misses={pst['misses']};"
              f"partition_cache_hits={pst['hits']};"
              f"fused_cache_misses={fst['misses']};"
              f"fused_cache_hits={fst['hits']}")
        if on_tpu:  # pragma: no cover - floor only where it means something
            assert speed >= 3.0, (
                f"fused on-device pipeline speedup {speed:.2f}x below "
                f"the 3x floor vs the host partitioner")

    def fused_h_bench():
        """Hilbert + fused refinement: the ISSUE-9 one-program cold
        path.

        Part 1 — cold flat ``select_mapping`` with ``sfc="H"`` and the
        jax partition backend: the device Hilbert state machine
        (Skilling's transpose) feeds the fused program, and the winner
        must be bit-identical to the all-numpy Hilbert oracle.  Part 2
        — node-level hierarchy: the bounded greedy swap refinement
        folds into the SAME compiled program; the refine trajectory
        must equal the host ``refine_swaps`` decision-for-decision
        (monotone), and the fused compile-cache counters must show one
        program per (machine, bucket) candidate stack — a repeat run
        may only add hits.  No speedup floor: this entry is a
        correctness + compile-accounting oracle; the cold timings land
        in the JSON trajectory.
        """
        import numpy as np

        try:  # accelerator-only entry: SKIP (not fail) on numpy-only
            import jax
            from repro.mapping import fused as fused_mod
        except Exception:  # noqa: BLE001 - jax optional
            print("fused_h,0,skipped=no_jax")
            return
        from repro.core import (block_allocation, gemini_xk7,
                                logical_mesh_graph, sfc_allocation,
                                stencil_graph, tpu_v5e_pod)
        from repro.mapping import (HierarchySpec, MappingPipeline,
                                   PipelineConfig)
        from repro.meshmap.device_mesh import select_mapping

        on_tpu = jax.default_backend() == "tpu"
        if args.smoke:
            side, n, dims, cores = 32, 1 << 8, (8, 4, 4), 4
        elif args.full:
            side, n, dims, cores = 256, 1 << 14, (32, 16, 16), 8
        else:
            side, n, dims, cores = 128, 1 << 12, (16, 16, 8), 4
        sb = SCORE_BACKEND if SCORE_BACKEND != "numpy" else "jax"
        if sb == "pallas" and not on_tpu and not args.smoke:
            sb = "jax"

        # part 1: cold flat select_mapping, device Hilbert vs host
        machine = tpu_v5e_pod(side=side)
        alloc = block_allocation(machine)
        graph = logical_mesh_graph((side, side), (8.0, 64.0),
                                   ("data", "model"))
        ab = [8.0, 64.0]

        def cold(pb, score):
            t0 = time.perf_counter()
            best, _, _ = select_mapping(graph, alloc, ab, rotations=4,
                                        sfc="H", partition_backend=pb,
                                        score_backend=score)
            return time.perf_counter() - t0, best

        cold("numpy", "numpy")  # warm the numpy pipelines
        cold("jax", sb)         # compile the fused Hilbert programs
        t_np, best_np = min((cold("numpy", "numpy") for _ in range(2)),
                            key=lambda tb: tb[0])
        t_jx, best_jx = min((cold("jax", sb) for _ in range(2)),
                            key=lambda tb: tb[0])
        assert np.array_equal(best_np.task_to_proc,
                              best_jx.task_to_proc), (
            "device-Hilbert select_mapping winner differs from the "
            "numpy oracle")

        # part 2: fused refinement vs the host refine_swaps trajectory
        e = n.bit_length() - 1
        a = e // 3
        g = stencil_graph((1 << (e - 2 * a), 1 << a, 1 << a))
        m2 = gemini_xk7(dims=dims, cores_per_node=cores)
        alloc2 = sfc_allocation(m2, n, nfragments=2, seed=3)
        kw = dict(sfc="H", rotations=6,
                  hierarchy=HierarchySpec.node())
        pipe_jx = MappingPipeline(PipelineConfig(
            partition_backend="jax", score_backend=sb, **kw))

        fused_mod.reset_fused_cache()
        t0 = time.perf_counter()
        rj = pipe_jx.map(g, alloc2)
        t_h_cold = time.perf_counter() - t0
        fst = fused_mod.fused_cache_stats()
        compiles = fst["misses"]
        assert compiles == fst["entries"], (
            "fused cache entries != compiles")
        t0 = time.perf_counter()
        rj2 = pipe_jx.map(g, alloc2)
        t_h_warm = time.perf_counter() - t0
        fst = fused_mod.fused_cache_stats()
        assert fst["misses"] == compiles and fst["hits"] >= compiles, (
            "repeat fused Hilbert run recompiled: one program per "
            "(machine, bucket) candidate stack is the contract")
        t0 = time.perf_counter()
        rn = MappingPipeline(PipelineConfig(**kw)).map(g, alloc2)
        t_h_np = time.perf_counter() - t0

        assert rj.stats.get("fused_refine") is True, (
            "hierarchical fused path did not refine on device")
        assert np.array_equal(rj.task_to_proc, rn.task_to_proc), (
            "fused-refinement mapping differs from the host pipeline")
        assert np.array_equal(rj.task_to_proc, rj2.task_to_proc)
        assert rj.stats["refine_history"] == rn.stats["refine_history"], (
            "fused refine trajectory != host refine_swaps")
        hist = [h[0] for h in rj.stats["refine_history"]]
        assert all(y <= x + 1e-9 for x, y in zip(hist, hist[1:])), (
            "fused refinement worsened the objective")

        print(f"fused_h,{t_jx*1e6:.0f},n={graph.n};hier_n={n};"
              f"numpy_us={t_np*1e6:.0f};"
              f"speedup={t_np/max(t_jx, 1e-9):.2f}x;"
              f"hier_cold_us={t_h_cold*1e6:.0f};"
              f"hier_warm_us={t_h_warm*1e6:.0f};"
              f"hier_numpy_us={t_h_np*1e6:.0f};"
              f"winner_identical=1;refine_identical=1;"
              f"refine_monotone=1;compile_once=1;"
              f"fused_compiles={compiles};"
              f"refine_rounds={rj.stats['refine_rounds_run']};"
              f"refine_accepted={rj.stats['refine_accepted']};"
              f"score_backend={sb};interpret={0 if on_tpu else 1}")

    def serve_bench():
        """Mapping-as-a-service: cold vs warm vs coalesced (ISSUE 5).

        Every mode runs the full oracle set (warm/coalesced results
        bit-identical to the cold pipeline pass, exact status
        accounting); the >=50x warm-path floor is enforced at the
        default scale and above, where the cold pipeline pass is long
        enough for the ratio to mean something.
        """
        if args.full:
            serve.main()  # 2^14-scale scenarios
            return
        scale = (1 << 9) if args.smoke else (1 << 12)
        results = serve.run(scale=scale, quiet=True,
                            check_speed=not args.smoke)
        t = results["t_warm_s"] / results["nscenarios"]
        print(f"serve,{t*1e6:.0f},{serve.headline(results)}")

    def faults_bench():
        """Resilience under injected faults (ISSUE 7).

        Replays the standard single-fault schedules (scorer compile
        failure, device OOM, partition failure, slow stage + deadline,
        eviction storm) through MappingService and asserts the
        availability/quality oracles: zero surfaced errors, every
        schedule served on its expected degradation-ladder rung,
        degraded results bit-identical to the healthy path, and the
        no-fault pass identical to the direct (fused) pipeline with
        every breaker closed.  Oracles run at every size — there is no
        perf floor here, availability IS the product.
        """
        if args.full:
            faults.main()  # 2^14-scale scenarios
            return
        scale = (1 << 9) if args.smoke else (1 << 12)
        results = faults.run(scale=scale, quiet=True)
        print(f"faults,{results['t_faulted_s']*1e6:.0f},"
              f"{faults.headline(results)}")

    def hier_bench():
        """Flat vs hierarchical (coarsen -> map -> refine) engine.

        Runs both sparse-XK7 scenarios of benchmarks/hier.py at depth
        2, 3 and 4; every pass asserts the quality budgets (depth-2
        within 5% of flat, depth-3 within 5% of depth-2), monotone
        refinement/polish at every level, core-level bijection and the
        per-level engine-pass point reduction oracles.  The speed
        floors (>=4x flat vs depth-2, ISSUE 3; depth-3 at least
        matching depth-2 wall-clock, ISSUE 10) are enforced at 2^18+
        tasks — ``--smoke`` runs 2^14 tasks where constant overheads
        dominate, so only the oracles run there.  The ``flat_vs_hier``
        / ``d3_vs_d2`` / ``wh_ratio_d3`` derived fields land in the
        JSON records so the bench trajectory tracks engine scaling.
        """
        if args.full:
            hier.main()  # 2^20 tasks / 64K+ allocated nodes
            return
        n = (1 << 14) if args.smoke else (1 << 18)
        results = hier.run(n=n, quiet=True, check_speed=not args.smoke)
        t = results["scenarios"][hier.SCENARIOS[0][0]]["t_node_s"]
        print(f"hier,{t*1e6:.0f},{hier.headline(results)}")

    def table1():
        if args.full:
            table1_orderings.main()
        else:
            t0 = time.perf_counter()
            results, worst = table1_orderings.run(max_tasks=65536,
                                                  quiet=True)
            dt = (time.perf_counter() - t0) * 1e6 / max(len(results), 1)
            print(f"table1_orderings,{dt:.0f},"
                  f"rows={len(results)};max_rel_err_vs_paper_ZFZMFZ="
                  f"{worst:.4f}")

    def mini():
        if args.full:
            minighost.main()
        else:
            t0 = time.perf_counter()
            res = minighost.run(core_counts=(8192, 32768), seeds=(0,),
                                quiet=True)
            h = minighost.headline(res)
            dt = (time.perf_counter() - t0) * 1e6 / len(res)
            print(f"minighost,{dt:.0f},"
                  f"lat_red_vs_default="
                  f"{h['latency_reduction_vs_default']:.2f}"
                  f";geo_growth={h['geo_hops_growth_weak_scaling']:.2f}")

    def bgq():
        if args.full:
            homme_bgq.main()
        else:
            t0 = time.perf_counter()
            res = homme_bgq.run(rank_counts=(8192,), quiet=True)
            best = min((v["data_vs_sfc"], k)
                       for k, v in res[8192].items()
                       if not k.startswith("_"))
            dt = (time.perf_counter() - t0) * 1e6
            print(f"homme_bgq,{dt:.0f},best_data_vs_sfc={best[0]:.3f}"
                  f";variant={best[1]}")

    def titan():
        if args.full:
            homme_titan.main()
        else:
            t0 = time.perf_counter()
            res = homme_titan.run(rank_counts=(10800,), seeds=(0,),
                                  quiet=True)
            z = res[10800]["Z2_2"]
            dt = (time.perf_counter() - t0) * 1e6
            print(f"homme_titan,{dt:.0f},z2_2_wh_vs_sfc={z['WH']:.3f}"
                  f";z2_2_lat_vs_sfc={z['Latency']:.3f}")

    benches = {
        "partition": partition_bench,
        "candidates": candidates_bench,
        "mapscore": mapscore_bench,
        "end2end": end2end_bench,
        "fused_h": fused_h_bench,
        "serve": serve_bench,
        "faults": faults_bench,
        "hier": hier_bench,
        "table1_orderings": table1,
        "minighost": mini,
        "homme_bgq": bgq,
        "homme_titan": titan,
        "mapping_tpu": mapping_tpu.main,
        "roofline": roofline.main,
    }
    ok = True
    records = []
    for name, fn in benches.items():
        if only is not None and name not in only:
            continue
        ok = _run(name, fn, records) and ok
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"benchmarks": records, "full": bool(args.full),
                       "smoke": bool(args.smoke)},
                      f, indent=2, sort_keys=True)
        print(f"[run] wrote {len(records)} records to {args.json}")
    sys.exit(0 if ok else 1)


if __name__ == '__main__':
    main()
