#!/usr/bin/env python3
"""Run one cell of the chip benchmark and print its result line.

    python3 benchmarks/chip/run_cell.py --workload NAME --seed N \\
        --seconds S --trace 0|1

From the root of a checkout, in this order:

1. find the chips the cell asks for, or exit non-zero with no result;
2. keep JAX's persistent compile cache in ``<checkout>/.jax_cache``
   (through the program's ``repro.compile_cache.configure``);
3. build the cell's deployment from its files and warm its shapes up
   by serving ``warmup_requests`` requests (set-up ends here);
4. serve new requests one after another, one client, each a new job,
   through ``MappingService.map`` until ``--seconds`` of serving have
   passed; with ``--trace 1`` under the profiler, for at most
   ``TRACE_SECONDS`` (a longer trace of the ``node`` cell overflows the
   profiler's buffer, which drops device events in silence);
5. compare a sample of the window's answers, drawn from the seed, with
   the plain reference, and print the numbers compared beside their
   limits on standard error, then the result line on standard output.

The metrics are read by files of their own: ``endtoend/<name>.py`` for
``BENCHMARK.json``'s ``end_to_end`` entries (``--trace 0``) and
``layers/<name>.py`` for its ``per_layer`` entries (``--trace 1``).
Each defines ``read(run)`` and returns a number, or ``None`` when the
run holds nothing for it to read.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import numpy as np  # noqa: E402

import checks  # noqa: E402
import workload  # noqa: E402

CACHE_DIR = os.path.join(ROOT, ".jax_cache")
COMPILE_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
TRACE_SECONDS = 10.0


class NoChip(Exception):
    """The machine lacks the chips the cell asks for."""


@dataclasses.dataclass
class Run:
    """What a run measured; the metric readers read it."""

    cell: workload.Cell
    deployment: workload.Deployment
    seed: int
    setup_s: float
    latencies_s: list
    failed: int
    device_kind: str
    spans: list = dataclasses.field(default_factory=list)
    planes: list | None = None
    window_ns: tuple | None = None

    @property
    def requests(self) -> int:
        return len(self.latencies_s)


def reader(kind: str, name: str, here: str = HERE):
    """``read`` of ``<here>/<kind>/<name>.py``."""
    path = os.path.join(here, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"chip_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def find_devices(chips: int):
    import jax

    devices = jax.devices()
    dev = devices[0]
    print(f"device platform={dev.platform} device_kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    if dev.platform != "tpu":
        raise NoChip(f"no TPU: JAX found {dev.platform}")
    if len(devices) < chips:
        raise NoChip(f"{chips} chips asked, {len(devices)} found")
    return devices


class CompileCount:
    """Programs lowered for compilation while it is open."""

    def __init__(self):
        self.n = 0

    def _listen(self, name, secs, **kw):
        if name == COMPILE_EVENT:
            self.n += 1

    def __enter__(self):
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc):
        from jax import monitoring
        monitoring.unregister_event_duration_listener(self._listen)


def _entries(path: str) -> int:
    return len(os.listdir(path)) if os.path.isdir(path) else 0


class GcPauses:
    """Collections of the garbage collector while it is open, and how
    long each took."""

    def __init__(self):
        self.pauses = []
        self._t0 = None

    def _cb(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._t0))

    def __enter__(self):
        import gc
        gc.callbacks.append(self._cb)
        return self

    def __exit__(self, *exc):
        import gc
        gc.callbacks.remove(self._cb)


def serve_window(program, dep, seed, first, seconds, obs):
    """Closed loop, one client: request after request until ``seconds``
    of serving have passed.  Building the next job's allocation is the
    client's work and is not counted.  Returns each request's latency,
    its start from the window's start, the answers and the failures."""
    latencies, starts, answers, failed = [], [], {}, 0
    index = first
    t_window = time.perf_counter()
    while sum(latencies) < seconds:
        with obs.span("bench.client", index=index):
            req = program.request(dep.allocation(seed, index))
        t0 = time.perf_counter()
        try:
            answers[index] = program.serve(req)
        except Exception as e:  # a request that never comes
            failed += 1
            print(f"request {index} failed: {type(e).__name__}: {e}",
                  file=sys.stderr, flush=True)
        latencies.append(time.perf_counter() - t0)
        starts.append(t0 - t_window)
        index += 1
    return latencies, starts, answers, failed


def report_requests(latencies, starts, spans, pauses) -> None:
    """Each request's start and latency, the phases of the slowest
    three, and the collector's pauses, on standard error: where a slow
    request went."""
    print("requests (start s, latency s): " + " ".join(
        f"{s:.3f}/{x:.4f}" for s, x in zip(starts, latencies)),
        file=sys.stderr)
    roots = [s for s in spans if s.name == "serve.request"]
    for i in sorted(range(len(latencies)), key=lambda i: -latencies[i])[:3]:
        if i >= len(roots):
            break
        phases: dict = {}
        for s in spans:
            if s.trace_id == roots[i].trace_id and s.t1 is not None:
                phases[s.name] = phases.get(s.name, 0.0) + (s.t1 - s.t0)
        print(f"request {i} ({latencies[i]:.4f} s): " + ", ".join(
            f"{k} {v:.4f}" for k, v in sorted(phases.items(),
                                              key=lambda kv: -kv[1])),
            file=sys.stderr)
    slow = [p for p in pauses if p[1] >= 0.01]
    print(f"collector: {len(pauses)} collections in the window, "
          f"{sum(p[1] for p in pauses):.4f} s; of 10 ms or more: "
          + (", ".join(f"gen {g} {t:.4f} s" for g, t in slow) or "none"),
          file=sys.stderr, flush=True)


def check_sample(cell, dep, seed, first, n, answers) -> dict:
    """Reference answers for a sample of the window's requests."""
    import refmap

    k = min(int(cell.mix["checked_requests"]), n)
    rng = workload.request_rng(seed, 0, "check")
    rows = []
    for i in sorted(rng.choice(n, size=k, replace=False)):
        index = first + int(i)
        if index not in answers:
            continue  # counted in failed_requests
        ref = refmap.reference_map(dep, dep.allocation(seed, index),
                                   cell.mix)
        rows.append(checks.compare(answers[index], ref,
                                   cell.mix["hierarchy"]))
    return checks.worst(rows)


def run(cell: workload.Cell, seed: int, seconds: float, trace: bool,
        devices, cache: str) -> dict:
    """Set up, warm up, serve the window, check; the result line.
    ``cache`` is the persistent compile cache's directory."""
    from program import Program
    from repro import obs

    entries0 = _entries(cache)
    print(f"compile cache {cache}: {entries0} entries at start", flush=True)

    dep = workload.Deployment(cell.config)
    program = Program(dep, cell.mix)
    warm = int(cell.mix["warmup_requests"])
    for index in range(warm):
        program.serve(program.request(dep.allocation(seed, index)))
    setup_s = time.perf_counter() - T_START
    new = _entries(cache) - entries0
    print(f"set-up {setup_s:.3f} s: {warm} warm-up requests, {new} new "
          f"compile-cache entries; cache held the cell's programs at "
          f"start: {'yes' if new == 0 else 'no'}", flush=True)

    misses0 = program.compile_misses()
    spans, planes, window_ns = [], None, None
    logdir = tempfile.mkdtemp(prefix="chip_trace_") if trace else None
    try:
        with CompileCount() as compiles, GcPauses() as gc_pauses:
            obs.add_sink(spans.append)
            if trace:
                import jax
                jax.profiler.start_trace(logdir)
            try:
                t_window = time.perf_counter()
                with _annotation(trace):
                    latencies, starts, answers, failed = serve_window(
                        program, dep, seed, warm,
                        min(seconds, TRACE_SECONDS) if trace else seconds,
                        obs)
            finally:
                if trace:
                    jax.profiler.stop_trace()
                obs.remove_sink(spans.append)
        if trace:
            import xtrace
            planes = xtrace.load(logdir)
            window_ns = xtrace.window(planes)
            xtrace.check_complete(planes, window_ns, max(latencies))
    finally:
        if logdir:
            shutil.rmtree(logdir, ignore_errors=True)
    lru = program.compile_misses() - misses0
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices[:cell.chips])
    n = len(latencies)
    degraded = sum(1 for a in answers.values() if a["degraded"])
    repeats = sum(1 for a in answers.values() if a["status"] != "cold")
    unfused = sum(1 for a in answers.values() if not a["fused"])
    q = np.quantile(latencies, [0.0, 0.25, 0.5, 0.75, 1.0])
    print(f"window: {n} requests in {sum(latencies):.3f} s served, "
          f"{failed} failed, {repeats} not cold, {degraded} degraded, "
          f"{unfused} unfused; "
          f"compiles in the window {compiles.n} (compile-cache misses "
          f"{lru}); latency min/q1/median/q3/max "
          + "/".join(f"{x:.4f}" for x in q) + " s", flush=True)
    report_requests(latencies, starts, spans, gc_pauses.pauses)

    # a run's spans are put on the trace's clock by the window's start
    offset = (window_ns[0] - t_window * 1e9) if trace else 0.0
    rec = Run(cell, dep, seed, setup_s, latencies, failed,
              devices[0].device_kind,
              spans=[{"name": s.name, "id": s.span_id,
                      "parent": s.parent_id,
                      "start_ns": s.t0 * 1e9 + offset,
                      "end_ns": s.t1 * 1e9 + offset} for s in spans],
              planes=planes, window_ns=window_ns)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        kind = "layers" if trace else "endtoend"
        value = reader(kind, m["name"])(rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    breakdown = None
    if trace:
        import readings
        device.update(readings.busy_window(rec))
        breakdown = readings.breakdown(rec)

    t_check = time.perf_counter()
    numbers = check_sample(cell, dep, seed, warm, n, answers)
    print(f"check: {int(cell.mix['checked_requests'])} of {n} requests "
          f"compared with the reference in "
          f"{time.perf_counter() - t_check:.3f} s", flush=True)
    numbers["failed_requests"] = failed
    numbers["degraded_requests"] = degraded
    numbers["window_compiles"] = compiles.n + lru
    numbers["repeat_requests"] = repeats
    correct, checked = checks.verdict(numbers)
    for k, c in checked.items():
        print(f"check {k} = {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    out = {"correct": bool(correct), "attempted": n, "failed": failed,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checked
    return out


def _annotation(trace: bool):
    import contextlib
    if not trace:
        return contextlib.nullcontext()
    import jax
    import xtrace
    return jax.profiler.TraceAnnotation(xtrace.WINDOW)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = workload.resolve_cell(workload.load_benchmark(ROOT),
                                 args.workload, ROOT)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    try:
        devices = find_devices(cell.chips)
    except NoChip as e:
        print(f"run_cell: {e}", file=sys.stderr)
        return 2
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"run_cell: the program is not at {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    from repro.compile_cache import configure

    cache = configure()
    result = run(cell, args.seed, args.seconds, bool(args.trace), devices,
                 cache)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
