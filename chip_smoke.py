#!/usr/bin/env python3
"""Bring-up smoke run of the mapping service on a TPU.

Serves three of the paper's mapping requests through one
``MappingService`` with the on-chip path switched on
(``partition_backend="jax"``, ``score_backend="pallas"``, the default
``fused="auto"``), each cold and then warm, and checks every answer
against the all-numpy pipeline in the same process:

- ``minighost-xk7_sparse-node-wh`` at 131072 tasks: the paper's 128K-core
  MiniGhost run on a sparse XK7, depth-2 hierarchy, swap refinement in
  the fused program;
- ``homme-bgq_block-flat-latency`` at 32768 tasks: the 32K-core
  contiguous BG/Q HOMME run, whose latency objective drives the
  kernel's link-load path;
- ``minighost-xk7_sparse-flat-wh`` at 131072 tasks: the flat fused
  sweep at full width.

With ``--chips 4`` it runs only the mesh-builder phase instead: a
``topology_mesh`` over four real chips, checked against the default
device order and a host matmul.

Run from the root of a checkout::

    python chip_smoke.py            # one chip
    python chip_smoke.py --chips 4  # the mesh builder on four chips

It exits non-zero, and prints no result line, when JAX finds no TPU or
any check fails.  The last line of a passing run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
The compile cache follows ``repro.compile_cache`` (the
``JAX_COMPILATION_CACHE_DIR`` variable, else ``<checkout>/.jax_cache``).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

# (scenario name, scale) of the served requests
REQUESTS = (
    ("minighost-xk7_sparse-node-wh", 131072),
    ("homme-bgq_block-flat-latency", 32768),
    ("minighost-xk7_sparse-flat-wh", 131072),
)


class SmokeFailure(Exception):
    """A check of the smoke run did not hold."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def _cache_entries(path: str) -> int:
    return len(os.listdir(path)) if os.path.isdir(path) else 0


def serve_phase(cache_dir: str) -> None:
    """The three requests, cold then warm, each against the numpy
    oracle; one line per request."""
    import numpy as np

    from repro import obs
    from repro.mapping import shared_pipeline
    from repro.serve import MappingService
    from repro.serve.scenarios import get_scenario

    service = MappingService()
    for name, scale in REQUESTS:
        req = get_scenario(name, scale=scale).request()
        cfg = dataclasses.replace(req.config, partition_backend="jax",
                                  score_backend="pallas")
        req = dataclasses.replace(req, config=cfg, _signature=None)
        files0 = _cache_entries(cache_dir)
        caches0 = obs.snapshot()["caches"]
        cold = service.map(req)
        caches1 = obs.snapshot()["caches"]
        warm = service.map(req)
        res = cold.result
        stats = res.stats
        check(cold.status == "cold", f"{name}: first response {cold.status}")
        check(warm.status == "warm", f"{name}: repeat response {warm.status}")
        check("degraded" not in stats,
              f"{name}: served degraded ({stats.get('degraded')})")
        check(stats.get("fused") is True, f"{name}: not served fused")
        check(stats.get("fused_score_backend") == "pallas",
              f"{name}: fused score backend "
              f"{stats.get('fused_score_backend')}")
        check(stats.get("partition_backend") == "jax",
              f"{name}: partition backend {stats.get('partition_backend')}")
        oracle_cfg = dataclasses.replace(cfg, partition_backend="numpy",
                                         score_backend="numpy")
        t0 = time.perf_counter()
        oracle = shared_pipeline(oracle_cfg).map(req.graph, req.alloc)
        oracle_s = time.perf_counter() - t0
        same = np.array_equal(np.asarray(res.task_to_proc),
                              np.asarray(oracle.task_to_proc))
        check(same, f"{name}: winner differs from the numpy pipeline "
              f"({int(np.sum(res.task_to_proc != oracle.task_to_proc))} "
              "tasks)")
        deltas = {k: caches1[k]["misses"] - caches0.get(k, {}).get(
                      "misses", 0)
                  for k in sorted(caches1) if "misses" in caches1[k]}
        print("request " + json.dumps({
            "name": name, "scale": scale, "tasks": int(req.graph.n),
            "partition_backend": stats["partition_backend"],
            "score_backend": cfg.score_backend,
            "fused": stats["fused"],
            "fused_score_backend": stats["fused_score_backend"],
            "cold_s": cold.latency_s, "warm_s": warm.latency_s,
            "numpy_oracle_s": oracle_s,
            "compile_misses": deltas,
            "persistent_cache_new_files":
                _cache_entries(cache_dir) - files0,
        }), flush=True)
    svc = service.stats()
    check(svc["rung_failures"] == 0,
          f"service counted {svc['rung_failures']} rung failures")
    check(svc["degraded"] == 0, f"service degraded {svc['degraded']}")
    interp = obs.snapshot()["counters"].get("pallas.interpret_compiles", 0)
    check(not interp, f"{interp} interpret-mode kernel compiles")


def mesh_phase(devices) -> None:
    """``topology_mesh`` over four chips through the real-coordinate
    branch, against the default order and a host matmul."""
    import numpy as np

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.meshmap.device_mesh import topology_mesh

    devices = list(devices)[:4]
    mesh, report = topology_mesh(
        (2, 2), ("data", "model"), devices=devices,
        partition_backend="jax", score_backend="pallas",
        return_report=True)
    ids = sorted(d.id for d in mesh.devices.ravel())
    check(ids == sorted(d.id for d in devices),
          f"mesh devices {ids} are not each of the four chips once")
    mapped = report["mapped"]["latency_max"]
    default = report["default"]["latency_max"]
    check(mapped <= default, f"mapped latency_max {mapped} > default "
          f"{default}")
    rng = np.random.default_rng(0)
    a = rng.standard_normal((512, 256)).astype(np.float32)
    b = rng.standard_normal((256, 384)).astype(np.float32)
    host = a.astype(np.float64) @ b.astype(np.float64)
    base = Mesh(np.array(devices).reshape(2, 2), ("data", "model"))
    for label, m in (("mapped", mesh), ("default", base)):
        xa = jax.device_put(a, NamedSharding(m, P("data", None)))
        xb = jax.device_put(b, NamedSharding(m, P(None, "model")))
        out = jax.jit(lambda x, y: jnp.matmul(
            x, y, precision=jax.lax.Precision.HIGHEST))(xa, xb)
        err = float(np.max(np.abs(np.asarray(out) - host)))
        check(err < 1e-3, f"{label} mesh matmul differs from host by "
              f"{err}")
    print("mesh " + json.dumps({
        "coords": [list(getattr(d, "coords", ())) for d in devices],
        "order": [d.id for d in mesh.devices.ravel()],
        "mapped_latency_max": mapped, "default_latency_max": default,
        "mapped_weighted_hops": report["mapped"]["weighted_hops"],
        "default_weighted_hops": report["default"]["weighted_hops"],
    }), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the served requests; 4: only the mesh "
                         "builder on four chips")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    print(f"device platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    if dev.platform != "tpu":
        print("chip_smoke: no TPU found", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: {args.chips} chips asked, {len(devices)} found",
              file=sys.stderr)
        return 2

    sys.path.insert(0, os.path.join(HERE, "src"))
    from repro.compile_cache import configure

    cache_dir = configure()
    print(f"compile cache {cache_dir} "
          f"({_cache_entries(cache_dir)} entries)", flush=True)
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            mesh_phase(devices)
        else:
            serve_phase(cache_dir)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(f"phases done in {time.perf_counter() - t0:.1f}s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": args.chips}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
