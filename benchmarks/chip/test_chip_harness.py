"""The harness on the CPU, at sizes a test run holds.

- every cell of ``BENCHMARK.json`` resolves its configuration, mix and
  metric readers by name, and a new cell, mix and reader are found as
  new files with no existing file edited;
- the same seed rebuilds identical requests; request indices give new
  signatures with unchanged array shapes (no recompiles);
- a run without a TPU exits non-zero and prints no result;
- the plain reference agrees with the program, and its control (the
  same reference in float32, and on MiniGhost's exact lattice in
  bfloat16 sums too) fails the comparison;
- a whole run, its chip check skipped, comes out correct, and comes out
  not correct with the timed path broken underneath it.
"""

import copy
import dataclasses
import itertools
import json
import os
import shutil
import subprocess
import sys

import ml_dtypes
import numpy as np
import pytest

import checks
import refmap
import run_cell
import workload

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = workload.ROOT
BENCH = workload.load_benchmark(ROOT)
CELLS = [w["name"] for w in BENCH["workloads"]]


def tiny(config: dict) -> dict:
    """The configuration at a test's size: same kinds, fewer ranks."""
    cfg = copy.deepcopy(config)
    if cfg["job"]["kind"] == "stencil3d":
        cfg["job"]["grid"] = [16, 8, 8]
        cfg["machine"]["router_dims"] = [8, 4, 4]
        cfg["allocation"]["cores"] = 1024
    else:
        cfg["job"]["ne"] = 8
        cfg["machine"]["router_dims"] = [2, 2, 2, 2, 2]
        cfg["allocation"]["cores"] = 384
    return cfg


def tiny_cell(name: str) -> workload.Cell:
    cell = workload.resolve_cell(BENCH, name, ROOT)
    return dataclasses.replace(cell, config=tiny(cell.config))


def control_cell(name: str) -> workload.Cell:
    """The cell at the size its control test runs: HOMME whole (its
    float32 sphere coordinates then change the partition), MiniGhost
    at 8192 ranks on a 16x8x8 torus."""
    cell = workload.resolve_cell(BENCH, name, ROOT)
    if cell.config["job"]["kind"] != "stencil3d":
        return cell
    cfg = copy.deepcopy(cell.config)
    cfg["job"]["grid"] = [32, 16, 16]
    cfg["machine"]["router_dims"] = [16, 8, 8]
    cfg["allocation"]["cores"] = 8192
    return dataclasses.replace(cell, config=cfg)


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_by_name(name):
    cell = workload.resolve_cell(BENCH, name, ROOT)
    entry = {c["name"]: c for c in BENCH["configs"]}[cell.config["name"]]
    assert cell.config["reduced"] == entry["reduced"]
    workload.Deployment(cell.config)
    assert {m["name"] for m in cell.end_to_end} >= {"map_s", "setup_s"}
    assert cell.per_layer
    for m in cell.end_to_end:
        assert callable(run_cell.reader("endtoend", m["name"]))
    for m in cell.per_layer:
        assert callable(run_cell.reader("layers", m["name"]))


def test_new_cell_is_new_files_only(tmp_path):
    """A deployment, a mix and a per-layer metric added as files, and
    entries in BENCHMARK.json, with no existing file changed."""
    here = tmp_path / workload.REL
    shutil.copytree(HERE, here, ignore=shutil.ignore_patterns(
        "__pycache__"))
    before = {p: p.read_bytes() for p in here.rglob("*") if p.is_file()}
    cfg = tiny(workload.resolve_cell(BENCH, CELLS[0], ROOT).config)
    cfg["name"] = "minighost-small"
    (here / "configs" / "minighost-small.json").write_text(json.dumps(cfg))
    mix = json.loads((here / "mixes" / "cold-flat-wh.json").read_text())
    mix.update(name="cold-flat-wh-8rot", rotations=8)
    (here / "mixes" / "cold-flat-wh-8rot.json").write_text(json.dumps(mix))
    (here / "layers" / "requests_n.py").write_text(
        "def read(run):\n    return float(run.requests)\n")
    bench = copy.deepcopy(BENCH)
    bench["configs"].append({"name": "minighost-small", "source": "x",
                             "file": f"{workload.REL}/configs/"
                                     "minighost-small.json",
                             "reduced": ["grid"], "why": "x"})
    bench["workloads"].append({"name": "mg-small", "config":
                               "minighost-small", "traffic":
                               "cold-flat-wh-8rot", "chips": 1,
                               "why": "x"})
    bench["per_layer"].append({"name": "requests_n", "unit": "1",
                               "better": "higher", "source":
                               "program_counter", "layer": "service",
                               "moves": "map_s",
                               "workloads": ["mg-small"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = workload.resolve_cell(workload.load_benchmark(str(tmp_path)),
                                 "mg-small", str(tmp_path))
    assert cell.mix["rotations"] == 8
    assert workload.Deployment(cell.config).job.n == 1024
    names = [m["name"] for m in cell.per_layer]
    assert names[-1] == "requests_n"
    read = run_cell.reader("layers", "requests_n", str(here))
    assert read(type("R", (), {"requests": 3})()) == 3.0
    for p, data in before.items():
        assert p.read_bytes() == data


@pytest.mark.parametrize("name", ["minighost-xk7-128k", "homme-bgq-32k"])
def test_requests_same_seed_same_arrays_new_signatures(name):
    from program import Program

    cfg = [c for c in BENCH["configs"] if c["name"] == name][0]
    with open(os.path.join(ROOT, cfg["file"])) as f:
        dep = workload.Deployment(tiny(json.load(f)))
    a = dep.allocation(2**31 + 11, 5)
    assert np.array_equal(a, dep.allocation(2**31 + 11, 5))
    mix = workload.resolve_cell(BENCH, CELLS[0], ROOT).mix
    prog = Program(dep, mix)
    reqs = [prog.request(dep.allocation(2**31 + 11, i)) for i in range(6)]
    sigs = {r.signature() for r in reqs}
    assert len(sigs) == 6
    assert {r.alloc.coords.shape for r in reqs} == {a.shape}
    assert {r.alloc.coords.dtype for r in reqs} == {a.dtype}


def test_no_tpu_exits_nonzero_without_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "run_cell.py"), "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
    assert "no TPU" in proc.stderr


def _program_answer(pipe, prog, alloc) -> dict:
    res = pipe.map(prog.graph, prog.request(alloc).alloc)
    return {"task_to_core": res.task_to_proc, "objective": res.score,
            "rotation": res.rotation,
            "history": res.stats.get("refine_history"),
            "accepted": res.stats.get("refine_accepted")}


def _control(dep, alloc, mix, ftype, stype) -> dict:
    out = refmap.reference_map(dep, alloc, mix, ftype, stype)
    out["objective"] = out["objective"][0]
    return out


@pytest.mark.parametrize("name", CELLS)
def test_reference_agrees_and_control_fails(name):
    """The program's all-host pipeline equals the reference, and the
    control fails the comparison on every seed tried.  The control is
    the reference in float32, one step below the configuration's
    float64.  On MiniGhost's integer lattice with unit volumes float32
    is exact (integer coordinates and cut weights, cluster centroids in
    32nds, hop sums under 2**24): it gives the reference's very answer,
    so there the control takes the next step down too, bfloat16 sums."""
    from program import Program, pipeline_config
    from repro.mapping import MappingPipeline

    cell = control_cell(name)
    dep = workload.Deployment(cell.config)
    mix = dict(cell.mix, partition_backend="numpy", score_backend="numpy")
    pipe = MappingPipeline(pipeline_config(mix))
    prog = Program(dep, mix)
    lattice = cell.config["job"]["kind"] == "stencil3d"
    for seed in (3, 4, 2**33 + 5):
        alloc = dep.allocation(seed, 1)
        ref = refmap.reference_map(dep, alloc, cell.mix)
        ok, got = checks.verdict(checks.compare(
            _program_answer(pipe, prog, alloc), ref, mix["hierarchy"]))
        assert ok, got
        ok, got = checks.verdict(checks.compare(
            _control(dep, alloc, cell.mix, np.float32, np.float32), ref,
            mix["hierarchy"]))
        assert ok is lattice, got
        if lattice:
            ok, got = checks.verdict(checks.compare(
                _control(dep, alloc, cell.mix, np.float32,
                         ml_dtypes.bfloat16), ref, mix["hierarchy"]))
            assert not ok, got


@pytest.mark.parametrize("shift", [True, False])
def test_reference_follows_the_mix_shift(shift):
    """The mix's ``shift`` reaches the reference as it reaches the
    program: both agree with it on and off, and it changes the answer."""
    from program import Program, pipeline_config
    from repro.mapping import MappingPipeline

    cell = tiny_cell("mg128k-xk7-flat")
    dep = workload.Deployment(cell.config)
    mix = dict(cell.mix, shift=shift, partition_backend="numpy",
               score_backend="numpy")
    pipe = MappingPipeline(pipeline_config(mix))
    prog = Program(dep, mix)
    alloc = dep.allocation(2**31 + 3, 2)
    ref = refmap.reference_map(dep, alloc, mix)
    ok, got = checks.verdict(checks.compare(
        _program_answer(pipe, prog, alloc), ref, "flat"))
    assert ok, got
    other = refmap.reference_map(dep, alloc, dict(mix, shift=not shift))
    assert not np.array_equal(other["task_to_core"], ref["task_to_core"])


def test_runjob_block_fills_the_first_nodes_of_an_order():
    """Each request's BG/Q allocation is the first nodes of one order of
    the block's dimensions, every core of a node in turn; the orders of
    a run differ."""
    cell = tiny_cell("homme32k-bgq-wh")
    dep = workload.Deployment(cell.config)
    m = dep.machine
    nodes = -(-dep.job.n // m.cores_per_router)
    seen = set()
    for index in range(8):
        a = dep.allocation(2**31 + 9, index)
        assert a.shape == (dep.job.n, len(m.dims))
        assert np.array_equal(a[:16, -1], np.arange(16))
        routers = a[::m.cores_per_router, :-1]
        assert len(routers) == nodes
        assert len(np.unique(routers, axis=0)) == nodes
        seen.add(a.tobytes())
        ok = False
        for order in itertools.permutations(range(len(m.router_dims))):
            shape = tuple(m.router_dims[k] for k in order)
            ids = np.ravel_multi_index(tuple(routers[:, order].T), shape)
            ok = ok or np.array_equal(ids, np.arange(nodes))
        assert ok
    assert len(seen) == 8


class _Stale:
    """Answers each request with the previous request's answer: a step
    that returns its state unchanged."""

    def serve(self, request):
        answer = super().serve(request)
        last, self._last = getattr(self, "_last", answer), answer
        return dict(last, status=answer["status"])


class _HalfBatch:
    """Maps a graph with half of its messages left out and the others
    doubled: half the batch dropped, the mean taken over the rest.  The
    half is drawn at random: a regular stride over a stencil's messages
    keeps exactly half of every hop total, and changes no answer."""

    def request(self, alloc):
        req = super().request(alloc)
        g = req.graph
        keep = np.sort(np.random.default_rng(0).permutation(
            len(g.edges))[:len(g.edges) // 2])
        half = dataclasses.replace(g, edges=g.edges[keep],
                                   weights=g.weights[keep] * 2)
        return dataclasses.replace(req, graph=half, _signature=None)


class _Altered:
    """Swaps the cores of ranks 0 and 1 where the answer is made."""

    def serve(self, request):
        answer = super().serve(request)
        t2c = np.array(answer["task_to_core"])
        t2c[[0, 1]] = t2c[[1, 0]]
        return dict(answer, task_to_core=t2c)


@pytest.mark.parametrize("fault", [None, _Stale, _HalfBatch, _Altered])
@pytest.mark.parametrize("name", CELLS)
def test_run_correct_and_faults_caught(name, fault, monkeypatch):
    import jax
    import program

    if fault is not None:
        broken = type("Broken", (fault, program.Program), {})
        monkeypatch.setattr(program, "Program", broken)
    out = run_cell.run(tiny_cell(name), 2**31 + 77, 0.3, False,
                       jax.devices(), os.devnull)
    assert out["attempted"] >= 2
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {"map_s", "setup_s"}
    assert out["correct"] is (fault is None), out["checks"]
