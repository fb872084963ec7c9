"""The readers of the metrics inside the program's layers, on hand-built
runs (no chip): the partition engine's own modules in the node cell,
coarsening's contraction and the refine stage's expansion, and the
fused program's host work beside its run.

Each reads its value per request where the run holds what it reads,
and ``None`` where it does not: a flat cell has no coarsening, and a
program older than its span or module name has neither.
"""

import pytest

import run_cell
import xtrace
from xtrace import Event, Line, Plane

REQUESTS = 2


def _span(i, name, parent, start, end):
    return {"name": name, "id": i, "parent": parent,
            "start_ns": float(start), "end_ns": float(end)}


def _node_spans():
    """Two node-cell requests of 1000 ns: coarsening [0, 300) holds its
    engine call and a contraction of 40 ns, the fused program [300,
    700) its run of 350 ns, refinement [700, 1000) an expansion of
    250 ns."""
    spans = []
    for r in range(REQUESTS):
        t, k = 1000 * r, 100 * r
        spans += [
            _span(k + 1, "pipeline.map", None, t, t + 1000),
            _span(k + 2, "pipeline.coarsen", k + 1, t, t + 300),
            _span(k + 3, "partition.jax", k + 2, t, t + 250),
            _span(k + 4, "pipeline.contract", k + 2, t + 260, t + 300),
            _span(k + 5, "pipeline.fused", k + 1, t + 300, t + 700),
            _span(k + 6, "fused.execute", k + 5, t + 320, t + 670),
            _span(k + 7, "pipeline.refine", k + 1, t + 700, t + 1000),
            _span(k + 8, "pipeline.expand", k + 7, t + 740, t + 990),
        ]
    return spans


def _plane(modules):
    return Plane("/device:TPU:0", (
        Line.of(xtrace.OPS_LINE, [Event("fusion.1", s, d)
                                  for _, s, d in modules]),
        Line.of(xtrace.MODULES_LINE, modules)))


NODE_MODULES = (
    Event("jit_partition_mj(93)", 20, 200),
    Event("jit_run(17)", 330, 320),
    Event("jit_partition_mj(93)", 1020, 210),
    Event("jit_run(17)", 1330, 330))


def _run(spans, modules=()):
    host = Plane("/host:CPU", (
        Line.of("python", (Event(xtrace.WINDOW, 0, 1000 * REQUESTS),)),))
    return run_cell.Run(
        cell=None, deployment=None, seed=0, setup_s=0.0,
        latencies_s=[1e-6] * REQUESTS, failed=0,
        device_kind="TPU v5 lite", spans=spans,
        planes=[host, _plane(modules)], window_ns=(0, 1000 * REQUESTS))


def _read(name, run):
    return run_cell.reader("layers", name)(run)


def test_readers_inside_the_node_cell_per_request():
    run = _run(_node_spans(), NODE_MODULES)
    # ms per request from ns: (200 + 210) / 2 ns, and so on
    assert _read("coarsen_device_ms", run) == pytest.approx(205e-6)
    assert _read("contract_ms", run) == pytest.approx(40e-6)
    assert _read("expand_ms", run) == pytest.approx(250e-6)
    # pipeline.fused 400 ns less its 350 ns run
    assert _read("fused_host_ms", run) == pytest.approx(50e-6)
    # the existing module reader still sees only the fused program
    assert _read("fused_device_ms", run) == pytest.approx(325e-6)


def _flat_spans():
    """A flat cell: the fused program alone, no hierarchy."""
    spans = []
    for r in range(REQUESTS):
        t, k = 1000 * r, 100 * r
        spans += [
            _span(k + 1, "pipeline.map", None, t, t + 1000),
            _span(k + 2, "pipeline.fused", k + 1, t + 10, t + 990),
            _span(k + 3, "fused.execute", k + 2, t + 30, t + 960),
        ]
    return spans


def test_readers_absent_in_a_flat_cell():
    run = _run(_flat_spans(), (Event("jit_run(17)", 40, 900),))
    for name in ("coarsen_device_ms", "contract_ms", "expand_ms"):
        assert _read(name, run) is None, name
    assert _read("fused_host_ms", run) == pytest.approx(50e-6)


@pytest.mark.parametrize("trace", [True, False], ids=["traced", "untraced"])
def test_readers_absent_without_the_names(trace):
    """A program without the new spans and module names (its fused
    program's run unsplit, its engine ``jit__unknown``) reads ``None``
    in every new metric, traced or not."""
    spans = [s for s in _node_spans() if s["name"] not in (
        "pipeline.contract", "pipeline.expand", "fused.execute")]
    modules = tuple(Event("jit__unknown(4)" if "partition" in e.name
                          else e.name, e.start_ns, e.duration_ns)
                    for e in NODE_MODULES)
    run = _run(spans, modules)
    if not trace:
        run.planes, run.window_ns = None, None
    for name in ("coarsen_device_ms", "contract_ms", "expand_ms",
                 "fused_host_ms"):
        assert _read(name, run) is None, name


def test_roofline_reader_finds_the_named_kernel():
    from test_chip_trace import _roofline_reader

    is_kernel = _roofline_reader().is_kernel
    # the named kernel's event in the latency cell's trace on a v5e
    assert is_kernel(
        "%mapscore.1 = (f32[4,8,128]{2,1,0:T(8,128)S(1)}, "
        "s32[4,8,128]{2,1,0:T(8,128)}) custom-call(s32[4,6,262144]"
        "{2,1,0:T(8,128)S(1)} %bitcast.565, s32[4,6,262144]{2,1")
    assert not is_kernel("%fusion.12 = f32[4,8,128]{2,1,0} fusion()")
