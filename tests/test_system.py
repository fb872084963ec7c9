"""End-to-end behaviour tests for the full system.

These exercise the integrated story: the paper's geometric mapper builds
the device mesh; a model trains on it (loss decreases deterministically);
the serving engine decodes consistently with training; the dry-run
lowering machinery produces coherent artifacts for a small config.
"""

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.core import (Allocation, evaluate, identity_mapping,
                        logical_mesh_graph, stencil_graph, sfc_allocation,
                        tpu_v5e_pod, make_machine)
from repro.mapping import MappingPipeline, PipelineConfig
from repro.models import ModelConfig, params_spec, tree_init
from repro.models.config import ShapeConfig
from repro.serve.engine import ServeEngine
from repro.train.driver import JobConfig, train
from repro.train.optimizer import OptConfig

TINY = ModelConfig(name="sys-tiny", family="dense", num_layers=2,
                   d_model=48, num_heads=4, num_kv_heads=2, d_ff=96,
                   vocab_size=64, head_dim=12, remat="none", loss_chunk=0,
                   dtype="float32")


def test_end_to_end_training_learns():
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))
    job = JobConfig(steps=25, log_every=0)
    hist = train(TINY, OptConfig(lr=1e-2, warmup_steps=2, total_steps=25,
                                 weight_decay=0.0),
                 job, mesh, shape=ShapeConfig("t", "train", 32, 8),
                 log=lambda *a: None)
    assert hist["loss"][-1] < hist["loss"][0] * 0.7


def test_end_to_end_serving_matches_training_forward():
    from repro.models import logits_fn
    params = tree_init(params_spec(TINY), jax.random.PRNGKey(0),
                       TINY.dtype)
    eng = ServeEngine(TINY, params, max_seq=24, batch=2)
    prompt = np.random.default_rng(0).integers(0, 64, (2, 8)).astype(
        np.int32)
    out = eng.generate(prompt, max_new_tokens=4)
    full = logits_fn(TINY, params, {"tokens": jnp.asarray(prompt)})
    assert (out[:, 0] == np.asarray(jnp.argmax(full[:, -1], -1))).all()


def test_geometric_mapping_improves_sparse_stencil():
    """The paper's headline behaviour: on a fragmented allocation, the
    geometric mapping cuts hops vs rank order."""
    m = make_machine((16, 16, 16), wrap=True, bw=1.0)
    alloc = sfc_allocation(m, 512, nfragments=8, seed=11)
    g = stencil_graph((8, 8, 8))
    geo = MappingPipeline(PipelineConfig(sfc="FZ", shift=True)).map(
        g, alloc)
    ours = evaluate(g, alloc, geo)
    base = evaluate(g, alloc, identity_mapping(g, alloc))
    assert ours["average_hops"] < base["average_hops"]


def test_candidate_selection_never_worse():
    from repro.meshmap.device_mesh import select_mapping
    m = tpu_v5e_pod(8)
    alloc = Allocation(m, m.all_coords())
    for shape, w in [((8, 8), (8.0, 64.0)), ((4, 16), (8.0, 64.0)),
                     ((16, 4), (8.0, 64.0))]:
        g = logical_mesh_graph(shape, w, None)
        best, ours, base = select_mapping(g, alloc, w, rotations=4)
        assert ours["latency_max"] <= base["latency_max"] + 1e-9


def test_dryrun_cell_small_mesh(tmp_path):
    """lower+compile+analyze pipeline end-to-end on the CPU's 1 device."""
    from repro.launch import dryrun
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))
    import repro.models.config as mc
    small = mc.ShapeConfig("tiny_train", "train", 32, 4)
    mc.SHAPES["tiny_train"] = small
    try:
        rec = dryrun.run_cell("zamba2_1p2b", "tiny_train", mesh,
                              str(tmp_path), "test")
    finally:
        mc.SHAPES.pop("tiny_train")
    assert rec["status"] == "ok", rec.get("error")
    assert rec["cost"]["flops"] > 0
    assert rec["memory"]["argument_size_in_bytes"] > 0
