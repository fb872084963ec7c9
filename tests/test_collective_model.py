"""Mapping-aware collective model tests (meshmap/collective_model)."""

from repro.core import (Allocation, identity_mapping, logical_mesh_graph,
                        make_machine, sfc_allocation, tpu_v5e_pod)
from repro.meshmap.collective_model import (collective_term,
                                            compare_mappings,
                                            split_axis_bytes)
from repro.meshmap.device_mesh import select_mapping


def test_split_axis_bytes_proportional():
    ab = split_axis_bytes(100.0, (16, 16), axis_weights=(1.0, 3.0))
    assert ab == [25.0, 75.0]
    # size-1 axes carry no collectives
    ab = split_axis_bytes(100.0, (1, 16), axis_weights=(1.0, 3.0))
    assert ab == [0.0, 100.0]


def test_contiguous_pod_close_to_flat_term():
    """On a contiguous pod with aligned logical shape, per-link traffic
    should be within ~2x of the ideal flat bytes/bw term (rings map to
    disjoint physical links)."""
    m = tpu_v5e_pod(8)
    alloc = Allocation(m, m.all_coords())
    axis_bytes = (1e9, 8e9)
    g = logical_mesh_graph((8, 8), axis_bytes)
    t = collective_term(alloc, (8, 8), identity_mapping(g, alloc),
                        axis_bytes)
    flat = sum(axis_bytes) / 50e9
    assert t < 2.0 * flat
    assert t > 0.2 * flat


def test_fragmented_allocation_dilates_term():
    """Fragmented allocations must show a strictly larger bottleneck-link
    term than the contiguous pod — the cost the paper's technique
    targets."""
    axis_bytes = (1e9, 8e9)
    m1 = tpu_v5e_pod(8)
    a1 = Allocation(m1, m1.all_coords())
    g = logical_mesh_graph((8, 8), axis_bytes)
    t1 = collective_term(a1, (8, 8), identity_mapping(g, a1), axis_bytes)
    ms = make_machine((16, 16), wrap=True, bw=50.0)
    a2 = sfc_allocation(ms, 64, nfragments=4, seed=3)
    t2 = collective_term(a2, (8, 8), identity_mapping(g, a2), axis_bytes)
    assert t2 > t1


def test_candidate_mapping_not_worse_on_collective_term():
    axis_bytes = (1e9, 8e9)
    ms = make_machine((16, 16), wrap=True, bw=50.0)
    alloc = sfc_allocation(ms, 64, nfragments=4, seed=1)
    g = logical_mesh_graph((8, 8), axis_bytes)
    best, _, _ = select_mapping(g, alloc, axis_bytes, rotations=2)
    res = compare_mappings(alloc, (8, 8), axis_bytes,
                           {"default": identity_mapping(g, alloc),
                            "mapped": best})
    assert res["mapped"] <= res["default"] * 1.001


class _Chip:
    """A stand-in for a TPU device: the attributes ``device_coords``
    reads."""

    platform = "tpu"

    def __init__(self, coords, slice_index=0):
        self.coords = coords
        self.slice_index = slice_index


def test_device_coords_real_chips_start_at_origin():
    """Real-coordinate branch: a 2x2 slice cut at an offset of a pod
    lands at the origin of the machine's torus (z and the core index
    are ignored); multislice adds the slice as the leading dim."""
    from repro.meshmap.device_mesh import device_coords, machine_for

    chips = [_Chip((4, 6, 0)), _Chip((5, 6, 0)), _Chip((4, 7, 0)),
             _Chip((5, 7, 0))]
    xy = device_coords(chips, machine_for(side=2))
    assert xy.tolist() == [[0, 0], [1, 0], [0, 1], [1, 1]]
    pods = [_Chip((c.coords[0], c.coords[1], 0), slice_index=s)
            for s in (0, 1) for c in chips[:2]]
    assert device_coords(pods, machine_for(pods=2, side=2)).tolist() == \
        [[0, 0, 0], [0, 1, 0], [1, 0, 0], [1, 1, 0]]


def test_device_coords_outside_machine_raise():
    import pytest

    from repro.meshmap.device_mesh import device_coords, machine_for

    chips = [_Chip((0, 0, 0)), _Chip((3, 0, 0))]
    with pytest.raises(ValueError, match="outside"):
        device_coords(chips, machine_for(side=2))
