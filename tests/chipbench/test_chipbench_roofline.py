"""The visit byte function against a hand count, and the peaks table."""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from chipbench import roofline  # noqa: E402


def _tiny():
    """Four vertices in two partitions of two:
    0 -> 1 (inside p0), 1 -> 2 and 1 -> 3 (p0 -> p1), 2 -> 0 (p1 -> p0)."""
    from repro.core.graph import BlockGraph, CSRGraph
    g = CSRGraph.from_edges(4, np.array([0, 1, 1, 2]),
                            np.array([1, 2, 3, 0]),
                            np.array([1.0, 2.0, 3.0, 4.0], np.float32))
    return BlockGraph.from_csr(g, 2)


@pytest.mark.parametrize("kind,planes", [("sssp", 1), ("ppr", 2)])
def test_partition_bytes_match_a_hand_count(kind, planes):
    bg = _tiny()
    q = 3
    # p0: 3 edges, 2 vertices, 2 targets outside (vertices 2 and 3)
    # p1: 1 edge, 2 vertices, 1 target outside (vertex 0)
    want = [8 * 3 + 2 * 4 * q * (planes + 1) * 2 + 2 * 4 * q * 2,
            8 * 1 + 2 * 4 * q * (planes + 1) * 2 + 2 * 4 * q * 1]
    got = roofline.partition_bytes(bg, q, kind)
    assert got.tolist() == want
    assert roofline.needed_bytes(bg, np.array([2, 1]), q, kind) == \
        2 * want[0] + want[1]


def test_a_padded_vertex_needs_no_state():
    from repro.core.graph import BlockGraph, CSRGraph
    g = CSRGraph.from_edges(3, np.array([0, 1]), np.array([1, 2]),
                            np.array([1.0, 1.0], np.float32))
    bg = BlockGraph.from_csr(g, 2)       # p1 holds vertex 2 and a pad
    got = roofline.partition_bytes(bg, 1, "sssp")
    assert got[1] == 2 * 4 * 1 * 2 * 1   # one real vertex, no edges


def test_peaks_of_a_known_kind():
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_an_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        roofline.peaks("cpu")
