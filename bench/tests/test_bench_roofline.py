"""Peaks by device kind and the least work of a search, by hand."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from bench import roofline  # noqa: E402


def test_peaks_of_a_v5e():
    p = roofline.peaks("TPU v5 lite")
    assert p == {"flops": 197e12, "hbm_bw": 819e9, "link_bw": 50e9}


def test_unknown_device_kind_is_an_error():
    with pytest.raises(ValueError, match="no published peaks"):
        roofline.peaks("cpu")


def test_search_work_by_hand():
    # dim 8, 4 PQ chunks, rows of 4, tunneling prefix of 2:
    # 3 reads + 1 cache hit fetch 4 nodes -> 4 * 4 = 16 candidates;
    # 5 tunneled nodes -> 5 * 2 = 10 more; 26 candidates in all.
    # ops:   26 * 4 adds + 4 reranks * 3 * 8 = 104 + 96 = 200
    # bytes: 26 * 4 * (1 code byte + 4 LUT bytes) + 4 * 8 * 4 = 520 + 128
    stats = {"n_ios": 3, "n_cache_hits": 1, "n_tunnels": 5, "n_exact": 4}
    ops, nbytes = roofline.search_work(stats, dim=8, pq_chunks=4, degree=4,
                                       r_max=2)
    assert (ops, nbytes) == (200.0, 648.0)


def test_least_time_names_its_bound():
    t, bound = roofline.least_time(197e12, 819e9 / 2, "TPU v5 lite")
    assert (t, bound) == (1.0, "operations")
    t, bound = roofline.least_time(1.0, 819e9 * 3, "TPU v5 lite")
    assert (t, bound) == (3.0, "bytes")
