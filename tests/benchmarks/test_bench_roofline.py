"""roofline/greedy_sweep.py against sizes counted by hand."""

import json

import pytest

from benchmarks.roofline import greedy_sweep


def test_work_of_a_small_table_counted_by_hand():
    # 8 task slots, 4 requesters, 1 type, 3 pairs
    w = greedy_sweep.work(nt=8, nr=4, ntypes=1, pairs=3)
    # reads: 8 x (4 + 4); 4 x (1 + 1); writes 4 x 4
    assert w["bytes"] == 64 + 8 + 16
    # ordering 8 x log2(8) = 24 comparisons; tests 2 x (3 + 2 + 1) = 12
    assert w["ops"] == 24 + 12


def test_the_world_shape_is_bound_by_memory_and_takes_under_a_microsecond():
    nt, nr = 65536, 8192
    seconds, bound = greedy_sweep.least_seconds(nt, nr, 1, 50.0,
                                                "TPU v5 lite")
    assert bound == "memory"
    # 65,536 x 8 + 8,192 x 2 + 8,192 x 4 = 573,440 bytes at 819 GB/s
    assert seconds == pytest.approx(573440 / 819e9)


def test_many_pairs_make_it_compute_bound():
    seconds, bound = greedy_sweep.least_seconds(1024, 1 << 20, 1, 1e6,
                                                "TPU v5 lite")
    assert bound == "compute"
    assert seconds == pytest.approx((1024 * 10 + 1e6 * (1e6 + 1)) / 393e12)


def test_an_unknown_device_kind_is_an_error_not_a_default():
    with pytest.raises(KeyError, match="no peaks for device kind"):
        greedy_sweep.least_seconds(8, 4, 1, 1, "TPU v9 imaginary")
    with pytest.raises(KeyError):
        greedy_sweep.peaks_of("cpu")


def test_the_table_names_its_source():
    with open(greedy_sweep.PEAKS) as f:
        table = json.load(f)
    assert "Google Cloud" in table["source"]
    v5e = table["peaks"]["TPU v5 lite"]
    assert (v5e["bf16_flops_per_s"], v5e["int8_ops_per_s"],
            v5e["hbm_bytes_per_s"]) == (197e12, 393e12, 819e9)
