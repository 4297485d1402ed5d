"""The roofline's work and least time on hand-worked shapes."""
import pytest

import roofline

V5E = roofline.peak("TPU v5 lite")


def test_msmarco_share_is_bound_by_bytes_at_8_3_ms():
    # 2,210,456 x 768 f32 rows read once: 6.7905e9 B; queries 0.79 MB,
    # answers 0.2 MB; 2*256*768*2,210,456 = 0.869 TFLOP
    flops, nbytes = roofline.search_work(m=256, d=768, k=100, rows=2_210_456,
                                         prune_frac=0.0)
    assert flops == pytest.approx(8.6917e11, rel=1e-4)
    assert nbytes == pytest.approx(6.7915e9, rel=1e-4)
    t, bound = roofline.least_time(flops, nbytes, V5E)
    assert bound == "bytes"
    assert t == pytest.approx(8.3e-3, abs=0.05e-3)


def test_deep96_whole_corpus_is_bound_by_bytes():
    flops, nbytes = roofline.search_work(m=256, d=96, k=10, rows=9_990_000,
                                         prune_frac=0.0)
    t, bound = roofline.least_time(flops, nbytes, V5E)
    assert bound == "bytes"
    assert t == pytest.approx(4 * 96 * 9_990_000 / 819e9, rel=1e-3)   # 4.68 ms


def test_pruned_rows_are_neither_read_nor_multiplied():
    full = roofline.search_work(m=256, d=768, k=10, rows=1_000_000,
                                prune_frac=0.0)
    half = roofline.search_work(m=256, d=768, k=10, rows=1_000_000,
                                prune_frac=0.5)
    assert half[0] == pytest.approx(full[0] / 2)
    assert half[1] == pytest.approx(full[1] / 2, rel=1e-3)


def test_a_wide_batch_is_bound_by_flops():
    # 2*m*d flops against 4*d bytes per row: past m = 2*197e12/819e9 ~ 481
    # queries the matmul binds
    t, bound = roofline.least_time(
        *roofline.search_work(m=1024, d=768, k=10, rows=1_000_000,
                              prune_frac=0.0), V5E)
    assert bound == "flops"
    assert t == pytest.approx(2 * 1024 * 768 * 1e6 / 197e12)


def test_a_chip_missing_from_the_table_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        roofline.peak("TPU v9 imaginary")
