"""The flop and task tables against hand values and the program's own."""

import pytest

from benchmark import work


@pytest.mark.parametrize("nt, tasks", [(16, 816), (32, 5984), (24, 2600),
                                       (1, 1), (2, 4)])
def test_potrf_task_count(nt, tasks):
    assert work.potrf_tasks(nt) == tasks
    by_class = nt + nt * (nt - 1) + nt * (nt - 1) * (nt - 2) // 6
    assert by_class == tasks         # POTRF + TRSM + SYRK + GEMM


def test_flop_counts():
    from parsec_tpu.apps.potrf import potrf_flops
    assert work.potrf_flops(98304) == potrf_flops(98304)
    assert work.potrf_flops(98304) == pytest.approx(3.167e14, rel=1e-3)
    assert work.gemm_flops(36864, 36864, 49152) == pytest.approx(1.336e14,
                                                                 rel=1e-3)
    assert work.gemm_tasks(3, 3, 4) == 36


def test_peaks_known_kind_and_unknown_kind():
    p = work.peak("TPU v5 lite")
    assert p["bf16_flop_per_s"] == 197e12 and "source" in p
    with pytest.raises(KeyError, match="no entry for device_kind"):
        work.peak("cpu")
