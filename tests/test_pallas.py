"""Pallas TPU kernels: correctness in interpret mode (SURVEY.md §7). That the
chip's compiler accepts them is tests/test_chip_compile.py; that they run on
the chip is chip_smoke.py's Pallas phase."""

import numpy as np

from daft_tpu.ops.pallas_kernels import segment_sum_planes


def test_segment_sum_planes_matches_numpy():
    rng = np.random.default_rng(0)
    N, P, CAP = 8192, 6, 16
    planes = rng.standard_normal((N, P)).astype(np.float32)
    codes = rng.integers(0, CAP + 1, N).astype(np.int32)  # CAP = trash (dropped)
    out = np.asarray(segment_sum_planes(planes, codes, CAP, interpret=True))
    expect = np.zeros((CAP, P), np.float32)
    for g in range(CAP):
        expect[g] = planes[codes == g].sum(axis=0)
    np.testing.assert_allclose(out, expect, atol=1e-3)


def test_segment_sum_planes_empty_segments_and_single_block():
    planes = np.ones((1024, 2), np.float32)
    codes = np.zeros(1024, np.int32)  # everything in segment 0
    out = np.asarray(segment_sum_planes(planes, codes, 8, interpret=True))
    assert out[0, 0] == 1024.0
    assert (out[1:] == 0).all()
