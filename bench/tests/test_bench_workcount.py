"""The work a round needs, and the shares built from it."""
import numpy as np
import pytest

import workcount

PEAK = workcount.peaks("TPU v5 lite")


def _bound_s(rows, q, c):
    flops, nbytes = workcount.round_work(rows, q, c)
    return max(flops / PEAK["bf16_flops_per_s"],
               nbytes / PEAK["hbm_bytes_per_s"])


@pytest.mark.parametrize("case", ["rows_ignore_padding", "naive_is_dense",
                                  "shares_at_the_bound", "cohort_only"])
def test_round_work(case):
    q, c = 2000, 10
    if case == "rows_ignore_padding":
        loads, u = np.array([400, 400, 237, 0, 12]), 1200
        # one round; client 1 missed the deadline
        reached = np.array([[True, False, True, False, True]])
        rows = workcount.needed_rows(reached, loads, u)
        assert rows == 400 + 237 + 12 + 1200
        # the padded fused tensor (n + 1, max(l, u), q) is never counted
        padded = (len(loads) + 1) * max(400, u)
        flops, nbytes = workcount.round_work(rows, q, c)
        assert flops == 4 * rows * q * c < 4 * padded * q * c
        assert nbytes == 4 * rows * (q + c)
    elif case == "naive_is_dense":
        n, l = 30, 400
        rows = workcount.needed_rows(np.ones((1, n), bool), np.full(n, l), 0)
        x = np.ones((n * l, q))
        theta = np.ones((q, c))
        # X theta and X^T r: two (n l, q) x (q, c) products, 2 flops a MAC
        dense = 2 * x.shape[0] * q * c + 2 * x.shape[0] * q * c
        assert (x.T @ (x @ theta)).shape == (q, c)
        assert workcount.round_work(rows, q, c)[0] == dense
    elif case == "shares_at_the_bound":
        flops, nbytes = workcount.round_work(12_450, q, c)
        t_bound = _bound_s(12_450, q, c)
        assert workcount.roofline_share(flops, nbytes, t_bound, PEAK) \
            == pytest.approx(100.0)
        assert workcount.mfu(flops, flops / PEAK["bf16_flops_per_s"],
                             PEAK) == pytest.approx(100.0)
        assert workcount.roofline_share(flops, nbytes, 2 * t_bound,
                                        PEAK) <= 100.0
        assert workcount.mfu(flops, t_bound, PEAK) <= 100.0
    else:
        # 10% cohorts over 4 aggregators of 444 writers: a path that plays
        # only the cohort's returned rows, at the chip's peak, reads 100%,
        # and one that also plays every other writer reads far less
        n, l, u, rounds = 1776, 226, 10034, 20
        rng = np.random.default_rng(0)
        loads = np.full(n, l)
        reached = (rng.random((rounds, n)) < 0.1) \
            & (rng.random((rounds, n)) < 0.9)
        rows = workcount.needed_rows(reached, loads, 4 * u)
        assert rows == l * int(reached.sum()) + rounds * 4 * u
        flops, nbytes = workcount.round_work(rows, q, 62)
        cohort_s = _bound_s(rows, q, 62)
        every_s = rounds * _bound_s(n * l + 4 * u, q, 62)
        assert workcount.roofline_share(flops, nbytes, cohort_s, PEAK) \
            == pytest.approx(100.0)
        assert workcount.roofline_share(flops, nbytes, every_s, PEAK) < 30.0
        assert workcount.mfu(flops, cohort_s, PEAK) <= 100.0 + 1e-9


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        workcount.peaks("TPU v99")
