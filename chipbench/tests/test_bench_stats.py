"""The end-to-end arithmetic on synthetic run records."""
import types

import pytest

from chipbench import stats


def req(arrival, first, done, n):
    return types.SimpleNamespace(t_arrival=arrival, t_first=first, t_done=done,
                                 out=[0] * n)


def test_percentile_matches_linear_interpolation():
    vals = [5.0, 1.0, 3.0, 2.0, 4.0]
    assert stats.percentile(vals, 50) == 3.0
    assert stats.percentile(vals, 90) == pytest.approx(4.6)
    assert stats.percentile([7.0], 90) == 7.0
    with pytest.raises(ValueError):
        stats.percentile([], 90)


def test_tok_s_is_all_tokens_over_the_whole_window():
    reqs = [req(0.0, 0.1, 1.0, 10), req(0.5, 0.6, 2.0, 30)]
    # the window's wall time, not the span of the requests, is the base
    assert stats.end_to_end(reqs, 4.0)["tok_s"] == pytest.approx(40 / 4.0)


def test_ttft_p90_is_over_all_requests():
    reqs = [req(float(i), float(i) + 0.001 * (i + 1), float(i) + 1.0, 5) for i in range(20)]
    ttft = [1.0 * (i + 1) for i in range(20)]
    assert stats.end_to_end(reqs, 30.0)["ttft_p90_ms"] == pytest.approx(
        stats.percentile(ttft, 90))


def test_tpot_counts_stalls_and_skips_single_token_requests():
    # a request that sat through a 1 s admission stall between its tokens:
    # (t_done - t_first) / (n - 1) keeps the stall
    stalled = req(0.0, 0.0, 1.0 + 9 * 0.01, 10)
    single = req(0.0, 0.5, 0.5, 1)
    m = stats.end_to_end([stalled, single], 2.0)
    assert m["tpot_p90_ms"] == pytest.approx((1.0 + 0.09) / 9 * 1e3)


def test_empty_window_raises():
    with pytest.raises(ValueError):
        stats.end_to_end([], 1.0)

