"""Percentile and open-loop timing arithmetic on synthetic timestamps."""
import math

import pytest

from benchmark.lib import stats


@pytest.mark.parametrize("vals,p,want", [
    ([], 95, None),
    ([7.0], 95, 7.0),
    (list(range(1, 101)), 95, 95),
    (list(range(1, 101)), 50, 50),
    (list(range(1, 21)), 95, 19),       # nearest rank: ceil(.95*20) = 19th
    ([3, 1, 2], 100, 3),
    ([3, 1, 2], 0, 1),
])
def test_percentile_nearest_rank(vals, p, want):
    assert stats.percentile(vals, p) == want


def test_iqr_share_matches_statistics_quantiles():
    vals = [10.0, 10.2, 9.9, 10.1, 10.4, 9.8]
    import statistics

    q = statistics.quantiles(vals, n=4)
    assert stats.iqr_share(vals) == pytest.approx(
        (q[2] - q[0]) / statistics.median(vals))
    assert stats.iqr_share([1.0]) is None


def _rec(due, sent, times, ok=True):
    return {"due": due, "sent": sent, "token_times": times, "ok": ok,
            "first": times[0] if times else None,
            "last": times[-1] if times else None, "n_tokens": len(times)}


def test_ttft_counts_from_due_time_not_send_time():
    # due at 10.0, the generator was 0.5 late, first token 0.2 after send
    lat = stats.request_latencies([_rec(10.0, 10.5, [10.7, 10.8, 10.9])])
    assert lat["ttft_s"] == [pytest.approx(0.7)]
    assert lat["late_s"] == [pytest.approx(0.5)]
    assert lat["tpot_s"] == [pytest.approx(0.1)]
    assert (lat["attempted"], lat["failed"]) == (1, 0)


def test_single_token_request_has_no_tpot():
    lat = stats.request_latencies([_rec(0.0, 0.0, [0.3])])
    assert lat["ttft_s"] == [pytest.approx(0.3)] and lat["tpot_s"] == []


def test_failed_request_is_a_miss_in_both_tails():
    recs = [_rec(0.0, 0.0, [0.1, 0.2])] * 9 + [_rec(0.0, 0.0, [], ok=False)]
    lat = stats.request_latencies(recs, miss_s=120.0)
    assert lat["failed"] == 1
    assert stats.percentile(lat["ttft_s"], 95) == 120.0
    assert stats.percentile(lat["tpot_s"], 95) == 120.0
    assert math.isinf(max(stats.request_latencies(recs)["ttft_s"]))


def test_unfinished_request_with_tokens_still_fails():
    lat = stats.request_latencies([_rec(0.0, 0.0, [0.1, 0.2], ok=False)],
                                  miss_s=60.0)
    assert lat["failed"] == 1 and lat["ttft_s"] == [60.0]


def test_tokens_in_window_is_half_open():
    recs = [_rec(0, 0, [1.0, 2.0, 3.0]), _rec(0, 0, [0.5, 2.5, 3.5])]
    assert stats.tokens_in_window(recs, 1.0, 3.0) == 3   # 1.0, 2.0, 2.5


def test_mean_loss_tolerance_is_measured_on_means_not_on_tokens():
    import numpy as np

    from benchmark.lib.chip import NOISE_FACTOR, mean_loss_tolerance

    rng = np.random.default_rng(0)
    n32 = rng.normal(11.0, 1.0, (8, 1024))
    n16 = n32 + rng.normal(0.0, 0.008, n32.shape)      # bf16-sized noise
    t = mean_loss_tolerance(n16, n32)
    # per-token noise cancels in a mean: the tolerance is some standard
    # errors of it, an order of magnitude under the per-token distance
    assert 4e-4 < t["tolerance"] < 1.2e-3
    assert t["tolerance"] < NOISE_FACTOR * t["bf16_noise"] / 10
    assert t["bf16_mean_se"] == pytest.approx(0.008 / 8192 ** 0.5, rel=0.05)
    # a route that shifts every token's loss widens it by that shift
    shifted = mean_loss_tolerance(n16 + 0.01, n32)
    assert shifted["tolerance"] == pytest.approx(
        t["tolerance"] + NOISE_FACTOR * 0.01, abs=2e-4)
