"""The generator: the same work for every seed, in another order."""
import numpy as np
import pytest

from benchmark.lib import traffic

OPEN = {"kind": "serve_open", "shape_seed": 5,
        "prompt_len": {"dist": "lognormal", "median": 200, "sigma": 0.8,
                       "min": 32, "max": 768},
        "output_len": {"dist": "uniform", "min": 32, "max": 128}}
CLOSED = {"kind": "serve_closed", "shape_seed": 5,
          "prompt_len": {"dist": "uniform", "min": 64, "max": 256},
          "output_len": {"dist": "uniform", "min": 128, "max": 256}}


def _shape(sessions):
    return (sorted(len(s["turns"][0]["prompt"]) for s in sessions),
            sorted(s["turns"][0]["max_tokens"] for s in sessions),
            [s["due"] for s in sessions])


@pytest.mark.parametrize("seed_a,seed_b", [(0, 1), (7, 2**31 + 12345)])
def test_open_loop_seeds_share_sizes_and_arrivals(seed_a, seed_b):
    a = traffic.make_sessions(OPEN, {"rate_rps": 5.0}, seed_a, 30, 50257, 768)
    b = traffic.make_sessions(OPEN, {"rate_rps": 5.0}, seed_b, 30, 50257, 768)
    assert _shape(a) == _shape(b)
    assert [len(s["turns"][0]["prompt"]) for s in a] != \
        [len(s["turns"][0]["prompt"]) for s in b]
    assert a[0]["turns"][0]["prompt"] != b[0]["turns"][0]["prompt"]


def test_same_seed_same_inputs():
    a = traffic.make_sessions(OPEN, {"rate_rps": 5.0}, 3, 20, 50257, 768)
    b = traffic.make_sessions(OPEN, {"rate_rps": 5.0}, 3, 20, 50257, 768)
    assert a == b


def test_open_loop_rate_and_limits():
    s = traffic.make_sessions(OPEN, {"rate_rps": 8.0}, 0, 100, 50257, 768)
    assert 700 < len(s) < 900
    lens = [len(x["turns"][0]["prompt"]) for x in s]
    assert min(lens) >= 32 and max(lens) <= 768
    assert 150 < np.median(lens) < 260
    assert all(0 <= x["due"] < 100 for x in s)
    assert all(0 <= t < 50257 for x in s[:20] for t in x["turns"][0]["prompt"])


def test_bursts_keep_the_mean_rate():
    t = dict(OPEN, arrivals={"burst_mean": 4.0})
    s = traffic.make_sessions(t, {"rate_rps": 8.0}, 0, 200, 512, 768)
    assert 1300 < len(s) < 1900
    dues = [x["due"] for x in s]
    assert len(set(dues)) < 0.5 * len(dues)      # requests share epochs


def test_closed_loop_deals_requests_to_clients():
    s = traffic.make_sessions(CLOSED, {"clients": 4, "requests_per_client": 5},
                              0, 10, 512, 256)
    assert len(s) == 20
    assert sorted({x["client"] for x in s}) == [0, 1, 2, 3]
    assert all(x["due"] == 0.0 for x in s)


def test_sessions_and_shared_prefixes():
    t = dict(OPEN, sessions={"turns_max": 6, "zipf_a": 1.2,
                             "user_len": {"dist": "uniform", "min": 8,
                                          "max": 32}, "think_s": 0.5},
             shared_prefix={"share": 0.6, "count": 4, "len": 512})
    s = traffic.make_sessions(t, {"rate_rps": 5.0}, 1, 60, 50257, 768)
    shared = [x for x in s if x["shared"]]
    assert 0.4 < len(shared) / len(s) < 0.8
    heads = {tuple(x["turns"][0]["prompt"][:512]) for x in shared}
    assert len(heads) <= 4
    assert max(len(x["turns"]) for x in s) > 1
    for x in s:        # a conversation never outgrows the largest bucket
        ctx = 0
        for turn in x["turns"]:
            assert ctx + len(turn["prompt"]) <= 768
            ctx += len(turn["prompt"]) + turn["max_tokens"]


def test_token_feed_is_a_function_of_seed_and_step():
    f = traffic.TokenFeed({"token_dist": {"zipf_a": 1.0}}, 2**31 + 5, 50257,
                          4, 128)
    g = traffic.TokenFeed({"token_dist": {"zipf_a": 1.0}}, 2**31 + 5, 50257,
                          4, 128)
    assert (f.batch(3) == g.batch(3)).all()
    assert (f.batch(3) != f.batch(4)).any()
    b = f.batch(0)
    assert b.shape == (4, 128) and b.dtype == np.int32
    assert b.min() >= 0 and b.max() < 50257
    # skewed: the commonest token is far commoner than uniform
    big = np.concatenate([f.batch(i).ravel() for i in range(20)])
    assert np.bincount(big).max() > 50 * len(big) / 50257
