"""Seeded traffic: the same seed gives the same requests, and every
seed gives the same sizes and arrival gaps in another order."""
import numpy as np

import bench_paths  # noqa: F401  (first: the import path)
import run
import traffic

CHAT = run.load_json(run.HERE / "traffic" / "chat-verified.json")
BATCH = run.load_json(run.HERE / "traffic" / "batch-decode.json")
BIG = 2**31 + 987_654_321


def _sizes(reqs):
    """The multisets of prompt and output lengths (their pairing is
    drawn from the seed too)."""
    return (sorted(len(r["prompt"]) for r in reqs),
            sorted(r["max_new_tokens"] for r in reqs))


def test_lengths_are_clipped_quantiles():
    v = traffic.lengths(CHAT["prompt_tokens"], 1000)
    assert (np.diff(v) >= 0).all()
    assert v.min() >= 16 and v.max() <= 1024
    assert abs(np.median(v) - 128) <= 1
    u = traffic.lengths(BATCH["prompt_tokens"], 113)
    assert u.min() >= 16 and u.max() <= 128


def test_same_seed_same_requests():
    a = traffic.make_requests(CHAT, 50, 32768, BIG)
    b = traffic.make_requests(CHAT, 50, 32768, BIG)
    assert [r["max_new_tokens"] for r in a] == [r["max_new_tokens"] for r in b]
    assert all((x["prompt"] == y["prompt"]).all() for x, y in zip(a, b))


def test_seeds_permute_the_same_sizes():
    a = traffic.make_requests(CHAT, 64, 32768, 1)
    b = traffic.make_requests(CHAT, 64, 32768, BIG)
    assert [len(r["prompt"]) for r in a] != [len(r["prompt"]) for r in b]
    assert sorted(len(r["prompt"]) for r in a) == \
        sorted(len(r["prompt"]) for r in b)
    assert sorted(r["max_new_tokens"] for r in a) == \
        sorted(r["max_new_tokens"] for r in b)
    assert all(0 <= t < 32768 for r in b for t in r["prompt"])


def test_open_loop_schedule():
    a = traffic.open_loop_schedule(CHAT, 51, 32768, 3)
    b = traffic.open_loop_schedule(CHAT, 51, 32768, BIG)
    rate = CHAT["arrivals"]["rate_per_s"]
    assert len(a) == len(b) == int(np.ceil(rate * 51))
    assert a[0]["due"] == 0.0 and b[0]["due"] == 0.0
    assert all(x["due"] <= y["due"] for x, y in zip(a, a[1:]))
    # the gaps between arrivals come from one quantile set, in two
    # orders; the sizes are the same
    gaps = set(np.round(traffic.arrival_gaps(CHAT["arrivals"], len(a)), 9))
    for s in (a, b):
        assert set(np.round(np.diff([r["due"] for r in s]), 9)) <= gaps
        assert s[-1]["due"] < 51
    assert _sizes(a) == _sizes(b)


def test_backlog_stream():
    a = traffic.Backlog(BATCH, 32768, BIG, block=16)
    b = traffic.Backlog(BATCH, 32768, BIG, block=16)
    first = a.take(5) + a.take(20)
    again = b.take(25)
    assert [r["id"] for r in first] == list(range(25))
    assert [r["max_new_tokens"] for r in first] == \
        [r["max_new_tokens"] for r in again]
    other = traffic.Backlog(BATCH, 32768, 5, block=16).take(16)
    assert _sizes(first[:16]) == _sizes(other)
