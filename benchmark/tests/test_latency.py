"""Latency arithmetic on a synthetic log."""
import pytest

from benchmark import latency, traffic


def rec(due, sent, first, last, tokens, ok=True, end=None, chunks=None):
    return {"due": due, "sent": sent, "first": first, "last": last,
            "end": end if end is not None else last, "tokens": tokens,
            "prompt_tokens": 100, "ok": ok,
            "chunks": chunks or ([(first, tokens)] if first else [])}


def test_percentile_is_nearest_rank():
    v = list(range(1, 101))
    assert latency.percentile(v, 95) == 95
    assert latency.percentile(v, 50) == 50
    assert latency.percentile([5.0], 95) == 5.0
    assert latency.percentile([1, 2, 3, 4], 95) == 4
    assert latency.percentile([], 95) is None


def test_token_gap_is_per_request_mean():
    r = rec(0.0, 0.0, 1.0, 3.0, 9)        # 8 gaps in 2 s
    assert latency.tpot_s(r) == pytest.approx(0.25)
    assert latency.tpot_s(rec(0.0, 0.0, 1.0, 1.0, 1)) is None


def test_open_loop_times_from_when_due():
    r = rec(due=10.0, sent=10.4, first=11.0, last=12.0, tokens=5)
    assert latency.ttft_s(r) == pytest.approx(1.0)     # not 0.6
    assert latency.lateness_s(r) == pytest.approx(0.4)


def test_summary_counts_failures_and_window_tokens():
    log = [
        rec(0.0, 0.0, 0.5, 1.5, 8, chunks=[(0.5, 4), (1.5, 4)]),
        rec(0.0, 0.0, 1.0, 3.0, 8, chunks=[(1.0, 4), (3.0, 4)]),
        rec(1.0, 1.0, None, None, 0, ok=False, end=1.2),     # refused
        rec(1.0, 1.0, 1.8, 4.5, 8, chunks=[(1.8, 4), (4.5, 4)]),  # cut
    ]
    s = latency.summarize(log, 0.0, 4.0)
    assert (s["attempted"], s["failed"]) == (3, 1)
    # 4+4 + 4+4 + 4 tokens arrived inside [0, 4]; the last chunk did not
    assert s["serve_tok_s"] == pytest.approx(20 / 4.0)
    assert s["ttft_p95_ms"] == pytest.approx(1000.0)
    assert s["tpot_p95_ms"] == pytest.approx(2000.0 / 7)


MIX = {"population": 64, "pairing_seed": 0,
       "prompt_len": {"dist": "lognormal", "median": 512, "sigma": 0.6,
                      "min": 272, "max": 2048},
       "output_len": {"dist": "lognormal", "median": 64, "sigma": 0.7,
                      "min": 8, "max": 256}}


def test_every_seed_gets_the_same_sizes_in_another_order():
    a = traffic.serve_requests(MIX, 1000, 1, 64)
    b = traffic.serve_requests(MIX, 1000, 2 ** 31 + 11, 64)
    size = lambda r: (len(r["prompt"]), r["max_new_tokens"])  # noqa: E731
    assert sorted(map(size, a)) == sorted(map(size, b))
    assert list(map(size, a)) != list(map(size, b))
    assert a[0]["prompt"] != b[0]["prompt"]
    lens = [len(r["prompt"]) for r in a]
    assert min(lens) == 272 and max(lens) <= 2048
    # a second pass over the population shares no prompt with the first
    c = traffic.serve_requests(MIX, 1000, 1, 128)
    assert c[0]["prompt"] != c[64]["prompt"]
    assert len(c[0]["prompt"]) == len(c[64]["prompt"])


def test_open_loop_offers_the_same_load_every_seed():
    mix = {"rate_per_s": 5.0, "arrivals": {"dist": "gamma", "cv": 3.0}}
    a, b = traffic.arrivals(mix, 40.0, 1), traffic.arrivals(mix, 40.0, 2)
    assert len(a) == len(b) == 200 and a != b
    assert a[0] == 0.0 and a == sorted(a) and a[-1] < 40.0
