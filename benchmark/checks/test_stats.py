"""Percentile and window arithmetic on a hand-made request log.

    JAX_PLATFORMS=cpu python -m pytest benchmark/checks -q
"""

import pytest

from benchmark.harness import stats


def rec(due, sent, chunks, done=None, error=None, engine_ttft_s=None):
    return {"due": due, "sent": sent, "chunks": chunks, "done": done,
            "error": error, "engine_ttft_s": engine_ttft_s}


# window [100, 110]
LOG = [
    rec(99.0, 99.0, [(99.5, 1), (100.5, 2), (101.5, 1)], done=101.5),
    rec(100.0, 100.1, [(100.4, 1), (100.6, 1), (101.0, 4)], done=101.0,
        engine_ttft_s=0.25),
    rec(105.0, 105.0, [(105.0, 0), (106.0, 1)], done=106.0,
        engine_ttft_s=0.9),
    rec(107.0, 107.2, [], error="ReplicaOverloadedError"),
    rec(109.0, 109.0, [(111.0, 1), (111.5, 1)]),     # first token late
    rec(109.5, 109.5, []),                           # never got a token
    rec(110.5, 110.5, [(110.6, 1)]),                 # due after the window
]


def test_percentile_interpolates_like_numpy():
    assert stats.percentile([], 50) is None
    assert stats.percentile([7.0], 90) == 7.0
    xs = [1.0, 2.0, 3.0, 4.0, 10.0]
    assert stats.percentile(xs, 50) == 3.0
    assert stats.percentile(xs, 90) == pytest.approx(4.0 + 0.6 * 6.0)
    assert stats.percentile(xs, 100) == 10.0
    assert stats.median([4.0, 1.0]) == 2.5


def test_tokens_counted_where_they_arrived():
    # request 0: 2 + 1 inside; request 1: 6; request 2: 1; the late ones
    # arrived after 110
    assert stats.tokens_in_window(LOG, 100.0, 110.0) == 3 + 6 + 1


def test_ttft_from_due_and_failures_at_window_length():
    sample = stats.ttft_sample_ms(LOG, 100.0, 110.0)
    # due inside: requests 1..5; the failed one and the one that never
    # had a token enter at the window's length, 10 s
    assert sorted(sample) == pytest.approx(
        [400.0, 1000.0, 2000.0, 10000.0, 10000.0])
    assert stats.attempted_in_window(LOG, 100.0, 110.0) == 5
    assert stats.failed_in_window(LOG, 100.0, 110.0) == 1


def test_gaps_divided_over_a_chunks_tokens():
    sample = stats.itl_sample_ms(LOG, 100.0, 110.0)
    want = ([500.0] * 2          # request 0: 99.5 -> 100.5, 2 tokens
            + [1000.0]           # 100.5 -> 101.5
            + [200.0]            # request 1: 100.4 -> 100.6
            + [100.0] * 4)       # 100.6 -> 101.0 over 4 tokens
    assert sorted(sample) == pytest.approx(sorted(want))


def test_client_overhead_is_from_send_not_from_due():
    sample = stats.client_overhead_sample_ms(LOG, 100.0, 110.0)
    # request 1: first token 100.4, sent 100.1, engine 0.25 -> 50 ms;
    # request 2: first token 106.0, sent 105.0, engine 0.9 -> 100 ms
    assert sorted(sample) == pytest.approx([50.0, 100.0])
