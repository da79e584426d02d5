"""Percentile, sample-count and client-side latency arithmetic."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import pytest

from benchmark.harness import latency, stats
from benchmark.harness.runners import serve as serve_runner


def test_percentiles_are_nearest_rank_like_the_programs():
    from byteps_tpu.observability.metrics import _nearest_rank

    vals = [5.0, 1.0, 9.0, 3.0, 7.0, 2.0, 8.0]
    for q in (0, 25, 50, 90, 99, 100):
        assert stats.pctl(vals, q) == _nearest_rank(sorted(vals), q)
    assert stats.pctl([], 50) is None
    assert stats.pctl(list(range(1, 101)), 90) == 90
    assert stats.median([1, 2, 3, 10]) == 2.5 and stats.median([]) is None


@pytest.mark.parametrize("n,q,beyond", [(200, 90, 20), (100, 99, 1),
                                        (11, 90, 1), (0, 90, 0),
                                        (1000, 99, 10)])
def test_samples_beyond_a_percentile(n, q, beyond):
    assert stats.samples_beyond(n, q) == beyond


def test_spread_is_interquartile_over_median():
    vals = [100.0, 101.0, 102.0, 103.0, 104.0]
    assert stats.spread(vals) == pytest.approx(2.0 / 102.0)
    assert stats.spread([1.0]) is None
    d = stats.describe("x", vals, 90, "ms")
    assert d["n"] == 5 and d["median"] == 102.0 and d["p90"] == 104.0


def rec(index, due, sent, tokens, done=None, error=None, prompt_len=10):
    return {"index": index, "due": due, "sent": sent, "prompt_len":
            prompt_len, "max_new_tokens": len(tokens), "error": error,
            "done": done if done is not None else (tokens[-1] if tokens
                                                   else sent),
            "token_times": tokens}


def test_open_loop_counts_from_the_due_instant_and_by_due_window():
    records = [
        rec(0, 9.0, 9.0, [9.5, 9.6]),                  # due in the ramp
        rec(1, 10.0, 10.002, [10.3, 10.35, 10.45]),
        rec(2, 12.0, 12.5, [13.0, 13.1]),              # sent 500 ms late
        rec(3, 15.0, 15.0, [], error="QueueFullError"),
        rec(4, 19.9, 19.9, [25.0, 25.1]),              # finished in drain
        rec(5, 20.0, 20.0, [20.1, 20.2]),              # due after window
    ]
    s = latency.open_loop_samples(records, (10.0, 20.0))
    assert (s["attempted"], s["failed"]) == (4, 1)
    assert s["ttft_ms"] == pytest.approx([300.0, 1000.0, 5100.0])
    assert sorted(s["itl_ms"]) == pytest.approx([50.0, 100.0, 100.0, 100.0])
    assert s["late_ms"] == pytest.approx([2.0, 500.0, 0.0, 0.0], abs=1e-6)


def test_a_failed_request_counts_as_missing_the_tail():
    v, ok = serve_runner.percentile_over_attempted([1.0] * 95, 5, 90)
    assert (v, ok) == (1.0, True)
    v, ok = serve_runner.percentile_over_attempted([1.0, 2.0] * 40, 20, 90)
    assert (v, ok) == (2.0, False)      # rank lands among the failures


def test_closed_loop_rate_is_taken_between_first_token_events():
    records = [
        rec(0, 0.0, 0.0, [4.0, 4.5], done=4.6, prompt_len=1000),   # ramp
        rec(1, 0.0, 0.0, [6.0, 10.0], done=10.0, prompt_len=1000),
        rec(2, 0.0, 0.0, [11.0, 12.0], done=12.0, prompt_len=3000),
        rec(3, 4.6, 4.6, [13.0, 14.0, 15.0], done=15.0, prompt_len=500),
        rec(4, 10.0, 10.0, [16.0], done=18.0, error="reset"),
        rec(5, 12.0, 12.0, [19.0, 19.5], done=20.0,
            error="unfinished when the run ended"),
        rec(6, 15.0, 15.0, [], done=20.0,
            error="unfinished when the run ended"),
    ]
    s = latency.closed_loop_samples(records, (5.0, 20.0))
    assert (s["attempted"], s["failed"], s["completed"]) == (4, 1, 3)
    # first-token events inside the window at t = 6, 11, 13, 16, 19:
    # the prompts of the last four, plus the later frames in (6, 19]
    # (10, 12, 14, 15), over 13 s; the frame at 19.5 is past the last
    assert s["prompts_finished"] == 5
    assert s["tokens_per_s"] == pytest.approx(
        (3000 + 500 + 10 + 10 + 4) / 13.0)
    one = latency.closed_loop_samples(records[:2], (5.0, 20.0))
    assert one["tokens_per_s"] is None


def test_decode_contexts_skip_the_prefill_token_and_clip_to_the_interval():
    records = [rec(0, 0.0, 0.0, [1.0, 2.0, 3.0, 4.0], prompt_len=100)]
    assert latency.decode_contexts(records, 1.5, 3.5) == [101, 102]
    assert latency.decode_contexts(records, 0.0, 9.0) == [101, 102, 103]


def test_pool_share_is_the_live_blocks_not_the_reservation():
    import types

    from benchmark.harness import manifest

    reader = manifest.load_module("layer_metrics", "kv_pool_live_share")
    pool = {"block": 128, "n_blocks": 768, "free": 576, "used": 192}
    ctx = types.SimpleNamespace(
        note=lambda **kw: None,
        serve={"stats_before": {"kv_blocks": dict(pool, used=96)},
               "stats_after": {"kv_blocks": pool}})
    assert reader.read(ctx) == pytest.approx(25.0)
    ctx.serve["stats_after"] = {"kv_blocks": None}      # a dense engine
    assert reader.read(ctx) is None
    ctx.serve = None                                    # a train cell
    assert reader.read(ctx) is None
