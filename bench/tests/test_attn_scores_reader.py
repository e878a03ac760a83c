"""The model cell's attention-score reader on made-up counter records."""
import pytest

import harness


def test_attn_scores_per_causal_reads_the_counters_ratio():
    read = harness.metric_reader("attn_scores_per_causal.moe")
    rounds, seqs, layers = 20, 8, 5
    triangle = 4096 * 4097 // 2
    ctx = {"window_counters": {
        "attn/scores": {"events": rounds,
                        "total": rounds * seqs * layers * 36 * 512 ** 2},
        "attn/causal_scores": {"events": rounds,
                               "total": rounds * seqs * layers * triangle}}}
    assert read(ctx) == pytest.approx(36 * 512 ** 2 / triangle)
    assert read(ctx) == pytest.approx(1.1247, abs=1e-4)
    square = dict(ctx["window_counters"], **{"attn/scores": {
        "events": rounds, "total": rounds * seqs * layers * 4096 ** 2}})
    assert read({"window_counters": square}) == pytest.approx(
        2 * 4096 / 4097)


@pytest.mark.parametrize("counters", [
    {}, {"attn/scores": {"events": 1, "total": 10}},
    {"attn/scores": {"events": 1, "total": 10},
     "attn/causal_scores": {"events": 1, "total": 0}}],
    ids=["program_without_counters", "no_triangle", "empty_window"])
def test_attn_scores_per_causal_is_silent_without_its_source(counters):
    read = harness.metric_reader("attn_scores_per_causal.moe")
    assert read({"window_counters": counters}) is None
