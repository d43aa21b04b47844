import math
import types

import numpy as np
import pytest

from benchmarks.harness import stats
from benchmarks.runners import serve


def test_percentile_is_nearest_rank_and_keeps_inf():
    vals = list(range(1, 101))
    assert stats.percentile(vals, 0.95) == 95
    assert stats.percentile(vals, 0.5) == 50
    assert stats.percentile([3.0], 0.95) == 3.0
    # 2 of 20 never answered: they are beyond the 95th percentile's rank
    assert stats.percentile([1.0] * 18 + [math.inf] * 2, 0.95) == math.inf
    assert stats.percentile([1.0] * 19 + [math.inf], 0.95) == 1.0
    with pytest.raises(ValueError):
        stats.percentile([], 0.5)


def test_union_and_gaps():
    assert stats.union_length([(0, 2), (1, 3), (5, 6)]) == 4
    assert stats.gaps([(1, 2), (1.5, 3), (5, 6)], 0, 7) == \
        [(0, 1), (3, 5), (6, 7)]


def test_a_prompt_astride_an_edge_counts_by_its_part_inside():
    # handed at 0; first tokens at 2, 4 and 4 (one step), then 10
    spans = stats.prefill_spans([(0.0, 2.0, 100), (0.0, 4.0, 200),
                                 (1.0, 4.0, 40), (9.0, 10.0, 60)])
    # each is spread back to the first-token instant before its own, or to
    # its own hand-over where that came later
    assert spans == [(0.0, 2.0, 100), (2.0, 4.0, 200), (2.0, 4.0, 40),
                     (9.0, 10.0, 60)]
    assert stats.spread_between(spans, 0.0, 20.0) == pytest.approx(400)
    assert stats.spread_between(spans, 1.0, 3.0) == pytest.approx(50 + 120)
    assert stats.spread_between(spans, 9.5, 20.0) == pytest.approx(30)
    # no length: whole at its end, inside [lo, hi)
    assert stats.spread_between([(3.0, 3.0, 7)], 3.0, 4.0) == 7
    assert stats.spread_between([(3.0, 3.0, 7)], 2.0, 3.0) == 0


def track(due, prompt, token_s, done=True, handed=None):
    plan = types.SimpleNamespace(due_s=due, prompt_ids=np.zeros(prompt),
                                 max_new_tokens=len(token_s))
    req = types.SimpleNamespace(status="FINISHED" if done else "RUNNING",
                                output_ids=[1] * len(token_s))
    tr = serve.Track(plan, req)
    tr.handed_s = due if handed is None else handed
    tr.token_s = list(token_s)
    tr.running_s = token_s[0] if token_s else None
    tr.done_s = token_s[-1] if done and token_s else None
    return tr


def timeline(stall=0.0, at=4.2):
    """Ten requests, one due each second, first token 0.1 s after it was
    due, then nine tokens 0.05 s apart; ``stall`` freezes the system from
    t = ``at`` for that long, and everything after shifts."""
    def shift(t):
        return t + stall if t >= at else t
    return [track(float(i), 100,
                  [shift(i + 0.1 + 0.05 * k) for k in range(10)])
            for i in range(10)]


def test_open_loop_clock_and_a_stall_in_the_window():
    calm = serve.end_to_end(timeline(), False, 10.0)
    assert calm["ttft_p95_ms"] == pytest.approx(100.0)
    assert calm["itl_p95_ms"] == pytest.approx(50.0)
    # every token served inside the window, over the whole window
    assert calm["serve_tokens_per_s"] == pytest.approx(10 * 110 / 10.0)
    stalled = serve.end_to_end(timeline(stall=1.0), False, 10.0)
    # request 4 was mid-answer: one of its gaps grows by the stall; of the
    # request due at 9 s no token falls inside now, and of its prompt,
    # prefilled over (9.1, 10.1], nine tenths
    assert stalled["itl_p95_ms"] == pytest.approx(50.0)
    assert max(g for tr in timeline(1.0)
               for g in stats.token_gaps(tr.token_s)) == pytest.approx(1.05)
    assert stalled["requests_completed_in_window"] == 9
    assert stalled["serve_tokens_per_s"] == pytest.approx(
        (9 * 110 + 100 * 0.9) / 10.0)
    # TTFT runs from when a request was DUE: those due during the stall
    # waited for it, though the system was handed them late
    assert stalled["ttft_p95_ms"] == pytest.approx(1100.0)


def test_a_stall_before_the_first_completion_moves_the_rate():
    # a backlog of five 1000-token prompts, a first token every 2 s from
    # 1 s on; the system freezes for 2 s at 0.5 s, before anything in the
    # window has said a token or completed, and everything shifts
    def backlog(stall, only=range(5)):
        return [track(-5.0, 1000, [1.0 + 2.0 * i + (stall if i in only
                                                     else 0.0)])
                for i in range(5)]
    calm = serve.end_to_end(backlog(0.0), True, 10.0)
    assert calm["serve_tokens_per_s"] == pytest.approx(
        (5 + 1000 / 6 + 4 * 1000) / 10.0)
    early = serve.end_to_end(backlog(2.0), True, 10.0)
    assert early["serve_tokens_per_s"] == pytest.approx(
        (4 + 1000 * 3 / 8 + 3 * 1000 + 1000 / 2) / 10.0)
    assert early["serve_tokens_per_s"] < 0.95 * calm["serve_tokens_per_s"]
    # and one after the last completion, still inside the window: the
    # last prompt's first token comes at 9.4 s, not 9 s
    late = serve.end_to_end(backlog(0.4, only=[4]), True, 10.0)
    assert late["requests_completed_in_window"] == 5
    assert late["serve_tokens_per_s"] == calm["serve_tokens_per_s"]
    late = serve.end_to_end(backlog(1.4, only=[4]), True, 10.0)
    assert late["serve_tokens_per_s"] == pytest.approx(
        (4 + 1000 / 6 + 3 * 1000 + 1000 * 3.0 / 3.4) / 10.0)


def test_a_stall_that_hits_a_batch_moves_the_gap_tail_and_the_rate():
    def batch(stall):
        shift = lambda t: t + stall if t >= 0.3 else t
        return [track(0.0, 100, [shift(0.1 + 0.05 * k) for k in range(10)])
                for _ in range(10)]
    calm = serve.end_to_end(batch(0.0), False, 1.0)
    assert calm["itl_p95_ms"] == pytest.approx(50.0)
    assert calm["requests_completed_in_window"] == 10
    assert calm["serve_tokens_per_s"] == pytest.approx(10 * 110 / 1.0)
    # ten of ninety gaps carry the stall: more than a twentieth
    stalled = serve.end_to_end(batch(1.0), False, 1.0)
    assert stalled["itl_p95_ms"] == pytest.approx(1050.0)
    assert stalled["requests_completed_in_window"] == 0
    assert stalled["serve_tokens_per_s"] == pytest.approx(10 * 104 / 1.0)


def test_a_request_that_never_answers_is_beyond_every_percentile():
    tracks = [track(float(i), 10, [i + 0.1, i + 0.2]) for i in range(5)]
    tracks += [track(5.0 + i, 10, [], done=False) for i in range(5)]
    out = serve.end_to_end(tracks, False, 10.0)
    assert out["ttft_p95_ms"] == math.inf


def test_backlog_counts_what_was_in_service_in_the_window():
    before = track(-5.0, 10, [-3.0, -2.0])          # done before it opened
    across = track(-5.0, 10, [-1.0, 1.0])
    inside = track(-5.0, 10, [2.0, 3.0])
    after = track(-5.0, 10, [11.0, 12.0])           # first token past the close
    got = [serve.in_window(t, True, 10.0)
           for t in (before, across, inside, after)]
    assert got == [False, True, True, False]
    out = serve.end_to_end([before, across, inside, after], True, 10.0)
    assert out["requests_completed_in_window"] == 2
    # tokens at 1, 2 and 3 s; the prompt prefilled over (-1, 2] by two
    # thirds; the one over (2, 11] by eight ninths
    assert out["serve_tokens_per_s"] == pytest.approx(
        (3 + 10 * 2 / 3 + 10 * 8 / 9) / 10.0)
