"""Window arithmetic and percentiles over client-side records."""
from types import SimpleNamespace as NS

import numpy as np
import pytest

from bench import stats


def rec(due, stamps, admit=None, submit=None):
    return NS(due=due, stamps=list(stamps), admit=admit,
              submit=due if submit is None else submit)


def test_percentile_is_exact_and_linear():
    v = list(range(1, 101))
    assert stats.percentile(v, 50) == pytest.approx(50.5)
    assert stats.percentile(v, 95) == pytest.approx(95.05)
    assert stats.percentile(v, 99) == pytest.approx(np.percentile(v, 99))
    assert stats.percentile([], 95) is None
    # a log2 histogram would put 33 and 60 in one bucket; this does not
    assert stats.percentile([33.0], 95) == 33.0
    assert stats.percentile([60.0], 95) == 60.0


def test_ttft_counts_every_request_due_in_the_window():
    recs = [rec(9.0, [9.5, 9.6]),              # due before: not counted
            rec(10.0, [10.2, 10.3]),
            rec(12.0, [13.5]),
            rec(19.9, [21.0, 21.1]),            # first token after close
            rec(19.95, []),                     # never served: failed
            rec(20.0, [20.1])]                  # due at close: not counted
    got, missing = stats.ttfts(recs, 10.0, 20.0)
    assert got == pytest.approx([0.2, 1.5, 1.1])
    assert missing == 1


def test_token_gaps_end_inside_the_window():
    recs = [rec(0.0, [9.0, 10.5, 11.0, 20.5]), rec(15.0, [16.0, 16.25])]
    assert sorted(stats.token_gaps(recs, 10.0, 20.0)) == \
        pytest.approx([0.25, 0.5, 1.5])
    assert stats.tokens_in(recs, 10.0, 20.0) == 4


def test_waiting_for_a_slot():
    recs = [rec(1.0, [], admit=2.0), rec(1.5, [], admit=None),
            rec(3.0, [], admit=3.5)]
    assert stats.waiting_at(recs, 1.2) == 1
    assert stats.waiting_at(recs, 2.5) == 1
    assert stats.waiting_at(recs, 3.2) == 2
    assert stats.waiting_at(recs, 4.0) == 1


def test_a_request_never_served_counts_in_the_tail_at_its_wait():
    recs = [rec(10.0, [10.2]), rec(11.0, [11.1]), rec(12.0, [])]
    assert stats.ttft_tail(recs, 10.0, 20.0, until=30.0) == \
        pytest.approx([0.2, 0.1, 18.0])
    served, _ = stats.ttfts(recs, 10.0, 20.0)
    assert stats.percentile(stats.ttft_tail(recs, 10.0, 20.0, 30.0), 95) \
        > stats.percentile(served, 95)


def test_end_to_end_arithmetic_a_cell_can_name():
    """Each end-to-end metric the harness knows, from one window's record:
    a later cell names one in BENCHMARK.json and gets it without an edit."""
    from bench import harness
    recs = [rec(10.0, [10.5, 11.0, 11.5]), rec(12.0, [12.25, 13.25]),
            rec(13.0, [])]
    c = NS(end_to_end=[{"name": n, "unit": u} for n, u in (
        ("ttft_p95_ms", "ms"), ("tbt_p99_ms", "ms"),
        ("tokens_per_s", "tokens/s"), ("setup_s", "s"))])
    run = NS(cell=c, records=recs, open=10.0, close=20.0, drained=30.0)
    m = harness.end_to_end(run, 7.5)
    assert m["ttft_p95_ms"]["value"] == pytest.approx(
        1e3 * np.percentile([0.5, 0.25, 17.0], 95))
    assert m["tbt_p99_ms"]["value"] == pytest.approx(
        1e3 * np.percentile([0.5, 0.5, 1.0], 99))
    assert m["tokens_per_s"]["value"] == pytest.approx(5 / 10.0)
    assert m["setup_s"] == {"value": 7.5, "unit": "s"}
