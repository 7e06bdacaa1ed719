"""Percentiles, and the censoring of requests the window left unfinished."""
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
for _p in (BENCH, BENCH.parent / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

import numpy as np
import pytest

from cb.stats import itl_samples, percentile, tpot_samples, ttft_samples
from cb.traffic import Request


def req(due, times):
    return Request(index=0, due=due, prefix=None, tokens=[2], max_new=4,
                   token_times=list(times))


@pytest.mark.parametrize("p", [0, 5, 50, 95, 99, 100])
def test_percentile_matches_numpy(p):
    xs = list(np.random.default_rng(0).exponential(size=37))
    assert percentile(xs, p) == pytest.approx(np.percentile(xs, p))


def test_percentile_of_nothing_is_none():
    assert percentile([], 50) is None
    assert percentile([3.0], 95) == 3.0


def test_unfinished_requests_count_at_their_age():
    close = 10.0
    rs = [req(1.0, [1.5, 1.6]),        # ttft 0.5
          req(2.0, [3.0]),             # ttft 1.0
          req(4.0, []),                # waiting at close: age 6.0
          req(9.0, [10.5]),            # first token after close: age 1.0
          req(11.0, [])]               # due after close: not in the sample
    assert sorted(ttft_samples(rs, close)) == [0.5, 1.0, 1.0, 6.0]
    # the backlog moves the tail, as it should
    assert percentile(ttft_samples(rs, close), 95) > 5.0


def test_gaps_counted_when_they_end_inside_the_window():
    rs = [req(0.0, [0.5, 1.5, 2.0, 12.0]), req(0.0, [3.0, 3.25])]
    assert sorted(itl_samples(rs, 1.0, 10.0)) == [0.25, 0.5, 1.0]


def test_time_per_output_token_spans_each_requests_tokens_in_the_window():
    rs = [req(0.0, [1.0 + 0.1 * i for i in range(11)]),   # 10 gaps of 0.1
          req(0.0, [2.0 + 0.2 * i for i in range(20)]),   # 8 gaps by 3.6
          req(0.0, [5.0, 5.1, 5.2]),                       # too few gaps
          req(0.0, [])]
    got = tpot_samples(rs, 0.0, 3.6)
    assert got == pytest.approx([0.1, 0.2])
    # a request still decoding at the close counts with what it had
    assert tpot_samples(rs, 0.0, 3.3) == pytest.approx([0.1])
