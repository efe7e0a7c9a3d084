"""Tail arithmetic: nearest-rank percentiles, with failed and unfinished
queries sorting above every finished one."""
import math
from types import SimpleNamespace

import pytest

from bench.harness import DRAIN_S, QueryRecord, percentile
from bench.reduce import tail


def test_nearest_rank():
    v = list(range(1, 101))
    assert percentile(v, 95) == 95
    assert percentile(v, 50) == 50
    assert percentile([3.0], 95) == 3.0
    assert math.isnan(percentile([], 95))


def _run(records, seconds=10.0):
    return SimpleNamespace(queries=records, seconds=seconds)


def _q(i, finish, state="done", level="IMMEDIATE"):
    return QueryRecord(qid=i, level=level, due=0.0, lateness=0.0, batch=1,
                       output_tokens=4, state=state, start=0.0, finish=finish)


def test_unfinished_sorts_last():
    done = [_q(i, 0.1 * (i + 1)) for i in range(19)]
    lost = [_q(99, None, state="failed")]
    # 20 queries: the 95th percentile is the 19th, still a finished one
    assert tail(_run(done + lost), "IMMEDIATE", 95,
                lambda r: r.finish - r.due) == pytest.approx(1.9)
    # two lost of 20: the 19th sorts among them, and reads as the wait
    lost2 = lost + [_q(98, None, state="running")]
    assert tail(_run(done[:18] + lost2), "IMMEDIATE", 95,
                lambda r: r.finish - r.due) == 10.0 + DRAIN_S


def test_other_levels_are_not_counted():
    rs = [_q(i, 1.0) for i in range(5)] + [_q(9, 50.0, level="RELAXED")]
    assert tail(_run(rs), "IMMEDIATE", 95, lambda r: r.finish - r.due) == 1.0
    assert tail(_run(rs), "BEST_EFFORT", 95, lambda r: r.finish) is None
