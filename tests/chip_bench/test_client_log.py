"""Metric arithmetic on a hand-made client log."""

import pytest

from harness import client_log


def entry(**kw):
    base = {"id": 0, "due": 100.0, "sent": 100.0, "headers": 100.02,
            "status": 200, "events": [], "finish_reason": "length",
            "done": 103.0, "error": None, "cut": None, "failed_at": None,
            "max_tokens": 4, "prompt_tokens": 10, "greedy": True}
    return {**base, **kw}


GOOD = entry(sent=100.01, events=[[100.5, 1], [100.7, 2], [101.1, 1]])


@pytest.mark.parametrize("values,q,want", [
    ([1, 2, 3, 4, 5], 50, 3), ([1, 2, 3, 4], 50, 2.5), ([7], 90, 7),
    ([0, 10], 90, 9.0), ([], 50, None)])
def test_percentile(values, q, want):
    assert client_log.percentile(values, q) == want


def test_ttft_is_from_when_the_request_was_due():
    assert client_log.ttft_ms(GOOD) == pytest.approx(500.0)
    assert client_log.late_ms(GOOD) == pytest.approx(10.0)
    assert client_log.headers_ms(GOOD) == pytest.approx(10.0)
    assert client_log.ttft_ms(entry()) is None


def test_tpot_counts_tokens_not_events():
    # 4 tokens in 3 events: (101.1 - 100.5) / 3
    assert client_log.n_tokens(GOOD) == 4
    assert client_log.tpot_ms(GOOD) == pytest.approx(200.0)
    assert client_log.tpot_ms(entry(events=[[100.5, 1]])) is None


@pytest.mark.parametrize("change,is_failed", [
    ({}, False), ({"status": 429}, True), ({"error": "boom"}, True),
    ({"done": None}, True), ({"status": None, "done": None}, True)])
def test_failed(change, is_failed):
    assert client_log.failed({**GOOD, **change}) is is_failed


def test_a_failed_request_meets_no_limit():
    limits = {"ttft_ms": 2000, "tpot_ms": 150}
    fast = entry(events=[[100.5, 1], [100.6, 1], [100.7, 1]])
    assert client_log.met(fast, limits)
    assert not client_log.met({**fast, "error": "x"}, limits)
    assert not client_log.met(GOOD, limits)              # tpot 200 > 150
    slow_first = entry(events=[[102.5, 1], [102.6, 1]])
    assert not client_log.met(slow_first, limits)        # ttft 2500


def test_tokens_between_counts_what_arrived_inside():
    log = [GOOD, entry(id=1, events=[[99.0, 5], [100.6, 1], [104.0, 9]])]
    assert client_log.tokens_between(log, 100.0, 101.0) == 4
    assert client_log.tokens_between(log, 100.0, 105.0) == 14


def test_which_requests_a_window_counts():
    a = entry(id=1, due=99.0, sent=99.0, done=100.5)
    b = entry(id=2, due=100.0, sent=100.0, done=101.0)
    c = entry(id=3, due=100.5, sent=100.5, done=None, cut=102.0)
    d = entry(id=4, due=100.6, sent=100.6, done=None, failed_at=100.9,
              error="refused")
    log = [a, b, c, d]
    assert [e["id"] for e in client_log.due_in(log, 100.0, 102.0)] == \
        [2, 3, 4]
    # closed loop: ended inside, whenever sent; the one cut at the close
    # is out
    assert [e["id"] for e in client_log.ended_in(log, 100.0, 102.0)] == \
        [1, 2, 4]


def _record():
    """Five requests, of which the window counts four; one of those was
    refused. Times to the first token of the three answered: 300, 500 and
    1,600 ms; per token after it 100, 200 and 100 ms."""
    def req(i, first, step, **kw):
        return entry(id=i, events=[[100.0 + first + k * step, 1]
                                   for k in range(3)], **kw)
    return {"counted_ids": [1, 2, 3, 4],
            "client": [req(0, 9.0, 0.9), req(1, 0.3, 0.1), req(2, 0.5, 0.2),
                       req(3, 1.6, 0.1), req(4, 0.1, 0.1, status=503)]}


@pytest.mark.parametrize("name,want", [
    ("ttft_mean_ms", 800.0),     # (300 + 500 + 1600) / 3: the refused one
    ("ttft_p50_ms", 500.0),      # and the uncounted one are in neither
    ("ttft_p90_ms", 1380.0),
    ("tpot_p50_ms", 100.0),
    ("tpot_p90_ms", 180.0)])
def test_client_readers_take_the_counted_and_answered_requests(name, want):
    from harness import spec

    assert spec.metric_reader(name).read(_record()) == pytest.approx(want)


def test_a_mean_of_no_answered_request_is_left_out():
    from harness import spec

    record = {"counted_ids": [4], "client": _record()["client"]}
    assert spec.metric_reader("ttft_mean_ms").read(record) is None
    assert spec.metric_reader("ttft_p50_ms").read(record) is None
