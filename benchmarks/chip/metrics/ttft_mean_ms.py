"""Mean, over the requests due in the window, of first token seen minus
when the request was due, on the client's clock.

The mean and not the median: a window of this mix holds 20 requests whose
times to the first token lie between 0.3 and 1.7 s with few near the
middle, so one request that changes its rank moves the median by tens of
milliseconds, and the mean by a twentieth of its own change (PERF.md,
PR 22, after the check refused `ttft_p50_ms`)."""

from harness import client_log

from . import _client


def read(record):
    return _client.mean_over_counted(record, client_log.ttft_ms)
