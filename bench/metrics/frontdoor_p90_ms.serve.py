"""Time a request spends in the HTTP front door before its first token
reaches the socket, p90 over the requests of the measured window: the
``serve.http`` span (request read -> handed to the engine thread) plus the
``serve.sse_first`` span (first token taken off the stream -> its SSE
frame drained), from ``ServeMetrics.request_log()`` (``bench/spans.py``)."""

from bench import spans


def read(ctx):
    return spans.phase_p90_ms(ctx.get("requests"), "frontdoor")
