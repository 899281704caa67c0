"""Length of a request's ``serve.admit`` span (popped from the engine's
queue -> its first token on the host: prefill dispatches, the fused
finisher and the wait for the token), p90 over the requests of the
measured window, from ``ServeMetrics.request_log()`` (``bench/spans.py``)."""

from bench import spans


def read(ctx):
    return spans.phase_p90_ms(ctx.get("requests"), "admission")
