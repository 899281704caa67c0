"""Time a request waits between the gateway handing it to the engine
thread and the engine popping it from its queue (the start of its
``serve.admit`` span), p90 over the requests of the measured window, from
``ServeMetrics.request_log()`` (``bench/spans.py``)."""

from bench import spans


def read(ctx):
    return spans.phase_p90_ms(ctx.get("requests"), "queue_wait")
