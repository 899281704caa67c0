"""Decode token steps per fused decode dispatch over the measured window:
the change in ``Engine.decode_stats()`` (``decode_steps / dispatches``)."""


def read(ctx):
    c = ctx.get("counters") or {}
    if not c.get("dispatches"):
        return None
    return c["decode_steps"] / c["dispatches"]
