"""Share of the traced window in which no operation ran on the device:
1 - busy / window, busy being the union of the device operations'
intervals (``bench/trace.py``), averaged over the chips."""


def read(ctx):
    t = ctx.get("trace")
    if not t or t["busy_s"] <= 0 or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
