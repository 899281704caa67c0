"""Share of the traced window's device busy time spent in the programs of
admission: chunked prefill, the fused finishers, the fresh prefill cache
and the prefix cache's snapshot and gather.  Device seconds per program
come from the ``XLA Modules`` line (``bench/spans.py``'s ``programs``)."""

ADMISSION = ("prefill_", "admit_", "fresh_cache", "snapshot_carry", "gather_prefix")


def read(ctx):
    t = ctx.get("trace")
    if not t or not t.get("programs") or t["busy_s"] <= 0:
        return None
    secs = sum(s for name, s in t["programs"] if name.startswith(ADMISSION))
    return 100.0 * secs / t["busy_s"]
