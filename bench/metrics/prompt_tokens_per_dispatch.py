"""Prompt tokens computed per admission dispatch over the measured
window: the change in ``Engine.prefill_stats()``, ``prompt_tokens /
(chunk_calls + tail_calls + finisher_calls)``."""


def read(ctx):
    c = ctx.get("prefill") or {}
    calls = sum(c.get(k, 0) for k in ("chunk_calls", "tail_calls", "finisher_calls"))
    if not calls:
        return None
    return c["prompt_tokens"] / calls
