"""Model FLOPs of the tokens that the served model processed in the traced
window (prompt tokens and generated ones), over the window times the chip's
bf16 peak.  FLOPs per token are counted from the configuration's shapes:
every projection, the GOOM layer's two per-head products (B u and A x) and
the output head, two FLOPs per multiply-add; padding and idle slots do not
count."""


def flops_per_token(cfg):
    d, hd, n, v = cfg["d_model"], cfg["head_dim"], cfg["n_layers"], cfg["vocab"]
    h = d // hd
    per_layer = (d * d            # in_proj
                 + 2 * h * hd * hd  # B u and A x
                 + 2 * h * hd * 2 * hd  # C x and D u
                 + d * d)         # out_proj
    return 2 * (n * per_layer + d * v)


def read(ctx):
    t, w = ctx.get("trace"), ctx.get("work") or {}
    if not t or not w.get("tokens") or t["window_s"] <= 0:
        return None
    flops = w["tokens"] * flops_per_token(ctx["config"])
    return 100.0 * flops / (t["window_s"] * ctx["peaks"]["bf16_flops_per_s"])
