"""Least time of the GOOM layer's recurrence work over the device time of
the GOOM kernels in the traced window.

Work is that of the sequential recurrence for each token the model
processed in the window, per layer and head: the products B u_t and
A x_{t-1} (d x d by d x 1), reading u_t and writing x_t as float32 log and
sign planes.  Least time = max(FLOPs / bf16 peak, bytes / HBM bandwidth);
at these shapes bytes bound it.  Padding, doubling and idle slots do not
count."""


def work_per_token(cfg):
    d, hd, n = cfg["d_model"], cfg["head_dim"], cfg["n_layers"]
    h = d // hd
    flops = n * h * 2 * (2 * hd * hd)
    nbytes = n * h * hd * 2 * 4 * 2     # u in, x out: log and sign, float32
    return flops, nbytes


def read(ctx):
    t, w = ctx.get("trace"), ctx.get("work") or {}
    if not t or not w.get("tokens") or t["kernel_s"] <= 0:
        return None
    flops, nbytes = work_per_token(ctx["config"])
    p = ctx["peaks"]
    least = w["tokens"] * max(flops / p["bf16_flops_per_s"], nbytes / p["hbm_bytes_per_s"])
    return 100.0 * least / t["kernel_s"]
