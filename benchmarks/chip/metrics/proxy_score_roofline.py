"""Roofline share of the paged identification kernel
(kernels/proxy_score.py proxy_score_paged): projection to rank r and
cosine drift of every valid row, one call per layer."""
import sys

import costs
import kernels

KERNEL = kernels.PROXY_SCORE_PAGED


def read(ctx):
    kv_len, rows = ctx.fixed_kv_len(), ctx.live_rows()
    steps = len(ctx.step_modules())
    t = ctx.kernel_time(KERNEL)
    if kv_len is None or not rows or not steps or t <= 0:
        return None
    one, bound = costs.roofline_time(
        *costs.proxy_score(ctx.cfg, kv_len, rows), ctx.peaks())
    print(f"proxy_score_roofline: bound by {bound}", file=sys.stderr)
    return 100.0 * steps * ctx.cfg["n_layers"] * one / t
