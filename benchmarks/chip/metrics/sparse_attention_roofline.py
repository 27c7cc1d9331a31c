"""Roofline share of the sparse attention kernel (kernels/
sparse_attention.py): the least time its required work takes at the
chip's peaks over its device time in the traced steps."""
import sys

import costs
import kernels

KERNEL = kernels.SPARSE_ATTENTION


def read(ctx):
    kv_len, rows = ctx.fixed_kv_len(), ctx.live_rows()
    steps = len(ctx.step_modules())
    t = ctx.kernel_time(KERNEL)
    if kv_len is None or not rows or not steps or t <= 0:
        return None
    pk = ctx.peaks()
    bounds = [costs.roofline_time(*costs.sparse_attention(ctx.cfg, k, kv_len),
                                  pk)
              for k in costs.k_exact(ctx.cfg, ctx.mix["canvas"])]
    print("sparse_attention_roofline: bound by "
          + ", ".join(sorted({b for _, b in bounds})), file=sys.stderr)
    return 100.0 * steps * rows * sum(tm for tm, _ in bounds) / t
