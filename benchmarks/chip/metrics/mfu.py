"""FLOPs the traced steps require over the traced window at the chip's
bf16 peak: per live row, the work its configuration's family counts for
one serve step (``step_flops``), with the logits of the candidates the
scheduler may commit from."""
import costs


def read(ctx):
    kv_len, rows = ctx.fixed_kv_len(), ctx.live_rows()
    steps = len(ctx.step_modules())
    if kv_len is None or not rows or not steps:
        return None
    cand = costs.mean_candidates(ctx.mix)
    need = steps * rows * ctx.family.step_flops(
        ctx.cfg, ctx.mix["canvas"], kv_len, cand)
    return 100.0 * need / (ctx.trace.window_s * ctx.peaks()["bf16_flops"])
