"""Share of the serve-step program's device time spent in the paged
cache passes: the view gather and write-back (gather_pages,
scatter_pages) and the paged proxy-row commit (scatter_rows_paged)."""
import kernels

KERNELS = "|".join((kernels.GATHER_PAGES, kernels.SCATTER_PAGES,
                    kernels.SCATTER_ROWS_PAGED))


def read(ctx):
    step = sum(e.dur for e in ctx.step_modules())
    if step <= 0:
        return None
    return 100.0 * ctx.kernel_time(KERNELS) / step
