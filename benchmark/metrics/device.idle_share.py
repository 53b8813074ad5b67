"""device.idle_share: the traced window's share in which no operation ran
on the device, 100 x (1 - busy / window), busy being the union of the
device-op intervals of the first TPU (trace_reduce.Reduction.busy_s)."""


def read(ctx):
    if ctx.window_s <= 0 or ctx.reduction.busy_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.reduction.busy_s / ctx.window_s)
