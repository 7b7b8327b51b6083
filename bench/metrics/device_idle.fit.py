"""Share of the traced window in which no operation ran on the device,
during back-to-back fits (mean over the chips the cell uses)."""


def read(ctx):
    red = ctx.reduced
    return 100.0 * (1.0 - red.busy_s / red.window_s)
