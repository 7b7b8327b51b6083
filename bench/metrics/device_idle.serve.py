"""Share of the traced window in which no operation ran on the device,
while the open loop of reads runs (mean over the chips the cell uses)."""


def read(ctx):
    red = ctx.reduced
    return 100.0 * (1.0 - red.busy_s / red.window_s)
