"""Set-up time: process start to the first timed request (imports, data
made on the device, the compile cache loaded, the cell's shapes warmed)."""


def read(ctx):
    return ctx.setup_s
