"""Share of the batch window in which no operation ran on the device."""


def read(ctx):
    if not ctx.trace.n_devices:
        return None
    return 100.0 * ctx.trace.idle_share
