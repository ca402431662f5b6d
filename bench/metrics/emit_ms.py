"""Device ms per match in the emit kernels (ops named ``emit_*``)."""


def read(ctx):
    matches = ctx.counts.get("matches", 0)
    mask = ctx.trace.select(op=lambda n: n.startswith("emit_"))
    if not matches or not mask.any():
        return None
    return 1e3 * ctx.trace.seconds(mask) / matches
