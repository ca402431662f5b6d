"""Share of the emit kernels' time that the HBM roofline needs.

Least time: the bytes the emit must move (``emit_bytes``: K, n, m only)
over the chip's published HBM bandwidth; measured time: the ops named
``emit_*`` in the window.
"""


def read(ctx):
    mask = ctx.trace.select(op=lambda n: n.startswith("emit_"))
    if not ctx.counts.get("emit_bytes") or not mask.any():
        return None
    least_s = ctx.counts["emit_bytes"] / ctx.peak("hbm_bytes_per_s")
    return 100.0 * least_s / ctx.trace.seconds(mask)
