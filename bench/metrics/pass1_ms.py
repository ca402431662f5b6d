"""Device ms per match outside the emit kernels and the checksum.

Everything else the match runs on the device: sorts, searches, counts,
offset scans and emit-table packing (``core/sbm.py`` through the
``kernels/ops.py`` table programs).
"""


def read(ctx):
    matches = ctx.counts.get("matches", 0)
    mask = ctx.trace.select(op=lambda n: not n.startswith("emit_"),
                            module=lambda m: "bench_checksum" not in m)
    if not matches or not mask.any():
        return None
    return 1e3 * ctx.trace.seconds(mask) / matches
