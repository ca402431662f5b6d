"""Blocking device-to-host reads per match: the program's ``ddm.sync``
spans in the window (``core/hostread.py``: K, per-emitter counts,
overflow flags), over matches.  A re-emit under the capacity policy
shows as extra reads."""

SYNC = "ddm.sync"


def read(ctx):
    matches = ctx.counts.get("matches", 0)
    syncs = sum(1 for name, _, _ in ctx.trace.host if name == SYNC)
    if not matches or not syncs:
        return None
    return syncs / matches
