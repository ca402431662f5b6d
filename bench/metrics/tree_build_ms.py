"""Device ms per rebuild in the interval-tree builds (``itm._build``).

A rebuild builds both trees of one tenant's snapshot.
"""


def read(ctx):
    rebuilds = ctx.counts.get("rebuilds", 0)
    secs = ctx.trace.run_seconds(lambda m: m == "jit__build")
    if not rebuilds or not secs:
        return None
    return 1e3 * secs / rebuilds
