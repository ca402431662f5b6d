"""Device ms per match left idle while the host waited in a blocking
read: device 0's idle gaps in the window, intersected with the union of
the program's ``ddm.sync`` spans, over matches."""
import numpy as np

from bench import xplane

SYNC = "ddm.sync"


def read(ctx):
    tr = ctx.trace
    matches = ctx.counts.get("matches", 0)
    syncs = np.array([(s, e) for name, s, e in tr.host if name == SYNC],
                     np.int64).reshape(-1, 2).clip(*tr.window)
    if not matches or not syncs.size or not tr.n_devices:
        return None
    on0 = tr.device == 0
    gaps = np.array(xplane.gaps_ns(tr.start[on0], tr.end[on0], *tr.window),
                    np.int64).reshape(-1, 2)
    # |gaps ∩ syncs| = |gaps| + |syncs| - |gaps ∪ syncs|
    both = np.concatenate([gaps, syncs])
    idle_ns = (xplane.union_ns(gaps[:, 0], gaps[:, 1])
               + xplane.union_ns(syncs[:, 0], syncs[:, 1])
               - xplane.union_ns(both[:, 0], both[:, 1]))
    return idle_ns / 1e6 / matches
