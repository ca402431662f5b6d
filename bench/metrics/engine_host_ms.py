"""Host ms per match in the engine's own code: the self time of the
program's ``ddm.pairs`` spans (``MatchPlan.pairs``), that is their
duration less the ``ddm.sync`` reads inside them: capacity policy,
route choice and dispatch."""
import numpy as np

from bench import xplane

PAIRS, SYNC = "ddm.pairs", "ddm.sync"


def _spans(tr, want):
    return np.array([(s, e) for name, s, e in tr.host if name == want],
                    np.int64).reshape(-1, 2).clip(*tr.window)


def read(ctx):
    tr = ctx.trace
    matches = ctx.counts.get("matches", 0)
    pairs, syncs = _spans(tr, PAIRS), _spans(tr, SYNC)
    if not matches or not pairs.size:
        return None
    # |pairs| - |pairs ∩ syncs| = |pairs ∪ syncs| - |syncs|
    both = np.concatenate([pairs, syncs])
    self_ns = (xplane.union_ns(both[:, 0], both[:, 1])
               - xplane.union_ns(syncs[:, 0], syncs[:, 1]))
    return self_ns / 1e6 / matches
