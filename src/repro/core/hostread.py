"""Blocking device-to-host reads of the match path, each in a span.

Every read the engine and its backends make to decide on the host (K,
per-emitter or per-device counts, overflow flags, the per-query max,
the hybrid geometry's endpoint copy) goes through ``to_host``, which
wraps ``np.asarray`` in the profiler span ``ddm.sync``.  In a profiler
trace the span shows where the host waited on the device; with no
trace running it costs one check.  A re-emit under the ``grow`` or
``exact`` capacity policy shows as extra ``ddm.sync`` spans.
"""
from __future__ import annotations

import jax
import numpy as np

SYNC_SPAN = "ddm.sync"


def to_host(x, dtype=None) -> np.ndarray:
    """``np.asarray(x, dtype)``, inside one ``ddm.sync`` span."""
    with jax.profiler.TraceAnnotation(SYNC_SPAN):
        return np.asarray(x, dtype)


def host_sum(*arrays) -> int:
    """Exact int64 sum of one or more device arrays, read one at a time
    (one ``ddm.sync`` span each)."""
    return int(sum(np.sum(to_host(a), dtype=np.int64) for a in arrays))
