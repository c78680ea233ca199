"""Median host milliseconds of one batcher tick: the program's
``batcher.tick`` span less the time it was blocked on the device
(``batcher.wait``) — admission's host part, the dispatch, the host
reads and the emission (``engine.metrics()["p50_tick_host_ms"]``, over
the engine's ring of ticks).  None where the program has no such
spans."""


def read(c):
    engine = c.get("engine") or {}
    if not engine.get("ticks_total"):
        return None
    return engine.get("p50_tick_host_ms")
