"""Median device milliseconds of a plain batcher tick — one that
admitted and staged nothing — measured inside the program from its
landing stamps: the exact landing of the dispatch before it (or its own
enqueue onto an empty queue) to its own exact landing
(``engine.metrics()["p50_tick_device_ms"]``, over the engine's ring of
ticks).  None where the program has no stamps or saw no plain tick
whole."""


def read(c):
    engine = c.get("engine") or {}
    if not engine.get("ticks_total"):
        return None
    return engine.get("p50_tick_device_ms")
