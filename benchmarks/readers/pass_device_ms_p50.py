"""Median device milliseconds of a staged prefill pass of exactly
``prefill_segment`` tokens, measured inside the program from its
landing stamps as a tick's is (``engine.metrics()
["p50_pass_device_ms"]``, over the engine's ring of ticks).  None where
the program has no stamps or the batcher stages nothing."""


def read(c):
    engine = c.get("engine") or {}
    if not engine.get("ticks_total"):
        return None
    return engine.get("p50_pass_device_ms")
