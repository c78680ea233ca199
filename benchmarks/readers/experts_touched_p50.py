"""Distinct experts a tick's rows routed a token to, mean over the
expert layers, median over the engine's ring of ticks (``engine.
metrics()`` ``p50_tick_experts_touched``: counted on the device in the
fused tick).  None where the program does not count it or the model
has no dropless expert layers (the count stays 0)."""


def read(c):
    engine = c.get("engine") or {}
    return engine.get("p50_tick_experts_touched") or None
