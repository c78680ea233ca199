"""Median milliseconds a tick's iteration of the engine's loop spends
outside the batcher: the program's ``engine.ingress`` + ``engine.deliver``
spans (intake, stamps under the lock, stream pushes, gauges, waking
waiters; ``engine.metrics()["p50_engine_host_ms"]``).  None where the
program has no such spans."""


def read(c):
    engine = c.get("engine") or {}
    if not engine.get("ticks_total"):
        return None
    return engine.get("p50_engine_host_ms")
