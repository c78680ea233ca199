"""Share of the engine's calls of the batcher — those that found work
waiting, a cold one is none — whose first enqueue went onto an empty
device queue: 100 x ``engine.metrics()["drained_share"]`` (the
program's own count, by a non-blocking readiness check, over the
engine's ring of ticks).  None where the program does not count."""


def read(c):
    engine = c.get("engine") or {}
    if not engine.get("ticks_total") or engine.get("drained_share") is None:
        return None
    return 100.0 * engine["drained_share"]
