"""Share of the decode slots that held a decoding row, mean over the
engine's ring of ticks: 100 x ``tick_rows_mean`` / ``slots`` of
``engine.metrics()`` (the program's own count, taken in the tick).
None where the program does not count."""


def read(c):
    engine = c.get("engine") or {}
    if not engine.get("ticks_total") or not engine.get("slots"):
        return None
    return 100.0 * engine["tick_rows_mean"] / engine["slots"]
