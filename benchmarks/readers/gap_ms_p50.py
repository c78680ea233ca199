"""Median client-side gap between consecutive token lines of a stream:
the batcher's tick as a stream sees it."""
import statistics


def read(c):
    return statistics.median(c["gaps_ms"]) if c.get("gaps_ms") else None
