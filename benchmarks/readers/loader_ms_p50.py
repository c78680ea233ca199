"""Median host milliseconds of one ``loader.run()`` in the window."""
import statistics


def read(c):
    return statistics.median(c["loader_ms"]) if c.get("loader_ms") else None
