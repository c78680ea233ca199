"""Median milliseconds per training step: the time between two sweeps
becoming ready on the device, over the steps of one dispatch."""
import statistics


def read(c):
    if not c.get("sweep_ms"):
        return None
    return statistics.median(c["sweep_ms"]) / c["steps_per_dispatch"]
