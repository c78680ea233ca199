"""95th percentile (nearest rank) over all client-side gaps between
consecutive token lines of all streams in the window: the ticks that
carried an admission's prefill."""


def read(c):
    gaps = sorted(c.get("gaps_ms") or ())
    return gaps[min(len(gaps) - 1, int(0.95 * len(gaps)))] if gaps else None
