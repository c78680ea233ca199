"""Prefill's share of the engine time of finished requests: sum of the
``prefill`` phase over the sums of ``prefill`` and ``decode``."""


def read(c):
    phases = c.get("phases") or ()
    prefill = sum(p.get("prefill", 0.0) for p in phases)
    decode = sum(p.get("decode", 0.0) for p in phases)
    if prefill + decode <= 0:
        return None
    return 100.0 * prefill / (prefill + decode)
