"""Share of the decoding rows' keys that a window layer's softmax ran
over: 100 x ``win_keys`` / ``kv_tokens``, medians over the engine's ring
of ticks (the program's own counts, taken in the tick on the device:
``engine.metrics()`` ``p50_tick_win_keys`` / ``p50_tick_kv_tokens``).
100 means no row had passed the window.  None where the program does
not count ``win_keys`` or the model has no window layers (it stays 0)."""


def read(c):
    engine = c.get("engine") or {}
    if not engine.get("p50_tick_kv_tokens") \
            or not engine.get("p50_tick_win_keys"):
        return None
    return 100.0 * engine["p50_tick_win_keys"] \
        / engine["p50_tick_kv_tokens"]
