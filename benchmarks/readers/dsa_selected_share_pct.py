"""Share of the decoding rows' keys that the attention's softmax ran
over: 100 x ``sel_keys`` / ``kv_tokens``, medians over the engine's ring
of ticks (the program's own counts, taken in the tick: ``engine.
metrics()`` ``p50_tick_sel_keys`` / ``p50_tick_kv_tokens``).  100 means
the traffic never reached the sparse-attention indexer.  None where the
program does not count ``sel_keys``."""


def read(c):
    engine = c.get("engine") or {}
    if not engine.get("p50_tick_kv_tokens") \
            or "p50_tick_sel_keys" not in engine:
        return None
    return 100.0 * engine["p50_tick_sel_keys"] \
        / engine["p50_tick_kv_tokens"]
