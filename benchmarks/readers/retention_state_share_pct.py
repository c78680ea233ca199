"""How much of a decode tick's HBM traffic the mechanism is: the bytes
of state the tick's decode steps read + wrote (the program's own count
from the shapes they ran on, ``engine.metrics()``
``p50_tick_state_bytes``: padding shows, an idle row does not) over
itself + the weight bytes a tick streams (``flops_brumby``).  None where
the program counts no state bytes or the configuration is not this
model's."""
from benchmarks import flops_brumby


def read(c):
    engine = c.get("engine") or {}
    if not engine.get("p50_tick_state_bytes") \
            or c.get("cfg", {}).get("model_type") != "brumby":
        return None
    state = engine["p50_tick_state_bytes"]
    return 100.0 * state / (state + flops_brumby.tick_weight_bytes(c["cfg"]))
