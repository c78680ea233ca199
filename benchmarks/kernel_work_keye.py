"""The sparse decode path in a device trace, and the least it could do.

The path is XLA, not a named Pallas kernel (``veles_paged_decode_sparse``
stays the name of the kernel that replaces it), so the trace holds no
instruction of that name.  What it holds is the ``conditional`` the
program puts the path under (``ops/attention.mha_step_paged``: taken
when a row of the tick stands at or past ``topk`` positions), and that
conditional alone returns the rows' attention AND how many keys each
attended: ``(f32|bf16[slots, heads, head_dim], s32[slots])``.  It is
found by opcode and result type, both from the cell's shapes; its
device time is the event's whole span, everything nested inside it
included (index scores over the table's pages, ``dsa_select``'s
counting passes, the running count and its search, the token gathers
of the pool, the attention over the selected keys).  A later kernel
placed in that branch is read the same way.

The least bytes follow ISSUE 29's count for the kernel to come: the
index keys of a row's whole context and K and V of the keys selected."""

import re

from benchmarks import trace


def sparse_decode_bytes(cfg, ranges, itemsize):
    """Bytes the sparse decode path must read for the decode steps of
    ``ranges``, whatever it does: a token decoded at position p >=
    ``topk`` scores the index key of each of its p + 1 keys and attends
    ``topk`` of them (K and V of every KV head), in every layer.  A
    range ``(a, b)`` is positions a..b-1 of one sequence; one that
    starts at 0 is a prefill and is left out, and so are positions
    under ``topk``, which take the dense ``veles_paged_decode``."""
    sa = cfg["sa_config"]
    topk = sa["topk"]
    per_index_key = sa["indexer_head_dim"] * itemsize
    per_selected = 2 * cfg["num_key_value_heads"] * cfg["head_dim"] \
        * itemsize
    total = 0
    for a, b in ranges:
        if a == 0:
            continue
        a = max(a, topk)
        if b > a:
            total += (b * (b + 1) - a * (a + 1)) // 2 * per_index_key \
                + (b - a) * topk * per_selected
    return total * cfg["num_hidden_layers"]


def is_sparse_decode(cfg, traffic):
    """``short name -> bool``: the conditional the sparse decode path
    runs under, by opcode and result type (see the module's text)."""
    pattern = re.compile(
        r"^\S+ conditional \((?:f32|bf16)\[%d,%d,%d\], s32\[%d\]\)$"
        % (traffic["slots"], cfg["num_attention_heads"], cfg["head_dim"],
           traffic["slots"]))
    return lambda name: bool(pattern.match(name))


def enclosed_seconds(profile, wanted, chips=1,
                     device_plane=trace.DEVICE_PLANE,
                     op_line=trace.OP_LINE):
    """Device seconds a chip, nested operations included, of the events
    whose ``trace.short_name`` ``wanted`` accepts, and how many there
    were a chip; None where the trace holds none."""
    total, n = 0.0, 0
    for plane in profile.planes:
        if not plane.name.startswith(device_plane):
            continue
        for line in plane.lines:
            if not line.name.startswith(op_line):
                continue
            for ev in line.events:
                if wanted(trace.short_name(ev.name)):
                    total += ev.duration_ns
                    n += 1
    return {"seconds": total / 1e9 / chips, "events": n / chips} if n \
        else None


def sparse_decode_seconds(tracer, cfg, traffic, chips=1):
    """``enclosed_seconds`` of the sparse decode conditional over the
    ``.xplane.pb`` of a ``harness.TraceWindow`` that has stopped and
    not yet been reduced (reducing deletes the file); None where there
    is no file or no such event."""
    import glob
    import os

    import jax
    paths = glob.glob(os.path.join(tracer.dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        return None
    return enclosed_seconds(jax.profiler.ProfileData.from_file(paths[0]),
                            is_sparse_decode(cfg, traffic), chips)
