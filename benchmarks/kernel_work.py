"""The Pallas kernels in a device trace, and the least they could do.

The program names every ``pl.pallas_call`` (``veles_tpu/ops/pallas/
flash.py`` and ``paged.py``, ``KERNEL_NAMES``), and the name is the HLO
instruction's: the device trace shows ``%veles_flash_fwd.1 = ...
custom-call(...)``, which ``trace.short_name`` keeps as the key
``%veles_flash_fwd.1 custom-call (bf16[...], f32[...])`` of
``op_seconds``.  A kernel is found by that name AND the opcode, never by
a substring: a fusion called ``%flash_something`` is not a kernel.  The
five strings are a contract (PERF.md section 3): a later implementation
of the same layer keeps them.  A program without them (the parent of
the PR that named them; the CPU rehearsal, which interprets the
kernels) has nothing to read, and the readers return None.

The least time follows the on-chip-measurement guide, section 4: the
larger of operations over the peak FLOP/s and bytes over the peak
bytes/s, both from shapes alone (``flops.py``)."""

import re

from benchmarks import flops

FLASH = ("veles_flash_fwd", "veles_flash_bwd_dq", "veles_flash_bwd_dkv")
PAGED = ("veles_paged_decode", "veles_paged_decode_q8")

#: bytes of one element of q, k, v, o and their gradients as the flash
#: kernels read and write them (the configurations' ``precision``:
#: "flash attention on bfloat16 q, k, v")
FLASH_ITEMSIZE = 2


def kernel_seconds(op_seconds, names):
    """Device seconds (self time) of the custom calls whose instruction
    is named one of ``names``, with or without the compiler's numeric
    suffixes; None where the trace holds none."""
    pattern = re.compile("^%%?(?:%s)(?:\\.\\d+)*$"
                         % "|".join(re.escape(n) for n in names))
    found = [seconds for key, seconds in op_seconds.items()
             if key.split(" ")[1:2] == ["custom-call"]
             and pattern.match(key.split(" ")[0])]
    return sum(found) if found else None


def flash_least_seconds(cfg, traffic, peaks):
    """The least seconds the chip could spend on attention in ONE
    training step, and the bound that sets it (``"flops"`` or
    ``"bytes"``).  Operations: per layer, forward + backward = 3 x one
    causal forward (``flops.causal_attn_flops``; the backward's
    recomputed scores are not work).  Bytes: per layer q, k, v, o read
    or written once by the forward and dO, dq, dk, dv by the backward,
    eight [batch, heads, seq, head_dim] tensors."""
    b, t, h = traffic["batch"], traffic["seq"], cfg["n_head"]
    d = cfg["n_embd"] // h
    ops = cfg["n_layer"] * 3 * flops.causal_attn_flops(b, h, t, d)
    moved = cfg["n_layer"] * 8 * b * h * t * d * FLASH_ITEMSIZE
    by_ops = ops / peaks["bf16_flops"]
    by_bytes = moved / peaks["hbm_bytes_per_s"]
    return max(by_ops, by_bytes), "flops" if by_ops >= by_bytes else "bytes"


def decode_kv_bytes(cfg, ranges, itemsize):
    """Bytes of keys and values the decode steps of ``ranges`` must
    read, whatever the kernel's grid does: a token decoded at position
    p attends p + 1 keys, each ``flops.kv_bytes_per_token`` over all
    layers.  A range ``(a, b)`` is positions a..b-1 of one sequence;
    one that starts at 0 is a prefill and is left out."""
    positions = sum((b * (b + 1) - a * (a + 1)) // 2
                    for a, b in ranges if a != 0)
    return positions * flops.kv_bytes_per_token(cfg, itemsize)
