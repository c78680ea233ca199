"""Operations and bytes the algorithm needs, from shapes alone.

Copied from ``veles_tpu/ops/flops.py`` (``lm_train_flops_per_token``,
``causal_attn_flops``) so that no later PR can move the numerator of a
utilization; the original is listed in PERF.md's Open questions."""


def causal_attn_flops(b, h, t, d):
    """Matmul FLOPs of ONE causal attention forward (qk + pv, each
    2*b*h*t*(t/2)*d with the triangular mask halving effective keys)."""
    return 4 * b * h * t * t * d / 2


def lm_forward_flops_per_token(cfg, seq):
    """Matmul FLOPs of one forward pass per token at context ``seq``:
    per layer q/k/v/o projections 8*d^2, MLP 4*d*d_ff, causal attention
    2*seq*d (seq/2 effective keys, qk + pv), plus the 2*d*V head.  The
    embedding lookup is a gather: no FLOPs."""
    d, d_ff = cfg["n_embd"], cfg["n_inner"]
    per_layer = 8 * d * d + 4 * d_ff * d + 2 * seq * d
    return cfg["n_layer"] * per_layer + 2 * d * cfg["vocab_size"]


def lm_train_flops_per_token(cfg, seq):
    """Forward + backward = 3x forward; recomputed operations (remat)
    do not count."""
    return 3 * lm_forward_flops_per_token(cfg, seq)


def matmul_params(cfg):
    """Parameters that take part in a matmul per token (the tied head
    counted once, as the head; embedding and position lookups are
    gathers)."""
    d, d_ff = cfg["n_embd"], cfg["n_inner"]
    return cfg["n_layer"] * (4 * d * d + 2 * d * d_ff) \
        + d * cfg["vocab_size"]


def lm_serve_flops(cfg, ranges):
    """FLOPs to process the token ranges ``(a, b)`` — positions a..b-1
    of some sequence, a prefill from 0 or decoded tokens further on:
    every token pays 2 x matmul parameters, and the token at position p
    attends to p+1 keys (4*d per key, qk + pv, per layer)."""
    d, layers = cfg["n_embd"], cfg["n_layer"]
    total = 0.0
    for a, b in ranges:
        total += 2.0 * matmul_params(cfg) * (b - a)
        total += layers * 4.0 * d * (b * (b + 1) - a * (a + 1)) / 2.0
    return total


def n_params(cfg):
    """All parameters of the GPT-2 block stack with a tied head."""
    d, d_ff = cfg["n_embd"], cfg["n_inner"]
    per_layer = 4 * d * d + 4 * d + 2 * d * d_ff + d_ff + d + 4 * d
    return (cfg["vocab_size"] + cfg["n_positions"]) * d \
        + cfg["n_layer"] * per_layer + 2 * d


def kv_bytes_per_token(cfg, itemsize):
    """Bytes of keys and values one token holds in the cache, over all
    layers (as many KV heads as heads in a GPT-2 block)."""
    return 2 * cfg["n_layer"] * cfg["n_embd"] * itemsize
