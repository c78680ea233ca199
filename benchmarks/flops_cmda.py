"""Operations command-a-plus-05-2026's language model needs on THIS
chip's share, from shapes alone: the whole serving step's numerator
(``mixed_serve_mfu``).

A token pays 2 x the matmul parameters it ACTIVATES here — the
attention projections, the four shared experts, the router, the routed
experts that are held (``num_experts_per_tok x experts held / experts
routed`` of them on average: 1 at 16 of 128), the sliced head; the
embedding is a gather — and attention (q.k and p.v) over ``min(p + 1,
sliding_window)`` keys in a sliding layer and ``p + 1`` in a full one."""


def layer_kinds(cfg):
    """``(sliding layers, full layers)`` of the layers built."""
    kinds = [cfg["layer_types"][i % len(cfg["layer_types"])]
             for i in range(cfg["num_hidden_layers"])]
    sliding = sum(k == "sliding_attention" for k in kinds)
    return sliding, len(kinds) - sliding


def routed_experts_here(cfg):
    """Routed experts a token activates on this share, on average."""
    return cfg["num_experts_per_tok"] * cfg["experts_held"][1] \
        / cfg["num_experts_routed"]


def active_matmul_params(cfg):
    d, hd, f = cfg["hidden_size"], cfg["head_dim"], cfg["intermediate_size"]
    attn = 2 * d * hd * (cfg["num_attention_heads"]
                         + cfg["num_key_value_heads"])
    shared = cfg["num_shared_experts"] * 3 * d * f
    routed = routed_experts_here(cfg) * 3 * d * f
    router = d * cfg["num_experts_routed"]
    return cfg["num_hidden_layers"] * (attn + shared + routed + router) \
        + d * cfg["vocab_size"]


def serve_flops(cfg, ranges):
    """FLOPs to process the token ranges ``(a, b)`` — positions a..b-1
    of some sequence, prefilled and decoded tokens alike.  The token at
    position p attends min(p + 1, window) keys in each sliding layer
    and p + 1 in each full one (4 x heads x head_dim a key: q.k and
    p.v)."""
    sliding, full = layer_kinds(cfg)
    window = cfg["sliding_window"]
    per_key = 4 * cfg["num_attention_heads"] * cfg["head_dim"]
    active = 2.0 * active_matmul_params(cfg)

    def keys_upto(n):                       # sum over p < n of (p + 1)
        return n * (n + 1) / 2.0

    def band_upto(n):                       # sum of min(p + 1, window)
        m = min(n, window)
        return m * (m + 1) / 2.0 + (n - m) * window

    total = 0.0
    for a, b in ranges:
        total += active * (b - a)
        total += full * per_key * (keys_upto(b) - keys_upto(a))
        total += sliding * per_key * (band_upto(b) - band_upto(a))
    return total


def kv_bytes_per_token_layer(cfg, itemsize):
    """Bytes one token holds in one layer's pool leaves: K and V of
    every KV head."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * itemsize


def parameters(cfg):
    """Parameters the chip holds: the cut's arithmetic (the embedding
    is the tied head's; norms' gains counted)."""
    d, hd, f = cfg["hidden_size"], cfg["head_dim"], cfg["intermediate_size"]
    attn = 2 * d * hd * (cfg["num_attention_heads"]
                         + cfg["num_key_value_heads"])
    experts = (cfg["num_shared_experts"] + cfg["experts_held"][1]) * 3 * d * f
    per_layer = attn + experts + d * cfg["num_experts_routed"] + d
    return cfg["num_hidden_layers"] * per_layer + d * cfg["vocab_size"] + d
