"""Operations the Keye-VL-2.0 language model needs, from shapes alone:
the whole serving step's numerator (``sparse_serve_mfu``).

A token pays 2 x the matmul parameters it ACTIVATES (attention and
indexer projections, the router, ``num_experts_per_tok`` experts of
three matrices, the head; the embedding is a gather), its index scores
against every key at or before it, and attention (q.k and p.v) over the
``min(p + 1, topk)`` keys the indexer selects."""


def active_matmul_params(cfg):
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    sa = cfg["sa_config"]
    attn = 2 * d * hd * (cfg["num_attention_heads"]
                         + cfg["num_key_value_heads"])
    index = d * (sa["indexer_num_heads"] * sa["indexer_head_dim"]
                 + sa["indexer_head_dim"] + sa["indexer_num_heads"])
    experts = cfg["num_experts_per_tok"] * 3 * d \
        * cfg["moe_intermediate_size"]
    router = d * cfg["num_experts"]
    return cfg["num_hidden_layers"] * (attn + index + router + experts) \
        + d * cfg["vocab_size"]


def serve_flops(cfg, ranges):
    """FLOPs to process the token ranges ``(a, b)`` — positions a..b-1
    of some sequence.  The token at position p scores p + 1 index keys
    (2 x heads x head_dim each, per layer) and attends min(p + 1, topk)
    keys (4 x heads x head_dim each: q.k and p.v, per layer)."""
    sa = cfg["sa_config"]
    layers, topk = cfg["num_hidden_layers"], sa["topk"]
    per_index_key = 2 * sa["indexer_num_heads"] * sa["indexer_head_dim"]
    per_attn_key = 4 * cfg["num_attention_heads"] * cfg["head_dim"]
    active = 2.0 * active_matmul_params(cfg)

    def keys_upto(n):                       # sum over p < n of (p + 1)
        return n * (n + 1) / 2.0

    def sel_upto(n):                        # sum of min(p + 1, topk)
        m = min(n, topk)
        return m * (m + 1) / 2.0 + (n - m) * topk

    total = 0.0
    for a, b in ranges:
        total += active * (b - a)
        total += layers * per_index_key * (keys_upto(b) - keys_upto(a))
        total += layers * per_attn_key * (sel_upto(b) - sel_upto(a))
    return total


def state_bytes_per_token(cfg, itemsize):
    """Bytes one token holds in the pool over all layers: K and V of
    every KV head and the one index key."""
    per_layer = 2 * cfg["num_key_value_heads"] * cfg["head_dim"] \
        + cfg["sa_config"]["indexer_head_dim"]
    return cfg["num_hidden_layers"] * per_layer * itemsize
