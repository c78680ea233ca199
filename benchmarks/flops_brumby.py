"""Operations and bytes Brumby-14B-Base needs, from shapes alone: the
whole serving step's numerator (``retention_serve_mfu``), the cut's
parameter count, and the bytes a decode tick streams.

A token pays 2 x the matmul parameters it activates — the q, k, v, o
and gate projections and the gated MLP of every layer built, the untied
head; the embedding is a gather — and the retention's NEEDED work: the
state is a sum over the symmetric second power of the key, ``D = hd (hd
+ 1) / 2`` = 8,256 features, and a token updates it once a KV head (``v
phi(k)^T``: 2 D hd) and reads it once a query head (``S phi(q)``: 2 D
hd), whatever ``phi``'s layout pads to and whichever form (recurrent,
chunked, quadratic) a pass runs."""


def features(cfg):
    hd = cfg["head_dim"]
    return hd * (hd + 1) // 2


def layer_matmul_params(cfg):
    d, hd, f = cfg["hidden_size"], cfg["head_dim"], cfg["intermediate_size"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return 2 * d * hd * (heads + kv) + d * kv + 3 * d * f


def active_matmul_params(cfg):
    return cfg["num_hidden_layers"] * layer_matmul_params(cfg) \
        + cfg["hidden_size"] * cfg["vocab_size"]


def retention_flops_per_token(cfg):
    per_head = 2 * features(cfg) * cfg["head_dim"]
    return cfg["num_hidden_layers"] * per_head * (
        cfg["num_attention_heads"] + cfg["num_key_value_heads"])


def serve_flops(cfg, ranges):
    """FLOPs to process the token ranges ``(a, b)`` — positions a..b-1
    of some sequence, prefilled and decoded tokens alike: a retention
    layer's work a token does not grow with its position."""
    per_token = 2.0 * active_matmul_params(cfg) \
        + retention_flops_per_token(cfg)
    return per_token * sum(b - a for a, b in ranges)


def parameters(cfg):
    """Parameters the chip holds: the cut's arithmetic (the gate's bias,
    the norms' and the QK-norm's gains counted; embedding and head)."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    per_layer = layer_matmul_params(cfg) + cfg["num_key_value_heads"] \
        + 2 * d + 2 * hd
    return cfg["num_hidden_layers"] * per_layer \
        + 2 * d * cfg["vocab_size"] + d


def tick_weight_bytes(cfg, itemsize=2):
    """Bytes of weights a decode tick streams whatever its rows: every
    layer's matrices and the head (the embedding is a gather of rows)."""
    return active_matmul_params(cfg) * itemsize


def state_bytes_per_row(cfg, padded_features=None):
    """Float32 bytes of S and z one slot holds over the layers built,
    at ``padded_features`` a KV head (default the needed 8,256)."""
    n = padded_features or features(cfg)
    return 4 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"] \
        * n * (cfg["head_dim"] + 1)
