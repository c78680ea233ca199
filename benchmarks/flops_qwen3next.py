"""Operations and bytes Qwen3-Next-80B-A3B's cut needs, from shapes
alone: the whole serving step's numerator (``hybrid_serve_mfu``), the
cut's parameter count, and the state a slot holds.

A token pays 2 x the matmul parameters it activates on this chip's
share — every layer's mixer projections (a DeltaNet layer's in_proj_qkvz,
in_proj_ba and out_proj; the attention layer's q, its gate, k, v and o),
every layer's router, shared expert and its gate, the routed experts'
``top_k x held / routed`` pairs that land here on average (5 of 10), and
the head over the vocabulary's slice; the embedding is a gather — plus
the mechanisms' NEEDED work: the conv (2 x taps a channel), the delta
rule's three products with S a value head (read S^T k, the rank-1
write, read S^T q: 6 dk dv), and in the attention layer the softmax
attention over the p + 1 keys of position p (4 x heads x head_dim a
key), whichever form (recurrent, chunked) a pass runs."""


def layer_kinds(cfg):
    """(linear layers, full-attention layers) in the layers built."""
    n, every = cfg["num_hidden_layers"], cfg["full_attention_interval"]
    full = n // every
    return n - full, full


def linear_matmul_params(cfg):
    d = cfg["hidden_size"]
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    return d * (2 * hk * dk + 2 * hv * dv) + d * 2 * hv + hv * dv * d


def attention_matmul_params(cfg):
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    q = cfg["num_attention_heads"] * hd
    return d * (2 * q + 2 * cfg["num_key_value_heads"] * hd) + q * d


def expert_params(cfg):
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def moe_active_params(cfg):
    """A token's expert-layer matmul parameters on this share: router,
    shared expert, its gate, and the held experts' mean share of the
    top-k pairs."""
    d = cfg["hidden_size"]
    first, held = cfg["experts_held"]
    pairs = cfg["num_experts_per_tok"] * held / cfg["num_experts_routed"]
    shared = 3 * d * cfg["shared_expert_intermediate_size"]
    return d * cfg["num_experts_routed"] + shared + d \
        + pairs * expert_params(cfg)


def active_matmul_params(cfg):
    linear, full = layer_kinds(cfg)
    return linear * linear_matmul_params(cfg) \
        + full * attention_matmul_params(cfg) \
        + cfg["num_hidden_layers"] * moe_active_params(cfg) \
        + cfg["hidden_size"] * cfg["vocab_size"]


def channels(cfg):
    return 2 * cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"] \
        + cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"]


def linear_flops_per_token(cfg):
    """The conv's and the delta rule's needed work a token, over the
    linear layers."""
    hv = cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    per_layer = 2 * cfg["linear_conv_kernel_dim"] * channels(cfg) \
        + 6 * hv * dk * dv
    return layer_kinds(cfg)[0] * per_layer


def serve_flops(cfg, ranges):
    """FLOPs to process the token ranges ``(a, b)`` — positions a..b-1
    of some sequence, prefilled and decoded tokens alike."""
    per_token = 2.0 * active_matmul_params(cfg) \
        + linear_flops_per_token(cfg)
    keys = sum((b * (b + 1) - a * (a + 1)) // 2 for a, b in ranges)
    attention = layer_kinds(cfg)[1] * 4 * cfg["num_attention_heads"] \
        * cfg["head_dim"] * keys
    return per_token * sum(b - a for a, b in ranges) + attention


def parameters(cfg):
    """Parameters the chip holds: every leaf of the cut (A_log, dt_bias,
    the gains and the shared expert's gate counted; embedding and head
    slices)."""
    d = cfg["hidden_size"]
    hv, dv = cfg["linear_num_value_heads"], cfg["linear_value_head_dim"]
    linear, full = layer_kinds(cfg)
    moe = d * cfg["num_experts_routed"] \
        + 3 * d * cfg["shared_expert_intermediate_size"] + d \
        + cfg["experts_held"][1] * expert_params(cfg)
    per_linear = linear_matmul_params(cfg) \
        + cfg["linear_conv_kernel_dim"] * channels(cfg) + 2 * hv + dv
    per_full = attention_matmul_params(cfg) + 2 * cfg["head_dim"]
    return linear * per_linear + full * per_full \
        + cfg["num_hidden_layers"] * (moe + 2 * d) \
        + 2 * d * cfg["vocab_size"] + d


def state_bytes_per_row(cfg):
    """Float32 bytes of S and the conv's tail one slot holds over the
    linear layers."""
    hv = cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    tail = (cfg["linear_conv_kernel_dim"] - 1) * channels(cfg)
    return 4 * layer_kinds(cfg)[0] * (hv * dk * dv + tail)


def kv_bytes_per_token(cfg, itemsize=2):
    """Bytes of K and V one token holds in the full-attention layers."""
    return layer_kinds(cfg)[1] * 2 * cfg["num_key_value_heads"] \
        * cfg["head_dim"] * itemsize
