"""The hybrid (delta-rule and attention) serving step's share of the
chip's bf16 peak: FLOPs needed by the tokens processed in the window
(each prefill that ran in it, each token delivered in it: 2 x the
matmul parameters a token activates on this chip's share, the conv's
and the delta rule's work in the linear layers, attention over p + 1
keys in the full one; benchmarks/flops_qwen3next.py) / window / peak.
None for any other configuration."""
from benchmarks import flops_qwen3next


def read(c):
    if not c.get("token_ranges") or "window_s" not in c \
            or c.get("cfg", {}).get("model_type") != "qwen3_next":
        return None
    need = flops_qwen3next.serve_flops(c["cfg"], c["token_ranges"])
    return 100.0 * need / c["window_s"] / c["peaks"]["bf16_flops"]
