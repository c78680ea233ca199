"""The sparse serving step's share of the chip's bf16 peak: FLOPs needed
by the tokens processed in the window (each prefill that ran in it,
each token delivered in it: 2 x the matmul parameters a token
activates, its index scores over its whole context, attention over the
min(p + 1, topk) keys selected; benchmarks/flops_keye.py) / window /
peak.  None for a configuration without an ``sa_config``."""
from benchmarks import flops_keye


def read(c):
    if not c.get("token_ranges") or "window_s" not in c \
            or "sa_config" not in c.get("cfg", {}):
        return None
    need = flops_keye.serve_flops(c["cfg"], c["token_ranges"])
    return 100.0 * need / c["window_s"] / c["peaks"]["bf16_flops"]
