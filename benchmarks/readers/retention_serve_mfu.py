"""The retention model's serving step's share of the chip's bf16 peak:
FLOPs needed by the tokens processed in the window (each prefill that
ran in it, each token delivered in it: 2 x the matmul parameters a
token activates — 8 layers and the head — and the retention's needed
2 x 8,256 x 128 a query head and a KV head, a layer;
benchmarks/flops_brumby.py) / window / peak.  None for any other
configuration."""
from benchmarks import flops_brumby


def read(c):
    if not c.get("token_ranges") or "window_s" not in c \
            or c.get("cfg", {}).get("model_type") != "brumby":
        return None
    need = flops_brumby.serve_flops(c["cfg"], c["token_ranges"])
    return 100.0 * need / c["window_s"] / c["peaks"]["bf16_flops"]
