"""The serving step's share of the chip's bf16 peak: FLOPs needed by the
tokens processed in the window (each prefill that ran in it, each token
delivered in it: 2 x matmul parameters a token plus attention over its
own context; benchmarks/flops.py) / window / peak."""
from benchmarks import flops


def read(c):
    if not c.get("token_ranges") or "window_s" not in c:
        return None
    need = flops.lm_serve_flops(c["cfg"], c["token_ranges"])
    return 100.0 * need / c["window_s"] / c["peaks"]["bf16_flops"]
