"""The window-and-full serving step's share of the chip's bf16 peak:
FLOPs needed by the tokens processed in the window (each prefill that
ran in it, each token delivered in it: 2 x the matmul parameters a
token activates on this chip's share, attention over min(p + 1, window)
keys in the sliding layers and p + 1 in the full ones;
benchmarks/flops_cmda.py) / window / peak.  None for a configuration
without ``layer_types``."""
from benchmarks import flops_cmda


def read(c):
    if not c.get("token_ranges") or "window_s" not in c \
            or "layer_types" not in c.get("cfg", {}):
        return None
    need = flops_cmda.serve_flops(c["cfg"], c["token_ranges"])
    return 100.0 * need / c["window_s"] / c["peaks"]["bf16_flops"]
