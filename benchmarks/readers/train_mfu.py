"""The whole training step's share of the chip's bf16 peak: FLOPs the
forward and backward passes need per token (benchmarks/flops.py; no
recomputed operation counted) x tokens/s of this run / peak."""
from benchmarks import flops


def read(c):
    if "tokens_per_s" not in c or c["traffic"].get("kind") != "train":
        return None
    per_token = flops.lm_train_flops_per_token(c["cfg"], c["traffic"]["seq"])
    return 100.0 * per_token * c["tokens_per_s"] / c["peaks"]["bf16_flops"]
