"""The flash attention kernels' share of their roofline in a training
step: least time / kernel time, both per step.  Kernel time per step =
device seconds of ``veles_flash_fwd``, ``veles_flash_bwd_dq`` and
``veles_flash_bwd_dkv`` in the traced window / that window x the
median step (the device is busy 99.98% of a training window, so the
window's share is the step's share).  Least time: the larger of the
attention FLOPs over the bf16 peak and the q, k, v, o, dO, dq, dk, dv
bytes over the HBM peak (``kernel_work.flash_least_seconds``); at both
accepted training shapes (hd 64 x T 1024, hd 128 x T 2048) the FLOPs
bound it, by 1.6 and 3.2 times.  None where the trace names no such
kernel."""
import statistics

from benchmarks import kernel_work


def read(c):
    tr = c.get("trace")
    if not tr or not c.get("sweep_ms") or tr["window_s"] <= 0:
        return None
    busy = kernel_work.kernel_seconds(tr["op_seconds"], kernel_work.FLASH)
    if not busy:
        return None
    step_s = statistics.median(c["sweep_ms"]) / 1e3 / c["steps_per_dispatch"]
    least, _ = kernel_work.flash_least_seconds(c["cfg"], c["traffic"],
                                               c["peaks"])
    return 100.0 * least / (busy / tr["window_s"] * step_s)
