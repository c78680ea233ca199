"""Pallas TPU kernels for the hot ops (see /opt/skills/guides/pallas_guide.md).

Kernels here are the hand-tiled VMEM path; every one has an XLA or numpy
equivalent elsewhere in ops/ that serves as ground truth in the tests.
Off-TPU the kernels run in interpret mode (``interpret=None`` auto-detects),
so the same code is exercised by the CPU test suite.

Every kernel module also registers a **launch-audit hook**
(:func:`register_kernel_audit`): a pure function that reports the
kernel's launch geometry — VMEM block shapes, scratch allocations, grid
divisibility, masking — for its *configured* block sizes, without
building or compiling anything.  ``analysis.numerics_audit`` runs the
VP6xx rules (tile alignment, ragged-grid masking, VMEM footprint) over
these descriptions, so a mis-sized ``root.common.engine.flash.block_q``
or an over-budget tile is caught by ``veles-tpu-lint --numerics``
before any chip sees the kernel (docs/static_analysis.md)."""

import jax


def autodetect_interpret(interpret):
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def mosaic_sublane_min(dtype):
    """Mosaic's minimum second-to-last-dim tile for ``dtype`` on TPU:
    8 rows for 4-byte types, 16 for bf16/f16, 32 for int8/fp8 (pallas
    guide, 'Block shape alignment').  THE one copy of the table: the
    paged batcher's construction check (models.generate) and the VP600
    tile lint (analysis.numerics_audit) must agree on which blocks
    compile."""
    import numpy as np
    return {4: 8, 2: 16, 1: 32}.get(np.dtype(dtype).itemsize, 8)


#: kernel name -> callable() -> [launch dict] (the shape
#: ``analysis.numerics_audit.audit_kernel_launch`` consumes)
KERNEL_AUDITS = {}


def register_kernel_audit(name):
    """Decorator: register a zero-arg launch-description hook for the
    VP6xx Pallas audit.  The hook must be pure geometry — no tracing,
    no compilation, no device access."""
    def deco(fn):
        KERNEL_AUDITS[name] = fn
        return fn
    return deco


def kernel_audit_launches():
    """All registered kernels' launch descriptions at their configured
    geometry.  Importing the kernel modules here (not at package
    import) keeps the base package light — the audit is the only
    consumer."""
    from veles_tpu.ops.pallas import (  # noqa: F401 — register
        deltanet, dsa, flash, head_xent, paged)
    launches = []
    for name in sorted(KERNEL_AUDITS):
        launches.extend(KERNEL_AUDITS[name]())
    return launches
