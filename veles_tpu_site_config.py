"""Site config — generated by tools/bake_flashtune.py from an
on-hardware flashtune sweep; do not hand-edit the numbers, re-run the
sweep.  Loaded automatically (config.apply_site_config)."""


def update(root):
    root.common.engine.flash.block_q = 512
    root.common.engine.flash.block_k = 512
    # provenance: a flashtune sweep fitted 2026-08-01, not re-measured
    # on this machine (ms fwd / fwd+bwd then: T=1024: 2.772/5.674;
    # T=8192: 4.132/10.656).  ROADMAP D2 removes this route.
