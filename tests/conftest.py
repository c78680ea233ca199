"""Test configuration: the suite runs on an 8-device virtual CPU platform
so sharding tests exercise real multi-device semantics without TPU
hardware (the driver separately dry-runs the multi-chip path via
__graft_entry__.dryrun_multichip).  This file owns the CPU pin.

Must run before any jax backend initializes."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# pytest plugins (jaxtyping) import jax before this conftest, and jax
# reads its environment at import — so pin through the live config too
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)


import pytest  # noqa: E402

#: Modules whose every test joins the slow tier (measured on the 1-core
#: CI box, see README "Test tiers": these are the multi-process,
#: compile-heavy, and subprocess-CLI suites).  Individual tests elsewhere
#: opt in with @pytest.mark.slow.  Smoke tier = `pytest -m "not slow"`.
#: BUDGET RULE (README "Test tiers"): the full suite must stay <= 45
#: minutes on the 1-core CI box.  Every NEW slow module must either
#: replace an existing one or document its wall-clock cost here, and
#: each round's session log records a ``--durations=20`` report so
#: creep is visible before it compounds.
SLOW_MODULES = {
    # real multi-process SPMD (jax.distributed over localhost)
    "test_multihost.py",
    # 8-virtual-device shard_map / pjit compile-heavy suites
    "test_parallel.py", "test_pipeline.py",
    "test_seq_parallel_training.py", "test_moe.py",
    # decode/generation: many distinct jit signatures to compile
    "test_generate.py",
    # transformer e2e trainings: 15-54s each on the 1-core CI box
    "test_transformer.py",
    # end-to-end subprocess trainings (fresh jax init per test)
    "test_cli.py", "test_genetics_ensemble.py", "test_elasticity.py",
    # long sweeps / CD-k training loops
    "test_fused_sweep.py", "test_rbm_recurrent.py",
    # r5: two small LM trainings + REST round-trips, ~85 s total
    "test_lora_serving.py",
}


#: Kept in the smoke tier despite living in a slow module — each is the
#: cheapest end-to-end sentinel for a subsystem smoke would otherwise
#: not touch at all.
SMOKE_SENTINELS = {
    "test_transformer_classifier_trains",   # transformer stack e2e
    "test_greedy_generation_continues_pattern",  # KV-cache decode
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        if item.fspath.basename in SLOW_MODULES \
                and item.originalname not in SMOKE_SENTINELS \
                and item.name not in SMOKE_SENTINELS:
            item.add_marker(pytest.mark.slow)


@pytest.fixture
def f32_precision():
    """Force f32 compute (precision_level 1) for tests that compare two
    computation paths tightly — under the default bf16 policy, different
    matmul groupings alone produce ~1e-2 disagreement."""
    from veles_tpu.config import root
    prev = root.common.engine.get("precision_level", 0)
    root.common.engine.precision_level = 1
    try:
        yield
    finally:
        root.common.engine.precision_level = prev
