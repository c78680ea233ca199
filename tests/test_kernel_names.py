"""The names the Pallas kernels carry into the compiled program
(``flash.KERNEL_NAMES`` / ``paged.KERNEL_NAMES`` / ``dsa.KERNEL_NAMES``):
the benchmark's ``flash_roofline_pct`` and ``paged_roofline_pct`` find
the kernels in a device trace by the HLO instruction's name, so each
name must reach the program compiled for the chip.

Each kernel is compiled at a tiny size for a DESCRIBED v5e (a compile,
not a run; the way ``benchmarks/compile_check.py`` does) and its name
looked for in ``compiled.as_text()``.  Where libtpu cannot describe a
topology (another process of this machine holds it), the same names are
read off the ``pallas_call`` equations of the jaxpr instead, so the
tests pass either way and never skip.  The topology is described in a
fixture of this one file, never at import."""

import os

import jax
import jax.numpy as jnp
import pytest

from veles_tpu.ops import attention as att
from veles_tpu.ops.pallas import dsa, flash, paged
from veles_tpu.ops.pallas import retention as retention_kernel

os.environ.setdefault("TPU_LOG_DIR", "disabled")


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, or None."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception:   # noqa: BLE001 — no libtpu, or it is held
        return None
    return SingleDeviceSharding(topo.devices[0])


def pallas_call_names(jaxpr):
    """Names of every ``pallas_call`` equation, sub-jaxprs included."""
    names = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            names.append(eqn.params["name"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names += pallas_call_names(sub)
    return names


def kernel_names(fn, args, one_chip):
    """The kernel names of ``fn(*args)``: from the HLO compiled for the
    described chip (``%name.N = ... custom-call``), else the jaxpr's."""
    if one_chip is None:
        return set(pallas_call_names(jax.make_jaxpr(fn)(*args).jaxpr))
    shapes = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=one_chip), tuple(args))
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        text = jax.jit(fn).lower(*shapes).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
    assert "tpu_custom_call" in text
    return {line.split("%", 1)[1].split(" ", 1)[0].split(".")[0]
            for line in text.splitlines()
            if "custom-call(" in line and "%" in line.split("=")[0]}


def flash_fwd_bwd(q, k, v):
    def loss(q, k, v):
        return att.flash_attention(
            q, k, v, causal=True, block_q=128, block_k=128,
            interpret=False).astype(jnp.float32).sum()
    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


def paged_decode(q, pool_k, pool_v, table, pos):
    return paged.paged_attention_decode(q, pool_k, pool_v, table, pos,
                                        interpret=False)


def paged_decode_window(q, pool_k, pool_v, table, pos):
    return paged.paged_attention_decode(q, pool_k, pool_v, table, pos,
                                        interpret=False, window=40)


def pass_attention(q, k, v, start, window=None):
    """The attention of one staged prefill pass of a model WITHOUT an
    indexer as ``mha_chunk_step`` calls it (``chunk_attend``), ``start``
    traced: the kernel is ``veles_dsa_prefill`` under a causal (and
    band) mask."""
    real = dsa.autodetect_interpret
    dsa.autodetect_interpret = lambda interpret: False
    try:
        return att.chunk_attend(q, k, v, start, window)
    finally:
        dsa.autodetect_interpret = real


def pass_attention_args(tk):
    """A 2,048-token pass of ``cmdaplus.serve_mixed``: 128 query / 8 KV
    heads of 128 over the full layer's row of 34,816 keys, or a window
    layer's ring of 6,160 — shapes only."""
    def s(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype)
    return (s(1, 128, 2048, 128), s(1, 8, tk, 128), s(1, 8, tk, 128),
            s(dtype=jnp.int32))


def retention_decode(s, f, v, decay, active):
    return retention_kernel.retention_decode(s, f, 5, v, decay, active,
                                             interpret=False)


def retention_args():
    """One retention layer's decode step of ``brumby14.serve_decode`` at
    two rows: 8 KV heads of 128 under 5 query heads each, the state
    [8, 128, 8320] float32 — shapes only."""
    def s(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype)
    b, hkv, hd, dp = 2, 8, 128, 8320
    return (s(b, hkv, hd, dp), s(b, hkv, 8, dp),
            s(b, hkv, hd, dtype=jnp.bfloat16), s(b, hkv),
            s(b, dtype=jnp.bool_))


def flash_args():
    x = jnp.zeros((1, 2, 256, 128), jnp.bfloat16)
    return (x, x, x)


def paged_args(quant):
    b, h, hd, bs, nbm = 2, 2, 128, 32, 4
    q = jnp.zeros((b, h, hd), jnp.bfloat16)
    table = jnp.zeros((b, nbm), jnp.int32)
    pos = jnp.zeros((b,), jnp.int32)
    if quant:
        pool = att.QuantCache(
            jnp.zeros((1 + b * nbm, h, bs, hd), jnp.int8),
            jnp.ones((1 + b * nbm, h, bs, 1), jnp.float32))
    else:
        pool = jnp.zeros((1 + b * nbm, h, bs, hd), jnp.bfloat16)
    return (q, pool, pool, table, pos)


def staged_pass(q, k, v, qi, ki, wi, start):
    """The attention of one staged prefill pass as ``mha_chunk_step``
    calls it, ``start`` traced.  Nothing but the platform tells
    ``dsa_attend`` to compile its kernel, so the test says so here."""
    real = dsa.autodetect_interpret
    dsa.autodetect_interpret = lambda interpret: False
    try:
        return att.dsa_attend(q, k, v, qi, ki, wi, start, 2048,
                              live_keys=start + q.shape[2])
    finally:
        dsa.autodetect_interpret = real


def staged_pass_args():
    """A 2,048-token pass of ``keye30.serve_long``: 32 query / 4 KV
    heads of 128 and 16 index heads of 64 over a row of 34,816 keys,
    bf16 — shapes only, nothing of this size is allocated."""
    def s(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype)
    tq, tk = 2048, 34816
    return (s(1, 32, tq, 128), s(1, 4, tk, 128), s(1, 4, tk, 128),
            s(1, 16, tq, 64), s(1, 1, tk, 64),
            s(1, tq, 16, dtype=jnp.float32), s(dtype=jnp.int32))


@pytest.mark.parametrize("fn,args,expected", [
    (flash_fwd_bwd, flash_args,
     {flash.KERNEL_NAMES[k][0] for k in ("forward", "bwd_dq",
                                         "bwd_dkv")}),
    (paged_decode, lambda: paged_args(False),
     {paged.KERNEL_NAMES[False][0]}),
    (paged_decode, lambda: paged_args(True),
     {paged.KERNEL_NAMES[True][0]}),
    (staged_pass, staged_pass_args, {dsa.KERNEL_NAMES["prefill"][0]}),
    (paged_decode_window, lambda: paged_args(False),
     {paged.KERNEL_NAMES_WINDOW[False][0]}),
    (paged_decode_window, lambda: paged_args(True),
     {paged.KERNEL_NAMES_WINDOW[True][0]}),
    (pass_attention, lambda: pass_attention_args(34816),
     {dsa.KERNEL_NAMES["prefill"][0]}),
    (lambda q, k, v, start: pass_attention(q, k, v, start, 4096),
     lambda: pass_attention_args(6160), {dsa.KERNEL_NAMES["prefill"][0]}),
    (retention_decode, retention_args,
     {retention_kernel.KERNEL_NAMES["decode"][0]}),
], ids=["flash", "paged", "paged_q8", "dsa_prefill", "paged_window",
        "paged_window_q8", "pass_full_layer", "pass_window_ring",
        "retention_decode"])
def test_kernel_names_reach_the_program(fn, args, expected, one_chip):
    assert expected <= kernel_names(fn, args(), one_chip)


def test_the_six_names_are_the_contract():
    """PERF.md records these strings; the benchmark's readers match
    them.  A rename here is a rename of the yardstick.  (Written as the
    HLO writes an instruction, with its ``%``.)"""
    assert ["%" + flash.KERNEL_NAMES[k][0]
            for k in ("forward", "bwd_dq", "bwd_dkv")] == [
        "%veles_flash_fwd", "%veles_flash_bwd_dq", "%veles_flash_bwd_dkv"]
    assert ["%" + paged.KERNEL_NAMES[q][0] for q in (False, True)] == [
        "%veles_paged_decode", "%veles_paged_decode_q8"]
    assert ["%" + name for name, _ in dsa.KERNEL_NAMES.values()] == [
        "%veles_dsa_prefill"]


def test_the_retention_kernels_name_is_the_contract():
    """The decode step of a retention layer (PERF.md, section 3):
    ``retention_decode_roofline_pct`` sums it."""
    assert ["%" + name for name, _ in
            retention_kernel.KERNEL_NAMES.values()] == [
        "%veles_retention_decode"]


def test_the_window_kernels_names_are_the_contract():
    """A window layer's decode step carries a name of its own (PERF.md,
    section 3): ``paged_window_roofline_pct`` sums it, and a reader of
    ``veles_paged_decode`` never counts it."""
    assert ["%" + paged.KERNEL_NAMES_WINDOW[q][0] for q in (False, True)] \
        == ["%veles_paged_decode_window", "%veles_paged_decode_window_q8"]
    assert not set(paged.KERNEL_NAMES_WINDOW.values()) \
        & set(paged.KERNEL_NAMES.values())


def test_audit_names_come_from_the_same_table():
    launches = flash.audit_launch(1024, 1024, 128)
    assert [l["kernel"] for l in launches] == [
        flash.KERNEL_NAMES[k][1] for k in ("forward", "bwd_dq",
                                           "bwd_dkv")]
    assert paged.audit_launch(128, 32)[0]["kernel"] == \
        paged.KERNEL_NAMES[False][1]
    assert paged.audit_launch(128, 32, dtype=jnp.int8)[0]["kernel"] == \
        paged.KERNEL_NAMES[True][1]
    assert dsa.audit_launch(2048, 34816, 128)[0]["kernel"] == \
        dsa.KERNEL_NAMES["prefill"][1]


@pytest.mark.parametrize("tq", [256, 2048])
def test_the_prefill_kernels_configured_launch_audits_clean(tq):
    """The VP6xx rules (tile alignment, ragged grids, the VMEM
    footprint with the score tile's temporaries priced in) over the
    launch of the cell's shortest and longest pass, at the tiles the
    code gives them — and the registered hook reports the kernel."""
    from veles_tpu.analysis.numerics_audit import audit_kernel_launch
    from veles_tpu.ops.pallas import kernel_audit_launches
    launch, = dsa.audit_launch(tq, 34816, 128)
    assert audit_kernel_launch(launch) == []
    blocks = {name: shape for name, shape, *_ in launch["blocks"]}
    assert blocks["mask"][2:] == att.dsa_prefill_tiles(tq, 34816, 128)
    assert dsa.KERNEL_NAMES["prefill"][1] in {
        l["kernel"] for l in kernel_audit_launches()}
    # a tile pair too fat for a core is what the audit is there for
    fat, = dsa.audit_launch(tq, 34816, 128, tiles=(256, 1024), g=64)
    assert [f.rule for f in audit_kernel_launch(fat)] == ["VP602"]
