#!/usr/bin/env python3
"""Four-chip companion of ``chip_smoke.py`` — run by hand, on a host with
four chips, in one process:

    chiprun --chips 4 -- python3 tools/mesh_smoke.py

It runs ``chip_smoke``'s train leg (the 124M flagship, two warm-up
sweeps then eight steps) on one chip, then under
``MeshConfig(make_mesh({"data": 4}), fsdp=True)`` and under
``{"data": 2, "model": 2}``, and for each mesh establishes — and
asserts — three things the eight-virtual-CPU-device tests cannot show:

* parameters, optimizer state, the dataset and the batch live on four
  distinct devices with the shard shapes their specs promise;
* what the flash kernel sees.  On silicon it is a Mosaic custom call
  that GSPMD cannot partition; the first lines printed are what the
  toolchain does with a BARE ``flash_attention`` under sharded inputs
  (JAX 0.9.0: refuses to lower it), and each mesh leg then reads the
  compiled train sweep: every ``tpu_custom_call`` operand must carry
  the per-chip share of batch x heads
  (``ops.attention.flash_attention``'s ``shard``);
* the eight-step loss agrees with the one-chip leg to the tolerance the
  CPU mesh tests use (``tests/test_parallel.py``: rtol 1e-3).

Like ``chip_smoke.py`` it fails on anything but a TPU and catches no
leg's failure; its last stdout line is one JSON object."""

import gc
import json
import os
import re
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_smoke  # noqa: E402
from chip_smoke import check, say  # noqa: E402

LOSS_RTOL = 1e-3
N_CHIPS = 4

#: first operand's shape of a Mosaic custom call in compiled TPU HLO
#: (operands print as bare names there; their shapes are in
#: ``operand_layout_constraints``)
_MOSAIC_OPERAND = re.compile(
    r"custom_call_target=\"tpu_custom_call\", "
    r"operand_layout_constraints=\{[a-z0-9]+\[(\d+)[\d,]*\]")


def mosaic_operand_batches(hlo_text, record=None):
    """Leading dim of the first operand of every Mosaic custom call in
    a compiled module: the flash kernels take q as ``[B*H, T, hd]``, so
    this is the batch x heads each chip's kernel launch works on.
    ``record``: a name under which the custom-call lines (clipped) are
    kept in ``chiprun_out/`` — the evidence behind the printed fact."""
    lines = [ln for ln in hlo_text.splitlines() if "tpu_custom_call" in ln]
    if record:
        os.makedirs("chiprun_out", exist_ok=True)
        with open("chiprun_out/mesh_smoke_%s.hlo.txt" % record, "w") as f:
            f.write("\n".join(ln.strip()[:700] for ln in lines) + "\n")
    out = []
    for ln in lines:
        m = _MOSAIC_OPERAND.search(ln)
        check(m, "unparsed Mosaic custom call: %s" % ln.strip()[:300])
        out.append(int(m.group(1)))
    return out


def bare_flash_under_gspmd(sizes, mesh):
    """What the toolchain does with an un-``shard_map``ped pallas_call
    in a partitioned program: jit ``flash_attention`` alone over inputs
    sharded batch-over-data and heads-over-model.  An observation with
    two legitimate outcomes, not a leg: JAX 0.9.0 refuses to lower it
    ("Mosaic kernels cannot be automatically partitioned"); a toolchain
    that accepts it shows here what its custom call is fed."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from veles_tpu.ops.attention import flash_attention

    shape = sizes.flash_shapes[0]
    sh = NamedSharding(mesh, P("data", "model" if "model" in mesh.shape
                                else None))
    arg = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=sh)
    bare = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True),
                   out_shardings=sh)
    try:
        text = bare.lower(arg, arg, arg).compile().as_text()
    except NotImplementedError as e:
        say("bare-flash", mesh=dict(mesh.shape), lowered=False,
            refusal=repr(str(e)))
        return
    seen = mosaic_operand_batches(
        text, "bare_" + "x".join("%s%d" % kv for kv in mesh.shape.items()))
    say("bare-flash", mesh=dict(mesh.shape), lowered=True,
        global_bh=shape[0] * shape[1],
        custom_call_operand_bh=sorted(set(seen)))


def check_placement(name, tree, expect_fraction):
    """Every leaf on four distinct devices, each shard of the shape its
    sharding promises, and the per-device bytes the expected fraction
    of the whole (not four copies, not all on device 0)."""
    import jax
    import numpy as np

    total = per_device = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        shards = leaf.addressable_shards
        want = leaf.sharding.shard_shape(leaf.shape)
        check({s.data.shape for s in shards} == {want}, path, want)
        check(len({s.device for s in shards}) == N_CHIPS, path)
        total += leaf.nbytes
        per_device += int(np.prod(want)) * leaf.dtype.itemsize
    fraction = per_device / total
    say("placement", what=name, total_MB=total / 2 ** 20,
        per_device_MB=per_device / 2 ** 20, fraction=fraction)
    check(fraction <= expect_fraction * 1.05, name, fraction)


def mesh_leg(sizes, axes, fsdp):
    import jax
    import numpy as np
    from veles_tpu.parallel import MeshConfig, make_mesh

    mc = MeshConfig(make_mesh(axes), fsdp=fsdp)
    say("mesh", axes=axes, fsdp=fsdp,
        devices=[d.id for d in mc.mesh.devices.flat])
    wf = chip_smoke.build_flagship(sizes, mc)
    tr = wf.trainer
    # fsdp: 1/data of everything; otherwise 1/model of the matrices
    frac = 1.0 / (mc.data_size if fsdp else mc.model_size)
    check_placement("params", tr.params, frac)
    check_placement("optimizer", {k: tr.velocity[k]
                                  for k in ("slot1", "slot2")}, frac)
    table = tr.params[tr.layers[0].name]["table"]
    say("placement", what="embedding", shape=table.shape,
        spec=table.sharding.spec,
        shard=table.addressable_shards[0].data.shape)
    check_placement("dataset", tr._data_dev, 1.0 / mc.data_size)
    check_placement("batch", tr._place_stack(np.zeros(
        (chip_smoke.SPD, sizes.batch), np.int32)), 1.0 / mc.data_size)

    text = chip_smoke.compile_sweep(tr).as_text()
    seen = mosaic_operand_batches(
        text, "sweep_" + "x".join("%s%d" % kv for kv in axes.items()))
    want = sizes.batch * sizes.n_heads // N_CHIPS
    say("flash-hlo", mesh=axes, global_bh=sizes.batch * sizes.n_heads,
        per_chip_bh=want, custom_calls=len(seen),
        custom_call_operand_bh=sorted(set(seen)))
    check(seen and set(seen) == {want},
          "a flash custom call is not fed its chip's share: %s"
          % sorted(set(seen)))

    loss = chip_smoke.train_leg(wf, sizes)
    del wf, tr
    gc.collect()
    jax.clear_caches()
    return loss


def run(sizes=chip_smoke.Sizes()):
    device = chip_smoke.report_device(require_tpu=True)
    if device["count"] < N_CHIPS:
        raise SystemExit("mesh_smoke: needs %d chips, jax found %d"
                         % (N_CHIPS, device["count"]))
    from veles_tpu import compile_cache
    from veles_tpu.parallel import make_mesh
    say("setup", compile_cache=compile_cache.enable())

    bare_flash_under_gspmd(sizes, make_mesh({"data": 4}))
    bare_flash_under_gspmd(sizes, make_mesh({"data": 2, "model": 2}))

    wf = chip_smoke.build_flagship(sizes)
    chip_smoke.compile_sweep(wf.trainer)
    one_chip = chip_smoke.train_leg(wf, sizes)
    del wf
    gc.collect()

    losses = {"data4_fsdp": mesh_leg(sizes, {"data": 4}, True),
              "data2_model2": mesh_leg(sizes, {"data": 2, "model": 2},
                                       False)}
    # both legs first, then the verdicts: one run shows every number
    for name, loss in losses.items():
        rel = abs(loss - one_chip) / abs(one_chip)
        say("loss", mesh=name, loss_per_token=repr(loss),
            one_chip=repr(one_chip), rel_diff=rel, rtol=LOSS_RTOL)
    for name, loss in losses.items():
        check(abs(loss - one_chip) <= LOSS_RTOL * abs(one_chip),
              name, loss, one_chip)
    return {"ok": True, "device": device,
            "loss_per_token": dict(losses, one_chip=one_chip)}


if __name__ == "__main__":
    t_start = time.perf_counter()
    result = run()
    say("done", wall_s=time.perf_counter() - t_start)
    print(json.dumps(result), flush=True)
