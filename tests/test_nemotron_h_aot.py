"""The Nemotron-3-Super cell's step, built for the chip without a chip: the
full-size step of ``nemotron_3_super_120b_a12b.lm_s8192`` lowered and compiled
against the described ``v5e:2x2`` host, as ``test_tpu_aot_compile.py`` does
for the other decoder cells (a file of its own so that another worker runs
it: 50 s to compile). Nothing here runs on a device, so nothing here is a
measurement."""

import functools
import re

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import paddle_tpu as pt
from paddle_tpu.models import nemotron_h
from paddle_tpu.ops.pallas import registry
from paddle_tpu.parallel.mesh import MeshConfig, make_mesh


@pytest.fixture(scope="module")
def full_size():
    """(compiled step, parameter shapes, lowered text) of the cell: one period
    EMEMEMEMEM* and the MTP module at the published widths, a quarter of
    the heads, 8 of 512 experts, an eighth of the vocabulary, batch 1 x
    8192, Adam, on one chip of the described host."""
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any libtpu refusal is the reason
        pytest.skip(f"libtpu cannot describe a v5e:2x2 topology here: {e!r}")
    cfg = nemotron_h.nemotron_3_super_120b_a12b(
        pattern="EMEMEMEMEM*", vocab_size=16384, mamba_heads=32,
        mamba_groups=2, num_heads=8, num_kv_heads=1, experts_held=(0, 8))
    mesh = make_mesh(MeshConfig(data=1), devices=topo.devices[:1])
    opt = pt.optimizer.Adam(1e-7)
    _, step_fn = nemotron_h.make_train_step(cfg, opt, mesh)
    replicated = NamedSharding(mesh, P())

    def on_chip(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=replicated), tree)
    pshape = jax.eval_shape(
        functools.partial(nemotron_h.init_params, cfg=cfg),
        jax.random.PRNGKey(0))
    batch = {k: jax.ShapeDtypeStruct(
        v.shape, v.dtype, sharding=NamedSharding(mesh, P("data")))
        for k, v in nemotron_h.synthetic_batch(cfg, 1, 8192).items()}
    with pytest.MonkeyPatch.context() as mp:
        # what registry.platform() answers on the chip
        mp.setattr(registry, "platform", lambda: "tpu")
        lowered = step_fn.jitted.lower(
            on_chip(pshape), on_chip(jax.eval_shape(opt.init, pshape)),
            batch)
    return lowered.compile(), pshape, lowered.as_text()


@pytest.mark.timeout(900)
def test_nemotron_3_super_step_at_published_widths_fits_a_v5e(full_size):
    """915.2 M parameters with their two Adam moments are 10.23 GiB of the
    step's arguments, the most of any cell; with every M and * mixer
    recomputed but for what the flash calls and the scan's forward kernel
    hand their backward ones (a Mamba layer: y, 32 MiB, and the state every
    second chunk starts from, 32 MiB) the whole step needs 14.35 GiB of the
    15.75 a v5e gives a program (compiler, PR 50; 14.16 with the scan in
    ``jax.numpy``, PR 48; 15.69 with a state a chunk kept, which the memory
    scheduler met with another order of the two head passes). Its Mosaic
    calls: the causal flash kernels at 8 heads over 1 (the * layer and the
    module's, each way once a layer), the state-space scan's ``ssd_fwd`` and
    ``ssd_bwd`` (once each a Mamba layer: the forward kernel is not in the
    recomputation), the two grouped matmuls of the six LatentMoE layers'
    loops, the way back of their held rows (rows of the latent's 1024), and
    the cross-entropy twice."""
    compiled, pshape, _ = full_size
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(pshape)) \
        == 915_161_056
    ma = compiled.memory_analysis()
    assert 10.2 * 2**30 < ma.argument_size_in_bytes < 10.3 * 2**30
    need = ma.argument_size_in_bytes + ma.temp_size_in_bytes \
        + ma.output_size_in_bytes - ma.alias_size_in_bytes
    assert 0.25 * 15.75 * 2**30 < need < 14.6 * 2**30, need / 2**30
    calls = [line for line in compiled.as_text().splitlines()
             if "tpu_custom_call" in line]
    stems = [re.sub(r"\.\d+$", "", line.split(" = ")[0].split("%")[-1])
             for line in calls]
    assert set(stems) == {"flash_fwd", "flash_bwd", "softmax_xent_fwd",
                          "grouped_matmul", "grouped_matmul_dw",
                          "moe_combine", "ssd_fwd", "ssd_bwd"}
    # a call a layer: no forward kernel is in the recomputation
    assert stems.count("flash_fwd") == 2 and stems.count("flash_bwd") == 2
    assert stems.count("ssd_fwd") == 5 and stems.count("ssd_bwd") == 5
    # x | B | C as the convolution leaves them, one array of 2048 + 2 x 256
    # columns (its gradient one array too); the state kept every second
    # chunk
    assert all("bf16[1,8192,2560]" in line and "bf16[1,8192,2048]" in line
               and "f32[1,32,2048,128]" in line and "f32[1,8192,32]" in line
               for line in calls if "ssd_" in line.split(" = ")[0])
    # nothing stands between the convolution and the kernel: its first
    # operand is the convolution's own fusion
    assert all(re.search(r"custom-call\(%multiply_convert_fusion", line)
               for line in calls if line.split(" = ")[0].strip()
               .startswith("%ssd_fwd"))
    assert stems.count("softmax_xent_fwd") == 2         # two head passes
    assert stems.count("moe_combine") == 12             # 6 layers, each way
    assert all("bf16[1,8,8192,128]" in line for line in calls
               if "flash_" in line.split(" = ")[0])
    # the way back sums rows of the latent, not of the hidden size
    assert all("[8192,1024]" in line and "[8192,4096]" not in line
               for line in calls if "moe_combine" in line.split(" = ")[0])
    op_names = "\n".join(re.findall(r'op_name="([^"]*)"', "\n".join(calls)))
    for scope, kernel in (
            ("attention_core", "flash_fwd"), ("attention_core", "flash_bwd"),
            ("ssd_core", "ssd_fwd"), ("ssd_core", "ssd_bwd"),
            ("moe_experts", "grouped_matmul"),
            ("moe_experts", "grouped_matmul_dw"),
            ("moe_dispatch", "moe_combine"), ("loss", "softmax_xent_fwd")):
        assert re.search(rf"{scope}[^\n]*/{kernel}/pallas_call", op_names), \
            (scope, kernel)
    every = "\n".join(re.findall(r'op_name="([^"]*)"', compiled.as_text()))
    for scope in ("ssd_core", "short_conv", "ssd_gate"):
        for where in (rf"jvp\(attention\)/{scope}/",
                      rf"/rematted_computation/attention/{scope}/",
                      rf"transpose\([^\n]*/checkpoint/attention/{scope}/"):
            assert re.search(where, every), where
    for scope in ("moe_latent", "moe_router", "moe_shared", "mtp_merge"):
        assert re.search(rf"[/(]{scope}[)/]", every), scope
    # Adam is the stock rule: no Mosaic call under the optimizer
    assert "/optimizer/" not in op_names


def test_the_scan_s_kernels_are_lowered_once_a_direction(full_size):
    """Set-up's guard: the five Mamba layers call one ``_ssd_fwd`` and one
    ``_ssd_bwd`` function, so the step's lowering holds each kernel's Mosaic
    payload once (``registry.traced_once`` for the trace, ``lowered_once``
    for the forward call under the mixers' checkpoint, which jax would
    otherwise re-cut and lower a layer: 5 + 1 payloads and a second more of
    lowering in this sandbox, three times that on the chip's host; PERF.md
    section 6, PR 50)."""
    _, _, text = full_size
    functions = re.findall(r"func\.func private @(_ssd_\w+)\(", text)
    assert sorted(functions) == ["_ssd_bwd", "_ssd_fwd"], functions
    for name in ("_ssd_fwd", "_ssd_bwd"):
        assert len(re.findall(rf"call @{name}\(", text)) == 5, name
    kernels = re.findall(r'kernel_name = "(\w+)"', text)
    assert kernels.count("ssd_fwd") == 1 == kernels.count("ssd_bwd"), kernels
