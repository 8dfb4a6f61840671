"""Build the program the chip runs — without a chip.

The tests force the CPU, and on the CPU the Pallas registry's ``auto``
mode selects the stock-jnp reference bodies. On a TPU the same ``auto``
selects the Pallas bodies, so the program that runs there is one no CPU
test executes. The sandbox's libtpu can still COMPILE for a TPU:
``jax.experimental.topologies`` describes a ``v5e:2x2`` host, and
lowering + compiling against its devices reproduces, in seconds, every
refusal a chip run would meet at start-up —

- "Mosaic kernels cannot be automatically partitioned" (a pallas_call
  traced for a multi-device mesh under GSPMD),
- a block that breaks Mosaic's (8, 128) tiling rule,
- a kernel past the scoped-VMEM limit (RESOURCE_EXHAUSTED ... vmem).

Nothing here runs on a device, so nothing here is a measurement.
"""

import contextlib
import functools
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

import paddle_tpu as pt
from paddle_tpu.models import (bert, deepseek_v3, evabyte, kimi_linear, laguna,
                               lfm2, olmoe, qwen3_next, transformer)
from paddle_tpu.ops import kda
from paddle_tpu.ops import pallas as plk
from paddle_tpu.ops.pallas import registry
from paddle_tpu.parallel.data_parallel import DataParallelTrainer
from paddle_tpu.parallel.mesh import MODEL_AXIS, MeshConfig, make_mesh


@pytest.fixture(scope="module")
def topo():
    """The described v5e:2x2 host. Described here, inside a fixture, and not
    while the module is imported: only one process may load libtpu, every
    xdist worker imports every test file, and only the worker that runs this
    file may take the library."""
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any libtpu refusal is the reason
        pytest.skip(f"libtpu cannot describe a v5e:2x2 topology here: {e!r}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@contextlib.contextmanager
def _chip_selection():
    """What registry.platform() answers on the chip."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(registry, "platform", lambda: "tpu")
        yield


@pytest.fixture(autouse=True)
def on_chip_selection():
    with _chip_selection():
        yield


def _abstract(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args, **jit_kw):
    return jax.jit(fn, **jit_kw).lower(*args).compile()


def _mosaic_calls(compiled):
    return compiled.as_text().count("tpu_custom_call")


def _mosaic_call_stems(compiled):
    """The ``name=`` of every Mosaic call in the program, one entry a call."""
    return [re.sub(r"\.\d+$", "", line.split(" = ")[0].split("%")[-1])
            for line in compiled.as_text().splitlines()
            if "tpu_custom_call" in line]


def _entry_text(compiled):
    """The instructions of the program's entry computation: the tensors that
    exist in HBM. What a fusion computes inside itself is not among them."""
    text = compiled.as_text()
    return text[text.index("\nENTRY "):]


# ---------------------------------------------------------------------------
# (a) every registered kernel's Pallas body, at one production shape
# ---------------------------------------------------------------------------
F32, BF16, I32, I8 = jnp.float32, jnp.bfloat16, jnp.int32, jnp.int8
VOCAB, H, FFN = 30522, 768, 3072
#: name -> (argument (shape, dtype)s, kwargs, differentiate?)
KERNEL_SHAPES = {
    "fused_matmul": ([((32768, H), F32), ((H, FFN), F32), ((FFN,), F32)],
                     {"act": "gelu"}, True),
    "fused_matmul_int8": ([((4096, H), F32), ((H, FFN), I8),
                           ((FFN,), F32), ((FFN,), F32)],
                          {"act": "relu"}, False),
    # DeepFM's table; n_pad * dp at DEFAULT_VMEM_BUDGET's edge
    "embedding_scatter_add": ([((100000, 16), F32), ((16384,), I32),
                               ((16384, 16), F32)], {}, False),
    "flash_attention": ([((8, 12, 2048, 64), BF16)] * 3, {}, True),
    # a layer of evabyte.lm_s16384: q, k, v heads-major and the summaries of
    # the 1024 chunks of 16, 128 a window of 2048
    "eva_attention": ([((1, 32, 16384, 128), BF16)] * 3
                      + [((1, 32, 1024, 128), BF16)] * 2,
                      {"window": 2048, "chunk": 16}, True),
    # OLMoE's gate/up product: 8 x 8192 assignments, 64 experts of 2048x1024
    "grouped_matmul": ([((65536, 2048), BF16), ((64, 2048, 1024), BF16),
                        ((64,), I32)], {}, True),
    # a pass of lfm2_24b_a2b.lm_b4_s8192's expert layers: the 32 768 rows of
    # a pass summed into the 32 768 tokens' rows
    "moe_combine": ([((32768, 2048), F32), ((32768, 2048), BF16),
                     ((32768,), I32), ((32768,), F32)], {}, False),
    "fused_layer_norm": ([((64, 512, H), BF16), ((H,), F32), ((H,), F32)],
                         {}, True),
    "softmax_cross_entropy": ([((5120, VOCAB), F32), ((5120,), I32)], {},
                              True),
    # a KDA layer of kimi_linear_48b_a3b.lm_s8192: q, k, v, g, beta
    "kda_chunked": ([((1, 8192, 32, 128), BF16)] * 3
                    + [((1, 8192, 32, 128), F32), ((1, 8192, 32), F32)],
                    {"chunk": 32}, True),
    # a Gated DeltaNet layer of qwen3_next_80b_a3b.lm_s16384: x @ w's
    # [q | k | v] and the taps; q and k normed a head of 128, v not
    "short_conv_norm": ([((1, 16384, 8192), BF16), ((4, 8192), F32)],
                        {"head_dim": 128,
                         "parts": ((2048, 128 ** -0.5), (2048, 1.0),
                                   (4096, None))}, True),
    # its output, the gate z and the gain a channel of the head
    "gated_head_norm": ([((1, 16384, 4096), BF16)] * 2 + [((128,), F32)],
                        {"eps": 1e-6, "act": "silu"}, True),
    # a convolution layer of lfm2_24b_a2b.lm_b4_s8192: x @ W_in's
    # [B | C | u] and the 3 taps
    "gated_short_conv": ([((4, 8192, 6144), BF16), ((3, 2048), F32)], {},
                         True),
    # a Mamba-2 layer of nemotron_3_super_120b_a12b.lm_s8192: x, dt, a, B,
    # C, D: 32 heads of 64 under 2 groups with a state of 128
    "ssd": ([((1, 8192, 32, 64), BF16), ((1, 8192, 32), F32),
             ((1, 8192, 32), F32), ((1, 8192, 2, 128), BF16),
             ((1, 8192, 2, 128), BF16), ((32,), F32)], {"chunk": 128}, True),
}


def test_every_registered_kernel_has_a_shape_here():
    assert sorted(KERNEL_SHAPES) == plk.list_kernels()


@pytest.mark.parametrize("name", sorted(KERNEL_SHAPES))
def test_pallas_body_compiles_for_the_chip(name, one_chip):
    shapes, kw, differentiate = KERNEL_SHAPES[name]
    body = functools.partial(plk.get_body(name, "pallas"), interpret=False,
                             **kw)
    args = [_abstract(s, d, one_chip) for s, d in shapes]
    fn = body
    if differentiate:
        floats = tuple(i for i, (_, d) in enumerate(shapes)
                       if jnp.issubdtype(d, jnp.floating))

        def fn(*a):
            return jax.value_and_grad(
                lambda *b: sum(jnp.sum(t.astype(F32))
                               for t in jax.tree.leaves(body(*b))),
                floats)(*a)
    assert _mosaic_calls(_compile(fn, *args)) >= 1


def test_scatter_add_budget_is_one_the_compiler_accepts(one_chip):
    """At DEFAULT_VMEM_BUDGET the body compiles (the case above); one
    step past it the registry hands over to the reference and counts —
    and the old budget of 4 Mi elements is a shape Mosaic refuses."""
    from paddle_tpu.monitor.registry import REGISTRY
    from paddle_tpu.ops.pallas import embedding
    assert 16384 * 128 == plk.DEFAULT_VMEM_BUDGET
    def abstract(shape, dtype=F32):
        return _abstract(shape, dtype, one_chip)

    dst, n = abstract((100000, 16)), 16384 + 128
    c = _compile(embedding.embedding_scatter_add_pallas, dst,
                 abstract((n,), I32), abstract((n, 16)))
    assert _mosaic_calls(c) == 0
    rejected = REGISTRY.get("pallas_vmem_budget_rejections_total")
    assert rejected.value(kernel="embedding_scatter_add") >= 1
    with pytest.raises(Exception, match="(?i)vmem"):
        _compile(lambda *a: embedding._scatter_add(*a, False), dst,
                 abstract((32768,), I32), abstract((32768, 16)))


# ---------------------------------------------------------------------------
# (b) the BERT train step, published width, small depth
# ---------------------------------------------------------------------------
def _bert_step(topo, mesh_cfg, n_devices, batch_size, seq=512, max_preds=80,
               **cfg_kw):
    """(compiled step, mesh) through bert.make_train_step itself."""
    mesh = make_mesh(mesh_cfg, devices=topo.devices[:n_devices])
    cfg = bert.bert_base(vocab_size=VOCAB, max_seq=seq, remat=False,
                         **cfg_kw)
    opt = pt.optimizer.Adam(1e-4)
    _, step_fn = bert.make_train_step(cfg, opt, mesh)
    specs = bert.param_specs(cfg)
    if mesh.shape[MODEL_AXIS] == 1:
        specs = jax.tree.map(lambda _: P(), specs,
                             is_leaf=lambda s: isinstance(s, P))
    pshard = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                          is_leaf=lambda s: isinstance(s, P))
    pshape = jax.eval_shape(functools.partial(bert.init_params, cfg=cfg),
                            jax.random.PRNGKey(0))
    oshape = jax.eval_shape(opt.init, pshape)

    def place(tree, shardings):
        return jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            tree, shardings)
    batch = {
        k: jax.ShapeDtypeStruct(
            v.shape, v.dtype, sharding=NamedSharding(mesh, P("data", "seq")))
        for k, v in bert.synthetic_batch(cfg, batch_size, seq,
                                         max_preds=max_preds).items()}
    compiled = step_fn.jitted.lower(
        place(pshape, pshard),
        place(oshape, opt.state_shardings(oshape, pshard, mesh)),
        batch).compile()
    return compiled, mesh


def _lower_replicated(make_train_step, init_params, cfg, batch, topo):
    """(compiled, parameter shapes, optimizer-state shapes) of a trainer's
    step under Adam on one chip of the described host."""
    mesh = make_mesh(MeshConfig(data=1), devices=topo.devices[:1])
    opt = pt.optimizer.Adam(1e-4)
    _, step_fn = make_train_step(cfg, opt, mesh)
    replicated = NamedSharding(mesh, P())

    def on_chip(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=replicated), tree)
    pshape = jax.eval_shape(functools.partial(init_params, cfg=cfg),
                            jax.random.PRNGKey(0))
    oshape = jax.eval_shape(opt.init, pshape)
    batch = {k: jax.ShapeDtypeStruct(
        v.shape, v.dtype, sharding=NamedSharding(mesh, P("data")))
        for k, v in batch.items()}
    with _chip_selection():    # a module's fixture is made before a test's
        compiled = step_fn.jitted.lower(on_chip(pshape), on_chip(oshape),
                                        batch).compile()
    return compiled, pshape, oshape


def _need_bytes(compiled):
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.temp_size_in_bytes
            + ma.output_size_in_bytes - ma.alias_size_in_bytes)


@pytest.fixture(scope="module")
def bert_two_layers(topo):
    """The one-chip step of the cell bert_base.mlm_s512 at two layers,
    compiled once for the tests that read it."""
    cfg = bert.bert_base(vocab_size=VOCAB, max_seq=512, remat=False,
                         num_layers=2)
    return _lower_replicated(
        bert.make_train_step, bert.init_params, cfg,
        bert.synthetic_batch(cfg, 64, 512, max_preds=80), topo)


def test_bert_step_one_device_runs_the_pallas_bodies(bert_two_layers):
    compiled, _, _ = bert_two_layers
    # 2 layers: two layer norms each, the embedding's and the head's,
    # forward; at 512 positions on one chip `auto` takes the flash kernels,
    # one forward and one backward call a layer; Adam is the stock rule
    # and no call
    assert sorted(_mosaic_call_stems(compiled)) == (
        ["flash_bwd"] * 2 + ["flash_fwd"] * 2 + ["layer_norm_fwd"] * 6)


def _relayouts_of(compiled, dims):
    """The compiled step's copy and transpose instructions (a fusion named
    for either among them) whose result has ``dims`` in any order."""
    found = []
    for line in compiled.as_text().splitlines():
        m = re.match(r"\s+(?:ROOT )?%?([\w.\-]+) = \w+\[([\d,]+)\]\S* "
                     r"([\w\-]+)\(", line)
        if m and re.search(r"copy|transpose", m.group(1) + " " + m.group(3)) \
                and sorted(map(int, m.group(2).split(","))) == sorted(dims):
            found.append(m.group(1))
    return found


def test_bert_step_one_device_relayouts_no_operand_of_a_flash_call(
        bert_two_layers):
    """The flash kernels read q, k and v where ``x @ qkv_w`` left them and
    write the context where ``@ out_w`` reads it (PR 36): the optimised step
    holds no copy or transpose of an array of the heads' shape, [B, N, S, D]
    in any order, and the calls are still two ``flash_fwd`` and two
    ``flash_bwd`` under ``attention_core``."""
    compiled, _, _ = bert_two_layers
    calls = [line for line in compiled.as_text().splitlines()
             if "tpu_custom_call" in line and "flash_" in line]
    assert len(calls) == 4
    assert all("/attention_core/" in line for line in calls)
    relayouts = _relayouts_of(compiled, (64, 12, 512, 64))
    assert not relayouts, (
        f"{len(relayouts)} copies or transposes of a [64, 12, 512, 64] "
        f"array around the flash calls ({relayouts[:4]}); the step before "
        "PR 36 held 17 in two layers (14 copies, 7 a layer, and 3 "
        "copy-done; 97 in the cell's twelve layers: q, k, v, the context "
        "and their gradients to and from [B, N, S, D])")


def test_bert_step_on_four_chips_runs_the_flash_kernels_a_shard_at_a_time(
        topo, capsys):
    """The cell mlm_s512_dp4: the mesh splits only the batch, so the registry
    runs the flash kernels inside shard_map over `data`, one forward and one
    backward call a layer on a chip's 64 rows, and no [64, 12, 512, 512]
    scores exist in HBM in either type. Nothing is resharded around the
    calls: the gradients' all-reduces are the program's only collectives."""
    compiled, _ = _bert_step(topo, MeshConfig(data=4), 4, 256,
                             num_layers=2, softmax_dtype="bf16")
    # the layer norm declares no batch split: its reference body on a mesh
    assert sorted(_mosaic_call_stems(compiled)) == (
        ["flash_bwd"] * 2 + ["flash_fwd"] * 2)
    entry = _entry_text(compiled)
    assert "[64,12,512,512]" not in entry
    text = compiled.as_text()
    assert "all-reduce" in text
    assert not re.search(r"[ )](all-gather|all-to-all|collective-permute)"
                         r"(-start)?\(", text)
    # the scope a profile's attention_core_ms reads is still on the calls
    op_names = re.findall(r'op_name="([^"]*)"', "\n".join(
        line for line in text.splitlines() if "tpu_custom_call" in line))
    assert len(op_names) == 4 and all(
        "/attention_core/jit(flash_attention_per_shard)/" in n
        for n in op_names), op_names
    with capsys.disabled():
        print(f"\nbert 2 layers on data=4, a device: "
              f"{_need_bytes(compiled) / 2**30:.3f} GiB (compiler)")


#: (positions, the mesh's axes or None for no mesh, batch, platform) -> what
#: `auto` runs: the flash kernels from blocks.FLASH_FROM positions on where
#: the Pallas body runs (one chip, or a mesh that splits only the batch, and
#: that evenly: there a shard at a time); where it does not, the callers'
#: dense code up to 1024 positions and the registry's reference body ("flash"
#: there) beyond
AUTO_CASES = {
    "one_chip_below_the_crossover": (256, {"data": 1}, 64, "tpu", "dense"),
    "one_chip_at_the_crossover": (512, {"data": 1}, 64, "tpu", "flash"),
    "one_chip_mlm_s4096": (4096, {"data": 1}, 8, "tpu", "flash"),
    "no_mesh_at_the_crossover": (512, None, 64, "tpu", "flash"),
    "four_chips_mlm_s512_dp4": (512, {"data": 4}, 256, "tpu", "flash"),
    "four_chips_s1024": (1024, {"data": 4}, 128, "tpu", "flash"),
    "four_chips_below_the_crossover": (256, {"data": 4}, 256, "tpu",
                                       "dense"),
    "four_chips_a_batch_the_mesh_does_not_divide": (512, {"data": 4}, 6,
                                                    "tpu", "dense"),
    "four_chips_beyond_1024": (2048, {"data": 4}, 32, "tpu", "flash"),
    "data2_model2_s512": (512, {"data": 2, "model": 2}, 128, "tpu", "dense"),
    "data2_model2_beyond_1024": (2048, {"data": 2, "model": 2}, 32, "tpu",
                                 "flash"),
    "cpu_s512": (512, {"data": 1}, 64, "cpu", "dense"),
    "cpu_four_devices_s512": (512, {"data": 4}, 256, "cpu", "dense"),
    "cpu_beyond_1024": (2048, {"data": 1}, 16, "cpu", "flash"),
}


@pytest.mark.parametrize("case", sorted(AUTO_CASES))
def test_auto_chooses_the_attention_body_from_what_it_observes(case,
                                                               monkeypatch):
    from paddle_tpu.models import blocks
    positions, axes, batch, platform, body = AUTO_CASES[case]
    monkeypatch.setattr(registry, "platform", lambda: platform)
    mesh = axes and make_mesh(
        MeshConfig(**axes),
        devices=jax.devices()[:int(np.prod(list(axes.values())))])
    assert blocks.attention_body(positions, mesh, batch) == body


@pytest.mark.parametrize("mesh_cfg,batch,mosaic_calls", [
    (MeshConfig(data=4), 256, 4), (MeshConfig(data=2, model=2), 128, 0)],
    ids=["data4", "data2_model2"])
def test_bert_step_lowers_and_compiles_on_four_chips(topo, mesh_cfg,
                                                      batch, mosaic_calls):
    """GSPMD refuses to partition a Mosaic call. Under a mesh that splits
    only the batch the flash kernels run a shard at a time inside shard_map
    (a forward and a backward call a layer); under any other mesh of more
    than one device `auto` takes the reference bodies. Either way the step
    lowers, and the partitioner inserts the gradient all-reduces."""
    compiled, mesh = _bert_step(topo, mesh_cfg, 4, batch, num_layers=2)
    assert mesh.size == 4
    text = compiled.as_text()
    assert _mosaic_calls(compiled) == mosaic_calls
    assert "all-reduce" in text
    out_sh = jax.tree.leaves(compiled.output_shardings)
    assert all(len(s.device_set) == 4 for s in out_sh)


def test_the_step_names_its_mosaic_calls(topo):
    """Every ``pallas_call`` has a ``name=``: it is the stem of the compiled
    instruction, which a profile shows and chipbench's breakdown prints, and
    the named scope around the call is on its ``op_name``. At S=4096 the
    step holds all three kernels of the BERT cells: the flash backward is
    one call, ``flash_bwd``, which yields dQ too."""
    compiled, _ = _bert_step(topo, MeshConfig(data=1), 1, 4, seq=4096,
                             max_preds=640, num_layers=1)
    calls = [line for line in compiled.as_text().splitlines()
             if "tpu_custom_call" in line]
    assert set(_mosaic_call_stems(compiled)) == {
        "flash_fwd", "flash_bwd", "layer_norm_fwd"}
    op_names = set(re.findall(r'op_name="([^"]*)"', "\n".join(calls)))
    # each flash call is a jitted function of its own, shared by the layers
    assert ("jit(step)/jvp(attention)/attention_core/jit(_flash_fwd)/"
            "flash_fwd/pallas_call" in op_names)
    assert ("jit(step)/transpose(jvp(attention))/attention_core/"
            "jit(_flash_attention_bwd)/flash_bwd/pallas_call" in op_names)
    assert any(n.endswith("layer_norm/layer_norm_fwd/pallas_call")
               for n in op_names)


def test_forcing_pallas_on_under_a_mesh_fails_loudly(topo):
    with plk.override("on"):
        with pytest.raises(NotImplementedError,
                           match="cannot be automatically partitioned"):
            _bert_step(topo, MeshConfig(data=4), 4, 256, num_layers=1)


def test_flash_backward_at_4096_fits_vmem_inside_the_step(topo):
    """The context in which the flash backward passed Mosaic's 16 MiB
    default (16.4 MiB): the whole train step at S=4096, full-sequence
    labels. The flash calls raise their limit explicitly, and the backward
    is one call a layer: a head's Q, dO and dQ, and dQ's float32
    accumulator, stay in VMEM across its key blocks."""
    compiled, _ = _bert_step(topo, MeshConfig(data=1), 1, 4, seq=4096,
                             max_preds=None, num_layers=2,
                             attention_impl="flash")
    stems = _mosaic_call_stems(compiled)
    # per layer: the flash forward and the one backward
    assert stems.count("flash_fwd") == 2
    assert stems.count("flash_bwd") == 2
    assert "flash_bwd_dq" not in compiled.as_text()


# ---------------------------------------------------------------------------
# (c) the OLMoE train step of the cell olmoe_1b_7b.lm_s4096, at full size
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def olmoe_full_size(topo):
    """The step of the cell olmoe_1b_7b.lm_s4096: one layer at the published
    widths, batch 2 x 4096. Compiled once for the tests that read it."""
    cfg = olmoe.olmoe_1b_7b(num_layers=1)
    return _lower_replicated(olmoe.make_train_step, olmoe.init_params, cfg,
                             olmoe.synthetic_batch(cfg, 2, 4096), topo)


@pytest.fixture(scope="module")
def transformer_one_plus_one(topo):
    """Transformer-big at one encoder and one decoder layer, the batch of
    the cell transformer_big.wmt_s256."""
    cfg = transformer.transformer_big(enc_layers=1, dec_layers=1)
    return _lower_replicated(
        transformer.make_train_step, transformer.init_params, cfg,
        transformer.synthetic_batch(cfg, 32, 256, 256), topo)


@pytest.mark.timeout(900)
def test_olmoe_step_at_published_widths_fits_a_v5e_and_names_its_calls(
        olmoe_full_size):
    """One layer of OLMoE-1B-7B with embedding and head (625.6 M parameters)
    at batch 2 x 4096 through ``olmoe.make_train_step``: it lowers and
    compiles for one v5e chip, needs less than the 15.75 GiB the chip gives
    a program, and every Mosaic call in it has a name and sits under the
    scope that issued it, causal flash at head size 128 and the three
    grouped matmuls of the expert layer, forward and backward, among them."""
    compiled, pshape, _ = olmoe_full_size
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(pshape)) \
        == 625_616_896
    need = _need_bytes(compiled)
    assert need < 15.75 * 2**30, need / 2**30
    stems = _mosaic_call_stems(compiled)
    assert set(stems) == {"flash_fwd", "flash_bwd", "softmax_xent_fwd",
                          "grouped_matmul", "grouped_matmul_dw"}
    # gate, up, down: forward, the rows' gradient, the weights' gradient
    assert stems.count("grouped_matmul") == 6
    assert stems.count("grouped_matmul_dw") == 3
    calls = [line for line in compiled.as_text().splitlines()
             if "tpu_custom_call" in line]
    op_names = set(re.findall(r'op_name="([^"]*)"', "\n".join(calls)))
    for name in (
            "jit(step)/jvp(attention)/attention_core/jit(_flash_fwd)/"
            "flash_fwd/pallas_call",
            "jit(step)/transpose(jvp(attention))/attention_core/"
            "jit(_flash_attention_bwd)/flash_bwd/pallas_call",
            "jit(step)/jvp(ffn)/moe_experts/jit(_gmm)/grouped_matmul/"
            "pallas_call",
            "jit(step)/transpose(jvp(ffn))/moe_experts/jit(_gmm)/"
            "grouped_matmul/pallas_call",
            "jit(step)/transpose(jvp(ffn))/moe_experts/jit(_tgmm)/"
            "grouped_matmul_dw/pallas_call",
            "jit(step)/jvp(loss)/softmax_xent_fwd/pallas_call"):
        assert name in op_names, (name, sorted(op_names))


# ---------------------------------------------------------------------------
# (c2) what Kimi Linear brought: flash at 192 / 128, a share of the experts,
# the chunked delta rule, and the step of kimi_linear_48b_a3b.lm_s8192
# ---------------------------------------------------------------------------
def test_flash_compiles_with_score_heads_of_192_beside_value_heads_of_128(
        one_chip):
    """Latent attention at 8192 positions: queries and keys of 192 channels
    (no multiple of the 128-lane grain: Mosaic lays them on two lane tiles),
    values of 128; forward and the one backward call."""
    q = _abstract((1, 32, 8192, 192), BF16, one_chip)
    v = _abstract((1, 32, 8192, 128), BF16, one_chip)

    def fn(q, k, v):
        return jax.value_and_grad(lambda *a: jnp.sum(plk.flash_attention(
            *a, causal=True).astype(F32)), (0, 1, 2))(q, k, v)
    compiled = _compile(fn, q, q, v)
    assert sorted(_mosaic_call_stems(compiled)) == ["flash_bwd", "flash_fwd"]


def test_grouped_matmul_compiles_for_a_share_of_the_experts(one_chip):
    """8 of 256 experts of 2304 x 1024 over the 8192 rows the expert layer
    sizes for them: the contraction is one block, the tiles past the rows
    held are on the work list to be zeroed."""
    args = [_abstract((8192, 2304), BF16, one_chip),
            _abstract((8, 2304, 1024), BF16, one_chip),
            _abstract((8,), I32, one_chip)]

    def fn(lhs, rhs, sizes):
        return jax.value_and_grad(lambda a, b: jnp.sum(plk.grouped_matmul(
            a, b, sizes).astype(F32)), (0, 1))(lhs, rhs)
    compiled = _compile(fn, *args)
    assert _mosaic_calls(compiled) == 3    # product, rows' and weights' grad


@pytest.mark.parametrize("rows, tokens, width", [
    (32768, 32768, 2048), (8192, 8192, 2304), (24576, 16384, 2048)],
    ids=["lfm2_and_laguna_s_pass", "kimi_s", "qwen3_next_s"])
def test_the_way_back_compiles_at_the_held_cells_passes(one_chip, rows,
                                                        tokens, width):
    """A pass's rows into the tokens' rows, updated in place: one Mosaic
    call, no XLA scatter, and no second [T, D] float32 array beside the
    donated one (the token-ordered rows are the one temporary of the rows'
    size, in their own dtype, beside the chunk of them being gathered)."""
    args = [_abstract((tokens, width), F32, one_chip),
            _abstract((rows, width), BF16, one_chip),
            _abstract((rows,), I32, one_chip),
            _abstract((rows,), F32, one_chip)]
    assert plk.selected_body("moe_combine") == "pallas"
    compiled = _compile(plk.moe_combine, *args, donate_argnums=(0,))
    assert _mosaic_call_stems(compiled) == ["moe_combine"]
    assert " scatter(" not in compiled.as_text()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 1.25 * rows * width * 2, temp / 2**20


def _no_row_scatter_under_dispatch(compiled, width):
    """The held experts' way back is the kernel's: no XLA scatter of rows
    ``width`` wide is left under ``moe_dispatch``, forward or backward (the
    scores' gradient is a scatter of scalars and stays)."""
    scatters = [line for line in compiled.as_text().splitlines()
                if " scatter(" in line and "moe_dispatch" in line]
    assert not [line for line in scatters
                if re.search(rf"= \(?\w+\[\d+,{width}\]", line)], scatters
    assert len(scatters) == 4 and all("transpose(jvp(" in line
                                      for line in scatters)


def test_the_delta_rule_is_two_mosaic_calls_where_a_head_is_a_lane_tile(
        one_chip):
    """[1, 8192, 32, 128], forward and backward: ``kda_fwd`` and ``kda_bwd``
    and no other custom call (the reference body's triangular solve is one);
    at a head of 16 channels (``kimi_linear_tiny``) the Pallas body hands
    over to the reference and the program holds no Mosaic call."""
    def compiled(d):
        wide = _abstract((1, 8192, 32, d), BF16, one_chip)
        g = _abstract((1, 8192, 32, d), F32, one_chip)
        beta = _abstract((1, 8192, 32), F32, one_chip)
        return _compile(lambda *a: jax.value_and_grad(
            lambda *b: jnp.sum(kda.kda_chunked(*b).astype(F32)),
            range(5))(*a), wide, wide, wide, g, beta)

    assert plk.selected_body("kda_chunked") == "pallas"
    full = compiled(128)
    assert sorted(_mosaic_call_stems(full)) == ["kda_bwd", "kda_fwd"]
    assert full.as_text().count("custom-call(") \
        == full.as_text().count("tpu_custom_call")
    assert _mosaic_calls(compiled(16)) == 0


def _no_head_view_around_the_rule(compiled, positions):
    """From the projections to the rule and from the rule to the output
    projection a delta-rule mixer's activations stay rows-major: the step
    holds no copy and no transpose of an array of the mixer's [B, S, H, 128]
    or [B, S, H 128] sizes under a delta-rule scope (the compiler's
    relayouts for a norm a head were 27.9 ms a step in the Qwen3-Next cell
    and 31.7 in Kimi Linear's: PERF.md section 6, PR 39)."""
    moved = [line for line in _entry_text(compiled).splitlines()
             if re.search(r" (copy|transpose)\(", line)
             and re.search(rf"\[1,{positions},(\d+,128|\d{{4}})\]", line)
             and re.search(r"short_conv|gdn_gate|gdn_core|kda_gate|kda_core",
                           line)]
    assert not moved, moved[:3]


#: name -> (the op's Pallas body's arguments, its statics): a Kimi Linear
#: layer's calls at [1, 8192, 4096] (the Qwen3-Next layer's at [1, 16384,
#: 8192] are ``KERNEL_SHAPES``')
DELTA_GLUE_AT_8192 = {
    "conv_q_normed": ("short_conv_norm",
                      [((1, 8192, 4096), BF16), ((4, 4096), F32)],
                      {"head_dim": 128, "parts": ((4096, 128 ** -0.5),)}),
    "conv_v_plain": ("short_conv_norm",
                     [((1, 8192, 4096), BF16), ((4, 4096), F32)],
                     {"head_dim": 128, "parts": ((4096, None),)}),
    "gate_sigmoid": ("gated_head_norm",
                     [((1, 8192, 4096), BF16)] * 2 + [((128,), F32)],
                     {"eps": 1e-5, "act": "sigmoid"}),
}


@pytest.mark.parametrize("case", sorted(DELTA_GLUE_AT_8192))
def test_the_passes_around_the_rule_compile_at_a_kimi_layers_shapes(
        case, one_chip):
    """Forward and backward, one Mosaic call each way and nothing between
    the operands and the kernels: no copy, no reshape that moves bytes. The
    gate's backward writes do and dz over o and z, which a recomputed mixer
    has no further use for: here they are the program's arguments, donated
    as a temporary is."""
    name, shapes, kw = DELTA_GLUE_AT_8192[case]
    body = functools.partial(plk.get_body(name, "pallas"), interpret=False,
                             **kw)
    compiled = _compile(
        lambda *a: jax.value_and_grad(
            lambda *b: sum(jnp.sum(t.astype(F32))
                           for t in jax.tree.leaves(body(*b))),
            range(len(shapes)))(*a),
        *[_abstract(s, d, one_chip) for s, d in shapes],
        donate_argnums=(0, 1) if name == "gated_head_norm" else ())
    stem = "conv_norm" if name == "short_conv_norm" else "gated_norm"
    assert sorted(_mosaic_call_stems(compiled)) == [stem + "_bwd",
                                                    stem + "_fwd"]
    assert not re.search(r" copy\(", _entry_text(compiled))


@pytest.fixture(scope="module")
def kimi_linear_full_size(topo):
    """The step of the cell kimi_linear_48b_a3b.lm_s8192: the published
    layers 1 to 5 at the published widths, 8 of 256 experts, an eighth of
    the vocabulary, batch 1 x 8192. The expert layers' loop over the rows
    held is part of the program whatever a batch routes, so a router
    collapsed onto the held experts runs what is compiled here."""
    cfg = kimi_linear.kimi_linear_48b_a3b(num_layers=5, vocab_size=20480,
                                          experts_held=(0, 8))
    return _lower_replicated(
        kimi_linear.make_train_step, kimi_linear.init_params, cfg,
        kimi_linear.synthetic_batch(cfg, 1, 8192), topo)


@pytest.mark.timeout(900)
def test_kimi_linear_step_at_published_widths_fits_a_v5e(
        kimi_linear_full_size):
    """602.4 M parameters with their two Adam moments are 6.7 GiB of the
    step's arguments; with every KDA mixer recomputed the whole step needs
    less than the 15.75 GiB a v5e gives a program; its Mosaic calls are the
    flash kernels (the MLA layer), the delta rule's two kernels (the four
    KDA layers: the forward once a layer, because the recomputed mixer
    keeps the kernel's outputs by name, ``blocks.recomputed``, PR 42; the
    backward once), the passes around the rule (formed again: the forward
    twice a layer), the grouped matmuls (the four expert layers' one loop
    over the rows held, forward and backward) and the cross-entropy, each
    under its scope."""
    compiled, pshape, _ = kimi_linear_full_size
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(pshape)) \
        == 602_434_432
    ma = compiled.memory_analysis()
    assert ma.argument_size_in_bytes > 6.7 * 2**30
    need = _need_bytes(compiled)
    assert need < 15.75 * 2**30, need / 2**30
    # 13.28 GiB with what ``kda_fwd`` hands ``kda_bwd`` kept from the pass
    # to the backward, 0.5 GiB a KDA layer (PR 42); 10.38 with the forward
    # kernel run again: 11.26 before the passes around the rule were kernels
    # (PR 39), 11.85 with them until the gate's backward wrote do and dz
    # over o and z (the heap packed a layer's long-lived dz, which waits
    # for the last weight-gradient products, above every other layer's
    # backward: PERF.md section 6, PR 39); 14.59 with the scan as a
    # jax.numpy body (PR 30). Of the 2.90 GiB that PR 42 added, 2.0 are the
    # kept arrays and the rest the order the compiler's memory scheduler
    # then takes (PERF.md section 6, PR 42)
    assert need < 13.58 * 2**30, need / 2**30
    stems = _mosaic_call_stems(compiled)
    assert set(stems) == {"flash_fwd", "flash_bwd", "softmax_xent_fwd",
                          "grouped_matmul", "grouped_matmul_dw",
                          "moe_combine", "kda_fwd", "kda_bwd",
                          "conv_norm_fwd", "conv_norm_bwd", "gated_norm_fwd",
                          "gated_norm_bwd"}
    assert stems.count("flash_fwd") == stems.count("flash_bwd") == 1
    # the way back of a pass, forward and backward, four expert layers
    assert stems.count("moe_combine") == 8
    _no_row_scatter_under_dispatch(compiled, 2304)
    # a call a layer: XLA inlines the jitted calls the layers share; the
    # rule's forward is not in the recomputation (PR 42: eight before)
    assert stems.count("kda_fwd") == 4 and stems.count("kda_bwd") == 4
    # the passes around the rule (PR 39): q, k and v a call each, the
    # output's norm and gate one, forward twice: they are formed again
    assert stems.count("conv_norm_fwd") == 24
    assert stems.count("conv_norm_bwd") == 12
    assert stems.count("gated_norm_fwd") == 8
    assert stems.count("gated_norm_bwd") == 4
    calls = [line for line in compiled.as_text().splitlines()
             if "tpu_custom_call" in line]
    op_names = "\n".join(re.findall(r'op_name="([^"]*)"', "\n".join(calls)))
    for scope, kernel in (("attention_core", "flash_fwd"),
                          ("attention_core", "flash_bwd"),
                          ("moe_experts", "grouped_matmul"),
                          ("moe_experts", "grouped_matmul_dw"),
                          ("moe_dispatch", "moe_combine"),
                          ("loss", "softmax_xent_fwd"),
                          ("kda_core", "kda_fwd"),
                          ("kda_core", "kda_bwd"),
                          ("short_conv", "conv_norm_fwd"),
                          ("short_conv", "conv_norm_bwd"),
                          ("kda_gate", "gated_norm_fwd"),
                          ("kda_gate", "gated_norm_bwd")):
        assert re.search(rf"{scope}[^\n]*/{kernel}/pallas_call", op_names), \
            (scope, kernel)
    # the scan is the kernels': every Mosaic call of the delta rule is under
    # ``kda_core``, and XLA's triangular solve and its loop over the chunks
    # are gone from the program
    assert all("kda_core" in name for name in op_names.splitlines()
               if "/kda_fwd" in name or "/kda_bwd" in name)
    assert "triangular" not in compiled.as_text().lower()
    _no_head_view_around_the_rule(compiled, 8192)


# (b2) the flash kernels on rows-major operands ([B, S, heads D]: PR 36)
#: name -> (q, k, v widths in heads of D; D; positions; the call's statics)
ROWS_MAJOR_FLASH = {
    # BERT's at 4096 positions: pairs of 64-wide heads, many tiles, packed
    "pairs_of_64_packed_s4096": ((12, None, None), 64, 4096, {}),
    # a head of 128 a lane tile: causal over a key/value group, and a window
    "heads_of_128_group_causal": ((16, 4, 4), 128, 4096, {"causal": True}),
    "heads_of_128_group_window": ((16, 4, 4), 128, 4096,
                                  {"causal": True, "window": 512}),
}


@pytest.mark.parametrize("case", sorted(ROWS_MAJOR_FLASH))
def test_flash_compiles_on_rows_major_operands(one_chip, case):
    """Forward and backward on the projections' own layout: the lane-tile
    index maps, the aligned lane slices and the pair's selects pass Mosaic
    at shapes no model of the benchmark runs yet (heads of 128) and at the
    one ``mlm_s4096`` does."""
    (hq, hk, hv), d, s, static = ROWS_MAJOR_FLASH[case]
    widths = (3 * hq,) if hk is None else (hq, hk, hv)
    operands = [_abstract((2, s, w * d), BF16, one_chip) for w in widths]

    def f(*operands):
        return jax.value_and_grad(lambda *a: jnp.sum(plk.flash_attention(
            *a, num_heads=hq, **static).astype(F32)),
            tuple(range(len(operands))))(*operands)

    compiled = _compile(f, *operands)
    window = "_window" if "window" in static else ""
    assert sorted(_mosaic_call_stems(compiled)) == [
        "flash_bwd" + window, "flash_fwd" + window]


# ---------------------------------------------------------------------------
# (c3) what Laguna brought: a window and groups in the flash kernels, and the
# step of laguna_xs2.lm_s16384
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("heads, window, names", [
    (64, 512, ["flash_bwd_window", "flash_fwd_window"]),
    (48, None, ["flash_bwd", "flash_fwd"])], ids=["sliding", "full"])
def test_flash_compiles_at_16384_positions_over_8_key_value_heads(
        one_chip, heads, window, names):
    """A layer's attention of the cell: 64 query heads behind a window of
    512 and 48 causal ones, each over 8 key/value heads of 128, forward and
    the one backward call. The windowed calls carry names of their own. No
    repeated K or V exists: the program's arguments are q and the 8 heads
    of k and v, and no [1, heads, 16384, 128] copy of them is made (the only
    arrays of the query heads' shape are q, the output, its cotangent, dQ
    and the dK and dV parts a query head, which one sum folds)."""
    q = _abstract((1, heads, 16384, 128), BF16, one_chip)
    kv = _abstract((1, 8, 16384, 128), BF16, one_chip)

    def fn(q, k, v):
        return jax.value_and_grad(lambda *a: jnp.sum(plk.flash_attention(
            *a, causal=True, window=window).astype(F32)), (0, 1, 2))(q, k, v)
    compiled = _compile(fn, q, kv, kv)
    assert sorted(_mosaic_call_stems(compiled)) == names
    per_head = 16384 * 128 * 2
    assert compiled.memory_analysis().argument_size_in_bytes \
        == (heads + 2 * 8) * per_head
    entry = _entry_text(compiled)
    assert "broadcast" not in "".join(
        line for line in entry.splitlines()
        if f"bf16[1,{heads},16384,128]" in line.split(" = ")[0])
    calls = [line for line in entry.splitlines() if "tpu_custom_call" in line]
    # each call reads the 8 heads as they are
    assert all(line.count("bf16[1,8,16384,128]") >= 2 for line in calls)


@pytest.fixture(scope="module")
def laguna_full_size(topo):
    """The step of the cell laguna_xs2.lm_s16384: the published layers 0 to
    4 at the published widths, 32 of 256 experts, an eighth of the
    vocabulary, batch 1 x 16384."""
    cfg = laguna.laguna_xs2(num_layers=5, vocab_size=12544,
                            experts_held=(0, 32))
    return _lower_replicated(
        laguna.make_train_step, laguna.init_params, cfg,
        laguna.synthetic_batch(cfg, 1, 16384), topo)


@pytest.mark.timeout(900)
def test_laguna_step_at_published_widths_fits_a_v5e(laguna_full_size):
    """691.6 M parameters with their two Adam moments are 7.73 GiB of the
    step's arguments; with every mixer and the dense feed-forward recomputed
    the whole step needs less than the 15.75 GiB a v5e gives a program (no
    proper subset of them does: PERF.md section 6, PR 33). Its Mosaic calls:
    the causal flash kernels (two full layers: the forward once a layer,
    because the recomputed mixer keeps the call's o and lse by name,
    ``blocks.recomputed``, PR 42; the backward once), the windowed ones
    (three sliding layers, the same), the grouped matmuls and the
    cross-entropy, each under its scope, the windowed ones under
    ``attention_window`` inside ``attention_core``."""
    compiled, pshape, _ = laguna_full_size
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(pshape)) \
        == 691_624_960
    ma = compiled.memory_analysis()
    assert ma.argument_size_in_bytes > 7.7 * 2**30
    need = _need_bytes(compiled)
    # 15.88 GiB by this account, which counts the heap's fragmentation
    # twice (``temp_size_in_bytes`` is the heap with its 1.03 GiB of holes,
    # plus the holes): the compiler's own total is 15.18 of 15.75, and it
    # refuses a program past that. 12.03 with the flash forward run again
    # (PR 33 to 41): the kept o and lse are 1.15 GiB of the difference, the
    # rest the order the compiler's memory scheduler takes with them in the
    # program (PERF.md section 6, PR 42)
    assert need < 15.95 * 2**30, need / 2**30
    stems = _mosaic_call_stems(compiled)
    assert set(stems) == {"flash_fwd", "flash_bwd", "flash_fwd_window",
                          "flash_bwd_window", "softmax_xent_fwd",
                          "grouped_matmul", "grouped_matmul_dw",
                          "moe_combine"}
    assert stems.count("moe_combine") == 8
    _no_row_scatter_under_dispatch(compiled, 2048)
    # a call a layer: XLA inlines the jitted calls the layers share; no
    # forward kernel is in the recomputation (PR 42: four and six before)
    assert stems.count("flash_fwd") == 2 and stems.count("flash_bwd") == 2
    assert stems.count("flash_fwd_window") == 3
    assert stems.count("flash_bwd_window") == 3
    calls = [line for line in compiled.as_text().splitlines()
             if "tpu_custom_call" in line]
    op_names = "\n".join(re.findall(r'op_name="([^"]*)"', "\n".join(calls)))
    for scope, kernel in (
            ("attention_core", "flash_fwd"), ("attention_core", "flash_bwd"),
            ("attention_core/attention_window", "flash_fwd_window"),
            ("attention_core/attention_window", "flash_bwd_window"),
            ("moe_experts", "grouped_matmul"),
            ("moe_experts", "grouped_matmul_dw"),
            ("moe_dispatch", "moe_combine"),
            ("loss", "softmax_xent_fwd")):
        assert re.search(rf"{scope}[^\n]*/{kernel}/pallas_call", op_names), \
            (scope, kernel)
    assert all("attention_window" in name for name in op_names.splitlines()
               if "_window/" in name)
    assert not any("attention_window" in name
                   for name in op_names.splitlines()
                   if "/flash_fwd/" in name or "/flash_bwd/" in name)


# ---------------------------------------------------------------------------
# (c4) Qwen3-Next: the delta rule with a decay a head over grouped key heads,
# flash at a head of 256, and the step of qwen3_next_80b_a3b.lm_s16384
# ---------------------------------------------------------------------------
def test_a_decay_a_head_is_two_mosaic_calls_that_read_a_key_head_in_place(
        one_chip):
    """[1, 16384, 16 | 32, 128] with a rank-3 decay, forward and backward:
    ``gdn_fwd`` and ``gdn_bwd`` and no other custom call. The arguments are
    the 16 key heads of q and k as they are: no [1, 16384, 32, 128] copy of
    them and no decay a channel exists (the only arrays of the value heads'
    shape are v, the output, its cotangent and dv)."""
    keys = _abstract((1, 16384, 16, 128), BF16, one_chip)
    values = _abstract((1, 16384, 32, 128), BF16, one_chip)
    scalar = _abstract((1, 16384, 32), F32, one_chip)
    compiled = _compile(lambda *a: jax.value_and_grad(
        lambda *b: jnp.sum(kda.kda_chunked(*b).astype(F32)), range(5))(*a),
        keys, keys, values, scalar, scalar)
    assert sorted(_mosaic_call_stems(compiled)) == ["gdn_bwd", "gdn_fwd"]
    assert compiled.as_text().count("custom-call(") == 2
    per_head = 16384 * 128 * 2
    assert compiled.memory_analysis().argument_size_in_bytes \
        == (2 * 16 + 32) * per_head + 2 * 16384 * 32 * 4
    entry = _entry_text(compiled)
    assert "f32[1,16384,32,128]" not in entry          # no decay a channel
    calls = [line for line in entry.splitlines() if "tpu_custom_call" in line]
    # each call reads q and k at their 16 heads' width
    assert all(line.count("bf16[1,16384,2048]") >= 2 for line in calls)


def test_flash_compiles_at_a_head_of_256_over_2_key_value_heads(one_chip):
    """The attention of a Qwen3-Next full layer: 16 causal query heads of 256
    over 2 key/value heads at 16 384 positions, forward and the one backward
    call. The backward's whole-sequence operands (Q, dO, dQ twice over and
    dQ's float32 accumulator: 64 MiB) pass the 64 MiB every other call asks,
    and the call asks what they need (``_bwd_compiler_params``); Laguna's
    heads of 128 keep their limit."""
    import importlib
    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    q = _abstract((1, 16, 16384, 256), BF16, one_chip)
    kv = _abstract((1, 2, 16384, 256), BF16, one_chip)

    def fn(q, k, v):
        return jax.value_and_grad(lambda *a: jnp.sum(plk.flash_attention(
            *a, causal=True).astype(F32)), (0, 1, 2))(q, k, v)
    compiled = _compile(fn, q, kv, kv)
    assert sorted(_mosaic_call_stems(compiled)) == ["flash_bwd", "flash_fwd"]
    assert compiled.memory_analysis().argument_size_in_bytes \
        == (16 + 2 * 2) * 16384 * 256 * 2
    entry = _entry_text(compiled)
    calls = [line for line in entry.splitlines() if "tpu_custom_call" in line]
    assert all(line.count("bf16[1,2,16384,256]") >= 2 for line in calls)
    resident = 16384 * (2 * 2 * 3 * 256 + 4 * 256)
    assert resident == 64 << 20
    assert fa._bwd_compiler_params(resident).vmem_limit_bytes == 80 << 20
    assert fa._bwd_compiler_params(resident // 2) \
        is fa._FLASH_BWD_COMPILER_PARAMS                # a head of 128
    assert fa._FLASH_BWD_COMPILER_PARAMS.vmem_limit_bytes == 64 << 20


@pytest.fixture(scope="module")
def qwen3_next_full_size(topo):
    """The step of the cell qwen3_next_80b_a3b.lm_s16384: the published
    layers 0 to 3 at the published widths, 32 of 512 experts, the
    vocabulary's padded eighth, batch 1 x 16384."""
    cfg = qwen3_next.qwen3_next_80b_a3b(num_layers=4, vocab_size=19072,
                                        experts_held=(0, 32))
    return _lower_replicated(
        qwen3_next.make_train_step, qwen3_next.init_params, cfg,
        qwen3_next.synthetic_batch(cfg, 1, 16384), topo)


@pytest.mark.timeout(900)
def test_qwen3_next_step_at_published_widths_fits_a_v5e(
        qwen3_next_full_size):
    """626.0 M parameters with their two Adam moments are 7.0 GiB of the
    step's arguments; with the Gated DeltaNet mixers recomputed the whole
    step needs less than the 15.75 GiB a v5e gives a program (PERF.md section
    6, PR 38, has the other choices). Its Mosaic calls: the delta rule's two
    kernels (three layers: the forward once for the pass and once where the
    mixer is recomputed, the backward once), the causal flash kernels (one
    layer, once each: its mixer keeps what it computed), the grouped matmuls
    of the four expert layers' loops and the cross-entropy, each under its
    scope."""
    compiled, pshape, _ = qwen3_next_full_size
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(pshape)) \
        == 625_994_816
    ma = compiled.memory_analysis()
    assert ma.argument_size_in_bytes > 6.9 * 2**30
    need = _need_bytes(compiled)
    # 14.09 GiB; 15.04 before the passes around the rule were kernels
    # (PR 39): no float32 [16384, 8192] residual and no second layout of
    # o, z and the convolution's output at the peak
    assert 0.25 * 15.75 * 2**30 < need < 14.2 * 2**30, need / 2**30
    stems = _mosaic_call_stems(compiled)
    assert set(stems) == {"gdn_fwd", "gdn_bwd", "flash_fwd", "flash_bwd",
                          "softmax_xent_fwd", "grouped_matmul",
                          "grouped_matmul_dw", "moe_combine",
                          "conv_norm_fwd", "conv_norm_bwd", "gated_norm_fwd",
                          "gated_norm_bwd"}
    assert stems.count("moe_combine") == 8
    _no_row_scatter_under_dispatch(compiled, 2048)
    # a call a layer: XLA inlines the jitted calls the layers share
    assert stems.count("gdn_fwd") == 6 and stems.count("gdn_bwd") == 3
    # the passes around the rule: a call a column range of [q | k | v], the
    # output's norm and gate one, forward twice as the rule's
    assert stems.count("conv_norm_fwd") == 18
    assert stems.count("conv_norm_bwd") == 9
    assert stems.count("gated_norm_fwd") == 6
    assert stems.count("gated_norm_bwd") == 3
    assert stems.count("flash_fwd") == 1 and stems.count("flash_bwd") == 1
    calls = [line for line in compiled.as_text().splitlines()
             if "tpu_custom_call" in line]
    op_names = "\n".join(re.findall(r'op_name="([^"]*)"', "\n".join(calls)))
    for scope, kernel in (
            ("gdn_core", "gdn_fwd"), ("gdn_core", "gdn_bwd"),
            ("short_conv", "conv_norm_fwd"), ("short_conv", "conv_norm_bwd"),
            ("gdn_gate", "gated_norm_fwd"), ("gdn_gate", "gated_norm_bwd"),
            ("attention_core", "flash_fwd"), ("attention_core", "flash_bwd"),
            ("moe_experts", "grouped_matmul"),
            ("moe_experts", "grouped_matmul_dw"),
            ("moe_dispatch", "moe_combine"),
            ("loss", "softmax_xent_fwd")):
        assert re.search(rf"{scope}[^\n]*/{kernel}/pallas_call", op_names), \
            (scope, kernel)
    # the 16 key heads are read in place: no array of q's or k's 32-head
    # width, and no decay a channel, exists in the program
    entry = _entry_text(compiled)
    assert "f32[1,16384,32,128]{3,2,1,0" not in "".join(
        line for line in entry.splitlines() if "broadcast" in line)
    _no_head_view_around_the_rule(compiled, 16384)


# ---------------------------------------------------------------------------
# (c5) LFM2: the double-gated convolution's two kernels, flash at a head of
# 64 with 32 over 8, and the step of lfm2_24b_a2b.lm_b4_s8192
# ---------------------------------------------------------------------------
def test_the_gated_convolution_is_two_mosaic_calls_on_the_projection_s_array(
        one_chip):
    """[4, 8192, 3 x 2048] and 3 taps, forward and backward: ``gated_conv_fwd``
    and ``gated_conv_bwd`` and no other custom call; the ranges are read in
    place and the gradient is one array: no [4, 8192, 2048] slice or
    concatenate exists beside the op's own result and its cotangent."""
    bcu = _abstract((4, 8192, 6144), BF16, one_chip)
    taps = _abstract((3, 2048), F32, one_chip)
    w = _abstract((4, 8192, 2048), BF16, one_chip)
    compiled = _compile(lambda x, t, w: jax.value_and_grad(
        lambda x, t: jnp.sum((plk.gated_short_conv(x, t) * w).astype(F32)),
        (0, 1))(x, t), bcu, taps, w)
    assert sorted(_mosaic_call_stems(compiled)) == ["gated_conv_bwd",
                                                    "gated_conv_fwd"]
    assert compiled.as_text().count("custom-call(") == 2
    entry = _entry_text(compiled)
    assert "concatenate(" not in entry and " slice(" not in entry
    calls = [line for line in entry.splitlines() if "tpu_custom_call" in line]
    assert all("bf16[4,8192,6144]" in line for line in calls)


def test_flash_compiles_at_a_head_of_64_with_32_heads_over_8(one_chip):
    """The attention of an LFM2 full layer through the heads-major blocks:
    32 causal query heads of 64 over 8 key/value heads at 4 x 8192
    positions, forward and the one backward call, each reading the 8
    key/value heads as they are."""
    q = _abstract((4, 32, 8192, 64), BF16, one_chip)
    kv = _abstract((4, 8, 8192, 64), BF16, one_chip)

    def fn(q, k, v):
        return jax.value_and_grad(lambda *a: jnp.sum(plk.flash_attention(
            *a, causal=True).astype(F32)), (0, 1, 2))(q, k, v)
    compiled = _compile(fn, q, kv, kv)
    assert sorted(_mosaic_call_stems(compiled)) == ["flash_bwd", "flash_fwd"]
    calls = [line for line in _entry_text(compiled).splitlines()
             if "tpu_custom_call" in line]
    assert all(line.count("bf16[4,8,8192,64]") >= 2 for line in calls)


@pytest.fixture(scope="module")
def lfm2_full_size(topo):
    """The step of the cell lfm2_24b_a2b.lm_b4_s8192: the published layers 1
    to 5 at the published widths, 8 of 64 experts, an eighth of the
    vocabulary tied with the head, batch 4 x 8192."""
    cfg = lfm2.lfm2_24b_a2b(
        num_layers=5, layer_types=lfm2.lfm2_24b_a2b().layer_types[1:6],
        num_dense_layers=1, vocab_size=8192, experts_held=(0, 8))
    return _lower_replicated(
        lfm2.make_train_step, lfm2.init_params, cfg,
        lfm2.synthetic_batch(cfg, 4, 8192), topo)


@pytest.mark.timeout(900)
def test_lfm2_step_at_published_widths_fits_a_v5e(lfm2_full_size):
    """469.3 M parameters with their two Adam moments are 5.24 GiB of the
    step's arguments; with nothing recomputed the whole step needs less than
    the 15.75 GiB a v5e gives a program (PERF.md section 6, PR 40, has what
    each choice of recomputation costs). Its Mosaic calls: the convolution's two kernels
    (four layers, once each way), the causal flash kernels (one layer), the
    grouped matmuls of the four expert layers' loops and the cross-entropy,
    each under its scope; the tied table is one argument and one Adam
    leaf."""
    compiled, pshape, oshape = lfm2_full_size
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(pshape)) \
        == 469_285_248
    assert "head_w" not in pshape
    ma = compiled.memory_analysis()
    assert 5.2 * 2**30 < ma.argument_size_in_bytes < 5.3 * 2**30
    need = _need_bytes(compiled)
    assert 0.25 * 15.75 * 2**30 < need < 15.3 * 2**30, need / 2**30          # 15.17 GiB
    stems = _mosaic_call_stems(compiled)
    assert set(stems) == {"gated_conv_fwd", "gated_conv_bwd", "flash_fwd",
                          "flash_bwd", "softmax_xent_fwd", "grouped_matmul",
                          "grouped_matmul_dw", "moe_combine"}
    assert stems.count("moe_combine") == 8
    _no_row_scatter_under_dispatch(compiled, 2048)
    # a call a layer: XLA inlines the jitted calls the layers share
    assert stems.count("gated_conv_fwd") == 4       # nothing is recomputed
    assert stems.count("gated_conv_bwd") == 4
    assert stems.count("flash_fwd") == 1 and stems.count("flash_bwd") == 1
    calls = [line for line in compiled.as_text().splitlines()
             if "tpu_custom_call" in line]
    op_names = "\n".join(re.findall(r'op_name="([^"]*)"', "\n".join(calls)))
    for scope, kernel in (
            ("gated_conv", "gated_conv_fwd"), ("gated_conv", "gated_conv_bwd"),
            ("attention_core", "flash_fwd"), ("attention_core", "flash_bwd"),
            ("moe_experts", "grouped_matmul"),
            ("moe_experts", "grouped_matmul_dw"),
            ("moe_dispatch", "moe_combine"),
            ("loss", "softmax_xent_fwd")):
        assert re.search(rf"{scope}[^\n]*/{kernel}/pallas_call", op_names), \
            (scope, kernel)
    # the convolution's ranges are read in place: no array of one range's
    # width is cut out of the projection's product or joined into its gradient
    entry = _entry_text(compiled)
    assert not [line for line in entry.splitlines()
                if "gated_conv" in line and ("concatenate(" in line
                                             or " slice(" in line)]


# ---------------------------------------------------------------------------
# (c6) Kanana-2: rotary latent attention in every layer, flash at 192 / 128
# over 16 384 positions, and the step of kanana_2_30b_a3b.lm_s16384
# ---------------------------------------------------------------------------
def test_flash_compiles_at_192_beside_128_over_16384_positions(one_chip):
    """Latent attention at the Kanana-2 cell's length, 32 tiles a head. The
    backward call's whole-sequence operands are 52 MiB a head: 192 channels
    lie on 256 lanes of VMEM, which ``_bwd_compiler_params``'s count knows
    (44 MiB by the channels alone), so the call asks 68 MiB. The compiler
    takes both calls, and with a key bias too, whose column block and the
    groups' temporaries make it count 64.54 MiB (PR 46)."""
    import importlib
    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    q = _abstract((1, 32, 16384, 192), BF16, one_chip)
    v = _abstract((1, 32, 16384, 128), BF16, one_chip)
    bias = _abstract((1, 16384), F32, one_chip)

    def fn(q, k, v, bias=None):
        return jax.value_and_grad(lambda *a: jnp.sum(plk.flash_attention(
            *a, bias=bias, causal=True).astype(F32)), (0, 1, 2))(q, k, v)
    for operands in ((q, q, v), (q, q, v, bias)):
        compiled = _compile(fn, *operands)
        assert sorted(_mosaic_call_stems(compiled)) == ["flash_bwd",
                                                        "flash_fwd"]
    resident = 16384 * (2 * 2 * (2 * 256 + 128) + 4 * 192)
    assert resident == 52 * 2**20
    assert fa._bwd_compiler_params(resident).vmem_limit_bytes == 68 << 20


@pytest.fixture(scope="module")
def kanana_2_full_size(topo):
    """The step of the cell kanana_2_30b_a3b.lm_s16384: the published layers
    0 to 4 at the published widths, 16 of 128 experts, the padded eighth of
    the vocabulary, batch 1 x 16384."""
    cfg = deepseek_v3.kanana_2_30b_a3b(num_layers=5, vocab_size=16128,
                                       experts_held=(0, 16))
    return _lower_replicated(
        deepseek_v3.make_train_step, deepseek_v3.init_params, cfg,
        deepseek_v3.synthetic_batch(cfg, 1, 16384), topo)


@pytest.mark.timeout(900)
def test_kanana_2_step_at_published_widths_fits_a_v5e(kanana_2_full_size):
    """576.3 M parameters with their two Adam moments are 6.44 GiB of the
    step's arguments; with every mixer recomputed but for its flash call's
    outputs and its turned queries the whole step needs 14.1 GiB of the
    15.75 a v5e gives a program (13.54 with the queries formed again, 15.78
    to 15.90 with nothing recomputed: PERF.md section 6, PR 44). Its
    Mosaic calls: the causal flash kernels at 192 / 128 (five layers, each
    way once a layer), the grouped matmuls of the four expert layers' loops,
    the way back of their held rows and the cross-entropy, each under its
    scope; the rotation is under ``rope`` inside ``attention`` in the forward
    pass, the recomputed one and the backward."""
    compiled, pshape, _ = kanana_2_full_size
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(pshape)) \
        == 576_349_184
    ma = compiled.memory_analysis()
    assert 6.4 * 2**30 < ma.argument_size_in_bytes < 6.5 * 2**30
    need = _need_bytes(compiled)
    assert 0.25 * 15.75 * 2**30 < need < 14.3 * 2**30, need / 2**30   # 14.08 to 14.14
    stems = _mosaic_call_stems(compiled)
    assert set(stems) == {"flash_fwd", "flash_bwd", "softmax_xent_fwd",
                          "grouped_matmul", "grouped_matmul_dw",
                          "moe_combine"}
    assert stems.count("moe_combine") == 8
    _no_row_scatter_under_dispatch(compiled, 2048)
    # a call a layer: no forward kernel is in the recomputation
    assert stems.count("flash_fwd") == 5 and stems.count("flash_bwd") == 5
    calls = [line for line in compiled.as_text().splitlines()
             if "tpu_custom_call" in line]
    assert all("bf16[1,32,16384,192]" in line for line in calls
               if "flash_" in line.split(" = ")[0])
    op_names = "\n".join(re.findall(r'op_name="([^"]*)"', "\n".join(calls)))
    for scope, kernel in (
            ("attention_core", "flash_fwd"), ("attention_core", "flash_bwd"),
            ("moe_experts", "grouped_matmul"),
            ("moe_experts", "grouped_matmul_dw"),
            ("moe_dispatch", "moe_combine"),
            ("loss", "softmax_xent_fwd")):
        assert re.search(rf"{scope}[^\n]*/{kernel}/pallas_call", op_names), \
            (scope, kernel)
    every = "\n".join(re.findall(r'op_name="([^"]*)"', compiled.as_text()))
    for scope in ("rope", "mla_expand"):
        for where in (rf"jvp\(attention\)/{scope}/",
                      rf"/rematted_computation/attention/{scope}/",
                      rf"transpose\([^\n]*/checkpoint/attention/{scope}/"):
            assert re.search(where, every), where


@pytest.fixture(scope="module")
def evabyte_full_size(topo):
    """The step of the cell evabyte.lm_s16384: four whole layers at the
    published widths, the 320 ids whole, eight heads, batch 1 x 16384."""
    cfg = evabyte.evabyte_6b5(num_layers=4)
    return _lower_replicated(
        evabyte.make_train_step, evabyte.init_params, cfg,
        evabyte.synthetic_batch(cfg, 1, 16384), topo)


@pytest.mark.timeout(900)
def test_evabyte_step_at_published_widths_fits_a_v5e(evabyte_full_size):
    """821.4 M parameters with their two Adam moments are 9.18 GiB of the
    step's arguments; with every mixer recomputed but for its aggregation
    kernel's outputs and every feed-forward recomputed the whole step needs
    13.8 GiB of the 15.75 a v5e gives a program. Its Mosaic calls: the EVA
    kernels (four layers, each way once a layer: no forward kernel is in the
    recomputation) under ``eva_core`` inside ``attention_core``, and the
    eight heads' cross-entropies under ``multibyte_head`` inside ``loss``;
    none of the four flash calls the other cells stand on; the summaries are
    under ``eva_summary`` in the forward pass, the recomputed one and the
    backward; the optimizer is the stock rule on every leaf."""
    compiled, pshape, _ = evabyte_full_size
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(pshape)) \
        == 821_366_784
    ma = compiled.memory_analysis()
    assert 9.1 * 2**30 < ma.argument_size_in_bytes < 9.3 * 2**30
    need = _need_bytes(compiled)
    assert 0.25 * 15.75 * 2**30 < need < 14.2 * 2**30, need / 2**30  # 13.78
    stems = _mosaic_call_stems(compiled)
    assert set(stems) == {"flash_fwd_eva", "flash_bwd_eva",
                          "softmax_xent_fwd"}
    assert stems.count("flash_fwd_eva") == 4 == stems.count("flash_bwd_eva")
    assert stems.count("softmax_xent_fwd") == 8
    calls = [line for line in compiled.as_text().splitlines()
             if "tpu_custom_call" in line]
    assert all("bf16[1,32,16384,128]" in line and "bf16[1,32,1024,128]" in line
               for line in calls if "_eva" in line.split(" = ")[0])
    op_names = "\n".join(re.findall(r'op_name="([^"]*)"', "\n".join(calls)))
    for scope, kernel in (
            ("attention_core/eva_core", "flash_fwd_eva"),
            ("attention_core/eva_core", "flash_bwd_eva"),
            (r"jvp\(loss\)/multibyte_head", "softmax_xent_fwd")):
        assert re.search(rf"{scope}[^\n]*/{kernel}/pallas_call", op_names), \
            (scope, kernel)
    every = "\n".join(re.findall(r'op_name="([^"]*)"', compiled.as_text()))
    for scope in ("rope", "eva_summary"):
        for where in (rf"jvp\(attention\)/{scope}/",
                      rf"/rematted_computation/attention/{scope}/",
                      rf"transpose\([^\n]*/checkpoint/attention/{scope}/"):
            assert re.search(where, every), where
    under = _under_optimizer(compiled)
    assert not [u for u in under if u[0] == "custom-call"]


# ---------------------------------------------------------------------------
# (d) the optimizer inside the three trainers' steps: the stock rule, in place
# ---------------------------------------------------------------------------
def _under_optimizer(compiled):
    """(opcode, elements of the first result) of every instruction whose
    ``op_name`` is under the scope ``optimizer``."""
    found = []
    for line in compiled.as_text().splitlines():
        op_name = re.search(r'op_name="([^"]*)"', line)
        if " = " not in line or not op_name \
                or "/optimizer/" not in op_name.group(1):
            continue
        rhs = line.split(" = ", 1)[1]
        dims = re.search(r"\w+\[([\d,]*)\]", rhs).group(1)
        found.append((re.search(r" ([a-z][a-z\-]*)\(", rhs).group(1),
                      int(np.prod([int(d) for d in dims.split(",") if d]))))
    return found


@pytest.mark.timeout(900)
@pytest.mark.parametrize("step", ["bert_two_layers",
                                  "transformer_one_plus_one",
                                  "olmoe_full_size"])
def test_the_optimizer_updates_every_leaf_in_place(step, request):
    """``Optimizer.apply_gradients`` runs the stock rule on every leaf in the
    shape it has: the compiled step holds no Mosaic call under the scope
    ``optimizer``, no ``reshape`` or ``copy`` of a leaf there, and every
    parameter, both moments of each and the step counter come back in the
    donated buffers. (XLA leaves the gradient of a bias in the
    ``[heads, head size]`` shape its reduction gives, scales it for the two
    moments there and reshapes those: a vector, 4 KiB, not a leaf's relayout;
    hence the size under which a ``reshape`` is let through.)"""
    compiled, pshape, oshape = request.getfixturevalue(step)
    under = _under_optimizer(compiled)
    assert len(under) > len(jax.tree.leaves(pshape))
    assert not [u for u in under if u[0] == "custom-call"]
    assert not [u for u in under
                if u[0] in ("reshape", "copy", "transpose") and u[1] > 4096]
    donated = jax.tree.leaves((pshape, oshape))
    header = compiled.as_text().split("\n", 1)[0]
    assert len(re.findall(r"(?:may|must)-alias", header)) == len(donated)
    ma = compiled.memory_analysis()
    exact = sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in donated)
    # the compiler's sizes are of tiled buffers: a little over the exact ones
    assert exact <= ma.alias_size_in_bytes <= 1.001 * exact
    assert ma.alias_size_in_bytes <= ma.output_size_in_bytes \
        <= ma.alias_size_in_bytes + 4096          # + the loss
    if step == "olmoe_full_size":
        # 10.86 GiB; 14.05 with the Pallas Adam and the copies around it
        assert _need_bytes(compiled) < 11.5 * 2**30


def test_zero_trainer_updates_with_no_mosaic_call(topo):
    """DataParallelTrainer(param_sharding="zero") updates inside a
    shard_map body, the replicated strategy under GSPMD: both run the one
    stock rule, and neither program holds a Mosaic call."""
    mesh = make_mesh(MeshConfig(data=4), devices=topo.devices)
    d = 512

    def loss_fn(params, state, rng, batch):
        return jnp.mean((jnp.tanh(batch["x"] @ params["w"])
                         - batch["y"]) ** 2), state

    def abstract_args(trainer, spec):
        w_sh = NamedSharding(mesh, spec)
        rep = NamedSharding(mesh, P())
        params = {"w": _abstract((d, d), F32, w_sh)}
        opt_state = {"step": _abstract((), I32, rep),
                     "slots": {"w": {"moment1": params["w"],
                                     "moment2": params["w"]}}}
        data = NamedSharding(mesh, P("data"))
        batch = {"x": _abstract((64, d), F32, data),
                 "y": _abstract((64, d), F32, data)}
        rng = _abstract((2,), jnp.uint32, rep)
        return params, opt_state, {}, rng, batch

    zero = DataParallelTrainer(loss_fn, pt.optimizer.Adam(1e-3), mesh=mesh,
                               param_sharding="zero", donate=False)
    zero._param_specs = {"w": P("data", None)}
    c = zero._step.lower(*abstract_args(zero, P("data", None))).compile()
    assert _mosaic_calls(c) == 0

    plain = DataParallelTrainer(loss_fn, pt.optimizer.Adam(1e-3),
                                mesh=mesh, donate=False)
    c = plain._step.lower(*abstract_args(plain, P())).compile()
    assert _mosaic_calls(c) == 0


# ---------------------------------------------------------------------------
# the bench.py cell at full depth: ~25 s of compile, and its memory
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_bert_base_full_depth_bs64_fits_a_v5e(topo):
    compiled, _ = _bert_step(topo, MeshConfig(data=1), 1, 64,
                             softmax_dtype="bf16")
    ma = compiled.memory_analysis()
    need = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
            + ma.output_size_in_bytes - ma.alias_size_in_bytes)
    print(f"BERT-base bs=64 s=512, compiler's account: arguments "
          f"{ma.argument_size_in_bytes / 2**30:.2f} GiB, temp "
          f"{ma.temp_size_in_bytes / 2**30:.2f} GiB, total "
          f"{need / 2**30:.2f} GiB of a v5e's 16 GiB")
    assert need < 16 * 2**30
    assert np.isfinite(need)
