"""BERT-style transformer encoder — the flagship pretraining model.

TPU-first design notes:
- one jitted train step = fused fwd+bwd+update (no per-op dispatch;
  contrast ref: framework/executor.cc:417 per-op hot loop);
- bf16 activations/matmuls on the MXU, fp32 master params + Adam moments
  (the reference's AMP decorator role, ref:
  python/paddle/fluid/contrib/mixed_precision/decorator.py:27);
- megatron-style tensor parallelism purely via sharding annotations on
  the "model" mesh axis; sequence axis sharded over "seq"; batch over
  "data" — GSPMD inserts the collectives (replaces the reference's
  multi-device graph passes + NCCL, ref:
  ir/multi_devices_graph_pass/multi_devices_graph_pass.cc:454);
- jax.checkpoint (remat) per encoder block to trade FLOPs for HBM;
- static shapes everywhere; masking handles ragged sequences (the LoD
  replacement, ref: framework/lod_tensor.h:229).
"""

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from paddle_tpu.models.blocks import attention_body
from paddle_tpu.ops.pallas.registry import mesh_scope
from paddle_tpu.parallel.mesh import (
    DATA_AXIS, MODEL_AXIS, SEQ_AXIS, get_mesh,
)
from paddle_tpu.profiler import RecordEvent

__all__ = ["BertConfig", "bert_base", "init_params", "forward", "mlm_loss",
           "make_train_step", "param_specs"]


@dataclasses.dataclass(frozen=True)  # hashable: used as a jit-static arg
class BertConfig:
    vocab_size: int = 30528          # multiple of 64 for MXU-friendly logits
    hidden: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate: int = 3072
    max_seq: int = 512
    type_vocab: int = 2
    dropout: float = 0.1
    dtype: object = jnp.bfloat16     # activation/compute dtype
    remat: bool = True               # jax.checkpoint per block
    # "auto": blocks.attention_body's choice (flash from blocks.FLASH_FROM
    # positions on where the Pallas body runs: one chip, or a shard at a time
    # on a mesh that splits only the batch; dense below, and on a mesh that
    # splits more).
    # "dense": GSPMD gathers K/V over "seq"; "ring": blockwise ring
    # attention (parallel/ring_attention.py) — K/V never materialised
    # whole, permutes ride ICI neighbor links. Use "ring" for long-context
    # runs where S/n_seq is still large. "flash": Pallas blockwise
    # online-softmax kernel (ops/pallas/flash_attention.py) —
    # single-device/dp fast path; scores never materialise in HBM.
    attention_impl: str = "auto"
    # softmax accumulation dtype on the dense path. "fp32" (default) is
    # the conservative choice and what every cell runs; "bf16" skips the
    # f32 round-trip over the [B,N,S,S] scores (no cell measures it).
    # Safe because softmax subtracts the row max before exponentiating,
    # keeping magnitudes in bf16's comfortable range.
    softmax_dtype: str = "fp32"

    @property
    def head_dim(self):
        return self.hidden // self.num_heads


def bert_base(**kw):
    return BertConfig(**kw)


def bert_large(**kw):
    kw.setdefault("hidden", 1024)
    kw.setdefault("num_layers", 24)
    kw.setdefault("num_heads", 16)
    kw.setdefault("intermediate", 4096)
    return BertConfig(**kw)


def ernie_base(**kw):
    """ERNIE 1.0/2.0 base (BASELINE.md north-star row): BERT-base
    architecture with ERNIE's vocab (ref models are distributed through
    PaddleNLP; the architectural config is what determines throughput —
    ERNIE's phrase/entity masking is a data-pipeline policy, expressible
    via mlm_loss's masked_positions layout)."""
    kw.setdefault("vocab_size", 18000)
    return BertConfig(**kw)


def bert_tiny(**kw):
    """Small config for tests / dry runs."""
    kw.setdefault("vocab_size", 512)
    kw.setdefault("hidden", 64)
    kw.setdefault("num_layers", 2)
    kw.setdefault("num_heads", 4)
    kw.setdefault("intermediate", 128)
    kw.setdefault("max_seq", 64)
    kw.setdefault("remat", False)
    return BertConfig(**kw)


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------
def _dense_init(key, shape, scale=0.02):
    return (scale * jax.random.normal(key, shape)).astype(jnp.float32)


def init_params(rng, cfg):
    """fp32 master params as a nested dict pytree."""
    keys = iter(jax.random.split(rng, 8 + 16 * cfg.num_layers))
    p = {
        "embed": {
            "word": _dense_init(next(keys), (cfg.vocab_size, cfg.hidden)),
            "pos": _dense_init(next(keys), (cfg.max_seq, cfg.hidden)),
            "type": _dense_init(next(keys), (cfg.type_vocab, cfg.hidden)),
            "ln_g": jnp.ones((cfg.hidden,), jnp.float32),
            "ln_b": jnp.zeros((cfg.hidden,), jnp.float32),
        },
        "layers": [],
        "mlm": {
            "dense_w": _dense_init(next(keys), (cfg.hidden, cfg.hidden)),
            "dense_b": jnp.zeros((cfg.hidden,), jnp.float32),
            "ln_g": jnp.ones((cfg.hidden,), jnp.float32),
            "ln_b": jnp.zeros((cfg.hidden,), jnp.float32),
            "bias": jnp.zeros((cfg.vocab_size,), jnp.float32),
        },
    }
    h, ffn = cfg.hidden, cfg.intermediate
    for _ in range(cfg.num_layers):
        p["layers"].append({
            "qkv_w": _dense_init(next(keys), (h, 3 * h)),
            "qkv_b": jnp.zeros((3 * h,), jnp.float32),
            "out_w": _dense_init(next(keys), (h, h)),
            "out_b": jnp.zeros((h,), jnp.float32),
            "ln1_g": jnp.ones((h,), jnp.float32),
            "ln1_b": jnp.zeros((h,), jnp.float32),
            "fc1_w": _dense_init(next(keys), (h, ffn)),
            "fc1_b": jnp.zeros((ffn,), jnp.float32),
            "fc2_w": _dense_init(next(keys), (ffn, h)),
            "fc2_b": jnp.zeros((h,), jnp.float32),
            "ln2_g": jnp.ones((h,), jnp.float32),
            "ln2_b": jnp.zeros((h,), jnp.float32),
        })
    return p


def param_specs(cfg):
    """Megatron-style PartitionSpecs over ("model",): qkv/fc1 split the
    output dim, out/fc2 split the input dim; embeddings split the vocab
    row dim; everything else replicated. The sharding-annotation analog of
    the reference's per-device graph cloning + param placement
    (ref: framework/parallel_executor.h:81 BCastParamsToDevices)."""
    layer = {
        "qkv_w": P(None, MODEL_AXIS), "qkv_b": P(MODEL_AXIS),
        "out_w": P(MODEL_AXIS, None), "out_b": P(),
        "ln1_g": P(), "ln1_b": P(),
        "fc1_w": P(None, MODEL_AXIS), "fc1_b": P(MODEL_AXIS),
        "fc2_w": P(MODEL_AXIS, None), "fc2_b": P(),
        "ln2_g": P(), "ln2_b": P(),
    }
    return {
        "embed": {"word": P(MODEL_AXIS, None), "pos": P(), "type": P(),
                  "ln_g": P(), "ln_b": P()},
        "layers": [dict(layer) for _ in range(cfg.num_layers)],
        "mlm": {"dense_w": P(), "dense_b": P(), "ln_g": P(), "ln_b": P(),
                "bias": P(MODEL_AXIS)},
    }


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
# The named scopes (embed, attention, attention_core, ffn, layer_norm, loss;
# optimizer is in optimizer.py) are how a profile of the step is read:
# chipbench's per-layer metrics key on them. They cost nothing at run time.
@jax.named_scope("layer_norm")
def _layer_norm(x, g, b, mesh=None, eps=1e-12):
    # registry-selected body (ops/pallas/registry.py): the stock-jnp
    # reference is bit-identical to the historical inline math here, the
    # Pallas body is one VMEM pass (ops/pallas/layer_norm.py).
    # mesh_scope: under a multi-device mesh GSPMD must partition this, and
    # the layer norm declares no batch split, so it takes the reference.
    from paddle_tpu.ops import pallas as _pk
    with mesh_scope(mesh):
        return _pk.fused_layer_norm(x, g, b, eps=eps)


@jax.named_scope("attention")
def _attention(lp, x, mask_bias, cfg, mesh=None, key_padding_mask=None):
    """MHA. "dense": GSPMD gathers K/V over "seq". "ring": blockwise
    ring attention via shard_map + ppermute (never materialises full
    K/V; parallel/ring_attention.py)."""
    B, S, H = x.shape
    nh, hd = cfg.num_heads, cfg.head_dim
    qkv = x @ lp["qkv_w"].astype(x.dtype) + lp["qkv_b"].astype(x.dtype)

    impl = cfg.attention_impl
    if impl == "auto":
        # Seq-sharded meshes take the ring path — flash is a
        # single-device kernel and would force a gather of the sharded
        # K/V. Else blocks.attention_body: the Pallas flash kernels from
        # blocks.FLASH_FROM positions on, on one chip (the cells mlm_s512
        # and mlm_s4096) and a shard at a time on a mesh that splits only
        # the batch (mlm_s512_dp4), XLA's dense attention below; on a mesh
        # that splits more (model) this function's own dense code up to
        # 1024 positions and the registry's reference beyond.
        if mesh is not None and mesh.shape.get(SEQ_AXIS, 1) > 1:
            impl = "ring"
        else:
            impl = attention_body(S, mesh, B)

    if impl == "flash":
        # Pallas blockwise kernel: [S, S] scores never hit HBM
        # (paddle_tpu/ops/pallas/flash_attention.py). It reads q, k and v
        # where the projection left them, side by side in qkv [B, S, 3H],
        # and writes the context as [B, S, H]: the heads are lane tiles of
        # its blocks, so nothing is split or transposed to [B,N,S,D] here
        # (97 such copies were 23.5 ms of the cell mlm_s512's step).
        # mask_bias [B,1,1,S] is a key-padding bias → [B, S].
        from paddle_tpu.ops import pallas as _pk

        bias = mask_bias.reshape(B, S).astype(jnp.float32)
        with mesh_scope(mesh), jax.named_scope("attention_core"):
            ctx = _pk.flash_attention(qkv, bias=bias, num_heads=nh)
        return ctx @ lp["out_w"].astype(x.dtype) \
            + lp["out_b"].astype(x.dtype)

    q, k, v = jnp.split(qkv, 3, axis=-1)
    if (impl == "ring" and mesh is not None
            and mesh.shape.get(SEQ_AXIS, 1) > 1):
        from paddle_tpu.parallel import ring_attention as _ra
        def bshd(t):
            return t.reshape(B, S, nh, hd)
        # qkv stay in cfg.dtype (bf16 MXU matmuls); ring_attention keeps
        # its softmax stats + output accumulator in fp32 internally.
        # key_padding_mask=None takes the maskless path (no mask permute).
        with jax.named_scope("attention_core"):
            ctx = _ra.ring_attention(mesh, bshd(q), bshd(k), bshd(v),
                                     key_padding_mask=key_padding_mask)
        ctx = ctx.reshape(B, S, H).astype(x.dtype)
        return ctx @ lp["out_w"].astype(x.dtype) \
            + lp["out_b"].astype(x.dtype)

    # dense path stays in [B, S, N, D]: the head dim rides dot_general
    # as a batch dimension, so XLA never materializes the [B,N,S,D]
    # transposes (they showed up as ~7 GB/step of "data formatting" on
    # the profile at bs=64 s=512)
    def heads(t):
        return t.reshape(B, S, nh, hd)

    q, k, v = heads(q), heads(k), heads(v)
    with jax.named_scope("attention_core"):
        scores = jnp.einsum("bqnd,bknd->bnqk", q, k) / math.sqrt(hd)
        if cfg.softmax_dtype == "bf16":
            # skip the fp32 round-trip over [B,N,S,S] (see BertConfig)
            scores = scores + mask_bias.astype(x.dtype)
            probs = jax.nn.softmax(scores, axis=-1)
        else:
            scores = scores + mask_bias  # [B,1,1,S] additive
            probs = jax.nn.softmax(scores.astype(jnp.float32),
                                   axis=-1).astype(x.dtype)
        ctx = jnp.einsum("bnqk,bknd->bqnd", probs, v)
    ctx = ctx.reshape(B, S, H)
    return ctx @ lp["out_w"].astype(x.dtype) + lp["out_b"].astype(x.dtype)


def _block(lp, x, mask_bias, cfg, mesh=None, key_padding_mask=None):
    a = _attention(lp, x, mask_bias, cfg, mesh=mesh,
                   key_padding_mask=key_padding_mask)
    x = _layer_norm(x + a, lp["ln1_g"], lp["ln1_b"], mesh)
    with jax.named_scope("ffn"):
        hme = jax.nn.gelu(x @ lp["fc1_w"].astype(x.dtype)
                          + lp["fc1_b"].astype(x.dtype), approximate=True)
        m = hme @ lp["fc2_w"].astype(x.dtype) + lp["fc2_b"].astype(x.dtype)
    return _layer_norm(x + m, lp["ln2_g"], lp["ln2_b"], mesh)


def forward(params, cfg, input_ids, token_type_ids=None, attention_mask=None,
            mesh=None):
    """Encoder forward; returns [B, S, H] in cfg.dtype. Pass `mesh` to pin
    activation shardings (make_train_step threads its mesh here); without
    one the computation is unconstrained (single device / auto-sharded)."""
    B, S = input_ids.shape
    emb = params["embed"]
    with jax.named_scope("embed"):
        x = (jnp.take(emb["word"], input_ids, axis=0)
             + emb["pos"][None, :S, :]
             + (jnp.take(emb["type"], token_type_ids, axis=0)
                if token_type_ids is not None else 0.0))
        x = _layer_norm(x.astype(cfg.dtype), emb["ln_g"], emb["ln_b"], mesh)
    x = _shard_act(x, mesh)
    if attention_mask is None:
        mask_bias = jnp.zeros((B, 1, 1, S), cfg.dtype)
    else:
        # large finite negative, NOT -inf: fp32 min overflows to -inf in
        # bf16 and an all-padded row would softmax to NaN
        mask_bias = jnp.where(attention_mask[:, None, None, :] > 0, 0.0,
                              -1e9).astype(cfg.dtype)
    kpm = attention_mask

    def blk(lp, x):
        return _block(lp, x, mask_bias, cfg, mesh=mesh,
                      key_padding_mask=kpm)
    if cfg.remat:
        blk = jax.checkpoint(blk)
    for lp in params["layers"]:
        x = blk(lp, x)
        x = _shard_act(x, mesh)
    return x


def _shard_act(x, mesh):
    """Constrain activations to (data, seq, -) on the given mesh."""
    if mesh is None or x.ndim != 3:
        return x
    if mesh.shape.get(DATA_AXIS, 1) * mesh.shape.get(SEQ_AXIS, 1) > 1:
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(DATA_AXIS, SEQ_AXIS, None)))
    return x


def mlm_loss(params, cfg, batch, mesh=None):
    """Masked-LM objective. Two batch layouts:

    - dense: dict(input_ids, labels, weights [, token_type_ids,
      attention_mask]) — labels/weights full-seq with weight 0 on
      unmasked positions.
    - gathered: same but with masked_positions/masked_labels/
      masked_weights [B, P] (P = max predictions, static) — the
      vocab-size head runs only on the ~15% masked positions, the way
      BERT pretraining defines the objective. Cuts head FLOPs by S/P
      (every BERT cell of BENCHMARK.json runs this layout).

    Both are static-shape (no dynamic-count gather), TPU-friendly."""
    hidden = forward(params, cfg, batch["input_ids"],
                     batch.get("token_type_ids"),
                     batch.get("attention_mask"), mesh=mesh)
    with jax.named_scope("loss"):
        if "masked_positions" in batch:
            pos = batch["masked_positions"]
            hidden = jnp.take_along_axis(                    # [B,P,H]
                hidden, pos[..., None].astype(jnp.int32), axis=1)
            lab = batch["masked_labels"]
            w = batch["masked_weights"]
        else:
            lab = batch["labels"]
            w = batch["weights"]
        m = params["mlm"]
        h = hidden @ m["dense_w"].astype(hidden.dtype) \
            + m["dense_b"].astype(hidden.dtype)
        h = jax.nn.gelu(h, approximate=True)
        h = _layer_norm(h, m["ln_g"], m["ln_b"], mesh)
        # tied output embedding (fp32 logits for a stable softmax; measured
        # faster than bf16-in/f32-accum dot_general on this chip — XLA's
        # fp32 path wins for this [BS,768]x[768,30522] shape)
        logits = (h.astype(jnp.float32)
                  @ params["embed"]["word"].T.astype(jnp.float32)
                  + m["bias"])
        logp = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(logp, lab[..., None], axis=-1)[..., 0]
        w = w.astype(jnp.float32)
        denom = jnp.maximum(jnp.sum(w), 1.0)
        return -jnp.sum(picked * w) / denom


# ---------------------------------------------------------------------------
# train step
# ---------------------------------------------------------------------------
def make_train_step(cfg, optimizer, mesh=None, steps_per_call=1):
    """Returns (init_fn, step_fn) jitted over the mesh with tp/dp/sp
    shardings pinned. step(params, opt_state, batch) ->
    (loss, params, opt_state).

    steps_per_call > 1 scans that many optimizer steps inside one jitted
    dispatch (train_from_dataset pattern, ref: executor.py:927 —
    amortizes the host's per-dispatch gap). batch
    leaves may carry a leading [steps_per_call] axis (one slice per
    inner step) or be plain (the same batch reused — fake-data shape)."""
    mesh = mesh or get_mesh()
    pspecs = param_specs(cfg)
    if mesh.shape.get(MODEL_AXIS, 1) == 1:
        pspecs = jax.tree.map(lambda s: P(), pspecs,
                              is_leaf=lambda s: isinstance(s, P))
    pshard = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                          is_leaf=lambda s: isinstance(s, P))
    def init_fn(rng):
        with RecordEvent("trainer/init"):
            params = jax.jit(
                functools.partial(init_params, cfg=cfg),
                out_shardings=pshard)(rng)
            opt_state = optimizer.init(params)
            opt_state = jax.device_put(
                opt_state, optimizer.state_shardings(opt_state, pshard, mesh))
        return params, opt_state

    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: mlm_loss(p, cfg, batch, mesh=mesh))(params)
        new_params, new_opt = optimizer.apply_gradients(
            params, grads, opt_state)
        return loss, new_params, new_opt

    def multi(params, opt_state, batch, stacked):
        def body(carry, xs):
            p, o = carry
            loss, p, o = step(p, o, xs if stacked else batch)
            return (p, o), loss

        (p, o), losses = jax.lax.scan(
            body, (params, opt_state), batch if stacked else None,
            length=None if stacked else steps_per_call)
        return losses[-1], p, o

    if steps_per_call == 1:
        jit_step = jax.jit(step, donate_argnums=(0, 1))
    else:
        jit_step = jax.jit(multi, donate_argnums=(0, 1),
                           static_argnums=(3,))

    # hoisted batch shardings: [B] / [B,S] plus the stacked
    # [K,B] / [K,B,S] variants (step_fn is the per-dispatch hot path)
    dshard = NamedSharding(mesh, P(DATA_AXIS, SEQ_AXIS))
    dshard_b = NamedSharding(mesh, P(DATA_AXIS))
    dshard_k = NamedSharding(mesh, P(None, DATA_AXIS, SEQ_AXIS))
    dshard_bk = NamedSharding(mesh, P(None, DATA_AXIS))

    def place(batch):
        """Put a host batch on the mesh: rows over "data", positions
        over "seq". Returns (batch, stacked)."""
        # a leading [steps_per_call] axis on the ids marks stacked
        # per-inner-step batches; otherwise one batch is reused
        stacked = (steps_per_call > 1
                   and np.ndim(batch["input_ids"]) == 3)
        if stacked and np.shape(batch["input_ids"])[0] != steps_per_call:
            raise ValueError(
                f"stacked batch leading axis "
                f"{np.shape(batch['input_ids'])[0]} != steps_per_call "
                f"{steps_per_call}")
        k = 1 if stacked else 0
        b_sh, s_sh = ((dshard_bk, dshard_k) if stacked
                      else (dshard_b, dshard))
        return {name: jax.device_put(
                    v, b_sh if np.ndim(v) == 1 + k else s_sh)
                for name, v in batch.items()}, stacked

    def step_fn(params, opt_state, batch):
        # host spans (they land on a profile's host plane): the two halves
        # of what the caller waits for before the step is in flight
        with RecordEvent("trainer/place"):
            batch, stacked = place(batch)
        with RecordEvent("trainer/enqueue"):
            if steps_per_call == 1:
                return jit_step(params, opt_state, batch)
            return jit_step(params, opt_state, batch, stacked)

    # for inspection (chip_smoke.py's shard check) and ahead-of-time
    # lowering against a topology (tests/test_tpu_aot_compile.py): the
    # batch placement and the jitted step themselves
    step_fn.place = lambda batch: place(batch)[0]
    step_fn.jitted = jit_step
    return init_fn, step_fn


# ---------------------------------------------------------------------------
# synthetic batch helper (benchmarks / dry runs)
# ---------------------------------------------------------------------------
def synthetic_batch(cfg, batch_size, seq_len=None, seed=0, max_preds=None):
    """Random pretraining batch. With ``max_preds`` set, emits the
    gathered MLM layout (masked_positions/labels/weights [B, P]) that
    runs the vocab head only on masked positions — BERT pretraining's
    max_predictions_per_seq (typically ceil(0.15*S))."""
    seq_len = seq_len or cfg.max_seq
    rng = np.random.RandomState(seed)
    ids = rng.randint(0, cfg.vocab_size, (batch_size, seq_len), dtype=np.int32)
    batch = {
        "input_ids": ids,
        "token_type_ids": np.zeros_like(ids),
        "attention_mask": np.ones_like(ids),
    }
    if max_preds:
        pos = np.stack([rng.choice(seq_len, max_preds, replace=False)
                        for _ in range(batch_size)]).astype(np.int32)
        batch["masked_positions"] = np.sort(pos, axis=1)
        batch["masked_labels"] = rng.randint(
            0, cfg.vocab_size, (batch_size, max_preds), dtype=np.int32)
        batch["masked_weights"] = np.ones((batch_size, max_preds),
                                          np.float32)
    else:
        batch["labels"] = rng.randint(0, cfg.vocab_size,
                                      (batch_size, seq_len), dtype=np.int32)
        batch["weights"] = (rng.rand(batch_size, seq_len)
                            < 0.15).astype(np.float32)
    return batch


def flops_per_token(cfg, seq_len=None, max_preds=None):
    """Approximate training FLOPs/token (fwd+bwd ≈ 3x fwd matmul FLOPs).
    ``max_preds`` scales the vocab-head term to the gathered-MLM layout
    (head runs on P of S positions)."""
    h, f = cfg.hidden, cfg.intermediate
    s = seq_len or cfg.max_seq
    per_layer = 2 * h * 3 * h + 2 * h * h + 2 * h * f + 2 * f * h \
        + 2 * 2 * s * h  # qkv + out + mlp + attention scores/ctx
    head = 2 * h * cfg.vocab_size * ((max_preds / s) if max_preds else 1.0)
    fwd = cfg.num_layers * per_layer + head
    return 3 * fwd
