"""Transformer encoder-decoder for NMT — BASELINE config "Transformer-big
WMT En-De" and machine_translation parity.

Parity targets: the reference's transformer test model (ref:
python/paddle/fluid/tests/unittests/dist_transformer.py — full
encoder/decoder with multi-head attention from primitive ops) and the book
machine_translation example (ref: python/paddle/fluid/tests/book/
test_machine_translation.py, seq2seq + beam search decode via
operators/beam_search_op.cc / beam_search_decode_op.cc).

TPU-first design notes:
- static shapes + padding masks everywhere (LoD replacement);
- bf16 compute, fp32 softmax/logits;
- greedy & beam-search decode as lax.while_loop / lax.scan with a fixed
  max_len — the structured-control-flow answer to the reference's
  dynamic beam_search op chain (ref: operators/controlflow/while_op.cc +
  beam_search_op.cc), fully jittable;
- decode keeps a KV cache laid out [layers, B*beam, S, H] updated with
  lax.dynamic_update_slice — no growing shapes under jit;
- tp sharding of qkv/ffn over "model" axis via the same megatron specs
  as models/bert.py.
"""

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from paddle_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS, get_mesh
from paddle_tpu.profiler import RecordEvent

__all__ = ["TransformerConfig", "transformer_base", "transformer_big",
           "transformer_tiny", "init_params", "forward", "nmt_loss",
           "make_train_step", "greedy_decode", "beam_search_decode",
           "synthetic_batch"]


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    src_vocab: int = 32768
    tgt_vocab: int = 32768
    hidden: int = 512
    num_heads: int = 8
    ffn: int = 2048
    enc_layers: int = 6
    dec_layers: int = 6
    max_seq: int = 256
    dropout: float = 0.1
    dtype: object = jnp.bfloat16
    label_smoothing: float = 0.1
    bos_id: int = 0
    eos_id: int = 1
    remat: bool = False

    @property
    def head_dim(self):
        return self.hidden // self.num_heads


def transformer_base(**kw):
    return TransformerConfig(**kw)


def transformer_big(**kw):
    kw.setdefault("hidden", 1024)
    kw.setdefault("num_heads", 16)
    kw.setdefault("ffn", 4096)
    return TransformerConfig(**kw)


def transformer_tiny(**kw):
    kw.setdefault("src_vocab", 64)
    kw.setdefault("tgt_vocab", 64)
    kw.setdefault("hidden", 32)
    kw.setdefault("num_heads", 4)
    kw.setdefault("ffn", 64)
    kw.setdefault("enc_layers", 2)
    kw.setdefault("dec_layers", 2)
    kw.setdefault("max_seq", 16)
    return TransformerConfig(**kw)


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------
def _dense(key, i, o, scale=None):
    scale = scale if scale is not None else np.sqrt(1.0 / i)
    return (scale * jax.random.normal(key, (i, o))).astype(jnp.float32)


def _ln_init(h):
    return {"g": jnp.ones((h,), jnp.float32),
            "b": jnp.zeros((h,), jnp.float32)}


def _attn_init(keys, h):
    return {"q_w": _dense(next(keys), h, h), "q_b": jnp.zeros((h,)),
            "k_w": _dense(next(keys), h, h), "k_b": jnp.zeros((h,)),
            "v_w": _dense(next(keys), h, h), "v_b": jnp.zeros((h,)),
            "o_w": _dense(next(keys), h, h), "o_b": jnp.zeros((h,))}


def _ffn_init(keys, h, f):
    return {"w1": _dense(next(keys), h, f), "b1": jnp.zeros((f,)),
            "w2": _dense(next(keys), f, h), "b2": jnp.zeros((h,))}


def init_params(rng, cfg):
    h = cfg.hidden
    n = 2 + cfg.enc_layers * 6 + cfg.dec_layers * 10 + 2
    keys = iter(jax.random.split(rng, n))
    p = {
        "src_embed": _dense(next(keys), cfg.src_vocab, h, scale=0.02),
        "tgt_embed": _dense(next(keys), cfg.tgt_vocab, h, scale=0.02),
        "enc": [], "dec": [],
        "enc_ln": _ln_init(h), "dec_ln": _ln_init(h),
    }
    for _ in range(cfg.enc_layers):
        p["enc"].append({
            "attn": _attn_init(keys, h), "ln1": _ln_init(h),
            "ffn": _ffn_init(keys, h, cfg.ffn), "ln2": _ln_init(h),
        })
    for _ in range(cfg.dec_layers):
        p["dec"].append({
            "self_attn": _attn_init(keys, h), "ln1": _ln_init(h),
            "cross_attn": _attn_init(keys, h), "ln2": _ln_init(h),
            "ffn": _ffn_init(keys, h, cfg.ffn), "ln3": _ln_init(h),
        })
    return p


def param_specs(cfg):
    """Megatron specs on the "model" axis (attention heads + ffn split)."""
    attn = {"q_w": P(None, MODEL_AXIS), "q_b": P(MODEL_AXIS),
            "k_w": P(None, MODEL_AXIS), "k_b": P(MODEL_AXIS),
            "v_w": P(None, MODEL_AXIS), "v_b": P(MODEL_AXIS),
            "o_w": P(MODEL_AXIS, None), "o_b": P()}
    ffn = {"w1": P(None, MODEL_AXIS), "b1": P(MODEL_AXIS),
           "w2": P(MODEL_AXIS, None), "b2": P()}
    ln = {"g": P(), "b": P()}
    return {
        "src_embed": P(MODEL_AXIS, None),
        "tgt_embed": P(MODEL_AXIS, None),
        "enc": [{"attn": dict(attn), "ln1": dict(ln), "ffn": dict(ffn),
                 "ln2": dict(ln)} for _ in range(cfg.enc_layers)],
        "dec": [{"self_attn": dict(attn), "ln1": dict(ln),
                 "cross_attn": dict(attn), "ln2": dict(ln),
                 "ffn": dict(ffn), "ln3": dict(ln)}
                for _ in range(cfg.dec_layers)],
        "enc_ln": dict(ln), "dec_ln": dict(ln),
    }


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
# The named scopes are the vocabulary of models/bert.py (embed, attention,
# attention_core, ffn, layer_norm, loss): how a profile of the step is read.
@jax.named_scope("layer_norm")
def _layer_norm(x, ln, eps=1e-6):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
    return ((x32 - mu) * jax.lax.rsqrt(var + eps) * ln["g"]
            + ln["b"]).astype(x.dtype)


def _sinusoid(max_seq, h):
    pos = np.arange(max_seq)[:, None]
    i = np.arange(h // 2)[None, :]
    angle = pos / np.power(10000.0, 2 * i / h)
    enc = np.concatenate([np.sin(angle), np.cos(angle)], axis=-1)
    return jnp.asarray(enc, jnp.float32)


def _heads(t, nh, hd):
    B, S, _ = t.shape
    return t.reshape(B, S, nh, hd).transpose(0, 2, 1, 3)


@jax.named_scope("attention")
def _mha(ap, q_in, kv_in, bias, cfg, kv=None):
    """bias: additive [B,1,q,k] fp32-safe. kv: optional precomputed (k, v)
    (cached cross-attention / incremental decode)."""
    nh, hd = cfg.num_heads, cfg.head_dim
    dt = q_in.dtype
    q = _heads(q_in @ ap["q_w"].astype(dt) + ap["q_b"].astype(dt), nh, hd)
    if kv is None:
        k = _heads(kv_in @ ap["k_w"].astype(dt) + ap["k_b"].astype(dt),
                   nh, hd)
        v = _heads(kv_in @ ap["v_w"].astype(dt) + ap["v_b"].astype(dt),
                   nh, hd)
    else:
        k, v = kv
    with jax.named_scope("attention_core"):
        scores = jnp.einsum("bnqd,bnkd->bnqk", q, k) / math.sqrt(hd)
        scores = scores.astype(jnp.float32) + bias
        probs = jax.nn.softmax(scores, axis=-1).astype(dt)
        ctx = jnp.einsum("bnqk,bnkd->bnqd", probs, v)
    B, _, S, _ = ctx.shape
    ctx = ctx.transpose(0, 2, 1, 3).reshape(B, S, nh * hd)
    return ctx @ ap["o_w"].astype(dt) + ap["o_b"].astype(dt), (k, v)


@jax.named_scope("ffn")
def _ffn(fp, x):
    dt = x.dtype
    f = jax.nn.relu(x @ fp["w1"].astype(dt) + fp["b1"].astype(dt))
    return f @ fp["w2"].astype(dt) + fp["b2"].astype(dt)


@jax.named_scope("embed")
def _embed(table, ids, cfg):
    """Token embedding scaled by sqrt(hidden), plus the sinusoid positions."""
    x = jnp.take(table, ids, axis=0) * math.sqrt(cfg.hidden)
    pos = _sinusoid(cfg.max_seq, cfg.hidden)[None, :ids.shape[1]]
    return (x + pos).astype(cfg.dtype)


def _enc_layer(lp, x, bias, cfg):
    a, _ = _mha(lp["attn"], x, x, bias, cfg)
    x = _layer_norm(x + a, lp["ln1"])
    return _layer_norm(x + _ffn(lp["ffn"], x), lp["ln2"])


def encode(params, cfg, src_ids, src_mask):
    x = _embed(params["src_embed"], src_ids, cfg)
    bias = jnp.where(src_mask[:, None, None, :] > 0, 0.0, -1e9)
    layer = _enc_layer
    if cfg.remat:
        layer = jax.checkpoint(_enc_layer, static_argnums=(3,))
    for lp in params["enc"]:
        x = layer(lp, x, bias, cfg)
    return _layer_norm(x, params["enc_ln"])


def _dec_layer(lp, x, self_bias, memory, mem_bias, cfg, cache=None, pos=None,
               cross_kv=None):
    if cache is None:
        a, _ = _mha(lp["self_attn"], x, x, self_bias, cfg)
        new_self = None
    else:
        # incremental: write this step's k/v into the cache at `pos`
        nh, hd = cfg.num_heads, cfg.head_dim
        dt = x.dtype
        ap = lp["self_attn"]
        k_new = _heads(x @ ap["k_w"].astype(dt) + ap["k_b"].astype(dt),
                       nh, hd)
        v_new = _heads(x @ ap["v_w"].astype(dt) + ap["v_b"].astype(dt),
                       nh, hd)
        k = jax.lax.dynamic_update_slice(cache["k"], k_new, (0, 0, pos, 0))
        v = jax.lax.dynamic_update_slice(cache["v"], v_new, (0, 0, pos, 0))
        a, _ = _mha(ap, x, None, self_bias, cfg, kv=(k, v))
        new_self = {"k": k, "v": v}
    x = _layer_norm(x + a, lp["ln1"])
    c, _ = _mha(lp["cross_attn"], x, memory, mem_bias, cfg, kv=cross_kv)
    x = _layer_norm(x + c, lp["ln2"])
    return _layer_norm(x + _ffn(lp["ffn"], x), lp["ln3"]), new_self


def decode_train(params, cfg, tgt_ids, memory, src_mask, tgt_mask):
    """Teacher-forced decoder over the whole target (causal mask)."""
    T = tgt_ids.shape[1]
    x = _embed(params["tgt_embed"], tgt_ids, cfg)
    causal = jnp.tril(jnp.ones((T, T), jnp.float32))
    self_bias = jnp.where(
        (causal[None, None] * tgt_mask[:, None, None, :]) > 0, 0.0, -1e9)
    mem_bias = jnp.where(src_mask[:, None, None, :] > 0, 0.0, -1e9)
    for lp in params["dec"]:
        x, _ = _dec_layer(lp, x, self_bias, memory, mem_bias, cfg)
    x = _layer_norm(x, params["dec_ln"])
    # tied output projection, fp32 logits
    with jax.named_scope("loss"):
        return x.astype(jnp.float32) @ params["tgt_embed"].T


def forward(params, cfg, src_ids, tgt_ids, src_mask=None, tgt_mask=None):
    src_mask = src_mask if src_mask is not None else jnp.ones_like(src_ids)
    tgt_mask = tgt_mask if tgt_mask is not None else jnp.ones_like(tgt_ids)
    memory = encode(params, cfg, src_ids, src_mask)
    return decode_train(params, cfg, tgt_ids, memory, src_mask, tgt_mask)


def nmt_loss(params, cfg, batch):
    """batch: src_ids, src_mask, tgt_in, tgt_out, tgt_mask. Label-smoothed
    CE averaged over non-pad target tokens.

    Smoothed CE decomposes as
    -( (1-eps) * logp[target] + eps/V * sum(logp) ): a take_along_axis
    + a reduction — no [B, T, V] one-hot materialization (at the WMT
    big config that tensor is B*T*V*4 = 1 GB of HBM traffic per step).
    """
    logits = forward(params, cfg, batch["src_ids"], batch["tgt_in"],
                     batch.get("src_mask"), batch.get("tgt_mask"))
    eps, n = cfg.label_smoothing, cfg.tgt_vocab
    with jax.named_scope("loss"):
        logp = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(
            logp, batch["tgt_out"][..., None].astype(jnp.int32),
            axis=-1)[..., 0]
        ll = (1.0 - eps) * picked + (eps / n) * jnp.sum(logp, axis=-1)
        w = batch["tgt_mask"].astype(jnp.float32) \
            if "tgt_mask" in batch else jnp.ones_like(ll)
        return -jnp.sum(ll * w) / jnp.maximum(jnp.sum(w), 1.0)


# ---------------------------------------------------------------------------
# train step
# ---------------------------------------------------------------------------
def make_train_step(cfg, optimizer, mesh=None):
    mesh = mesh or get_mesh()
    pspecs = param_specs(cfg)
    if mesh.shape.get(MODEL_AXIS, 1) == 1:
        pspecs = jax.tree.map(lambda s: P(), pspecs,
                              is_leaf=lambda s: isinstance(s, P))
    pshard = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                          is_leaf=lambda s: isinstance(s, P))
    dsh = NamedSharding(mesh, P(DATA_AXIS))

    def init_fn(rng):
        with RecordEvent("trainer/init"):
            params = jax.jit(functools.partial(init_params, cfg=cfg),
                             out_shardings=pshard)(rng)
            opt_state = optimizer.init(params)
            opt_state = jax.device_put(
                opt_state, optimizer.state_shardings(opt_state, pshard, mesh))
        return params, opt_state

    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: nmt_loss(p, cfg, batch))(params)
        new_params, new_opt = optimizer.apply_gradients(
            params, grads, opt_state)
        return loss, new_params, new_opt

    jit_step = jax.jit(step, donate_argnums=(0, 1))

    def place(batch):
        """Put a host batch on the mesh, rows over "data"."""
        # device-resident feeds pass through (np.asarray on a jax array
        # would round-trip it to host); device_put no-ops on committed
        # arrays with matching sharding
        return {k: jax.device_put(
                    v if isinstance(v, jnp.ndarray) else np.asarray(v), dsh)
                for k, v in batch.items()}

    def step_fn(params, opt_state, batch):
        # host spans, as bert.make_train_step's
        with RecordEvent("trainer/place"):
            batch = place(batch)
        with RecordEvent("trainer/enqueue"):
            return jit_step(params, opt_state, batch)

    # for inspection and ahead-of-time lowering, as bert.make_train_step
    step_fn.place = place
    step_fn.jitted = jit_step
    return init_fn, step_fn


# ---------------------------------------------------------------------------
# decoding (jittable; replaces beam_search_op.cc / while_op chains)
# ---------------------------------------------------------------------------
def _init_cache(cfg, B):
    return [{"k": jnp.zeros((B, cfg.num_heads, cfg.max_seq, cfg.head_dim),
                            cfg.dtype),
             "v": jnp.zeros((B, cfg.num_heads, cfg.max_seq, cfg.head_dim),
                            cfg.dtype)}
            for _ in range(cfg.dec_layers)]


def _cross_kv(params, cfg, memory):
    """Pre-project encoder memory to per-layer cross-attention K/V once
    (instead of re-projecting it every decode step)."""
    nh, hd = cfg.num_heads, cfg.head_dim
    dt = memory.dtype
    out = []
    for lp in params["dec"]:
        ap = lp["cross_attn"]
        k = _heads(memory @ ap["k_w"].astype(dt) + ap["k_b"].astype(dt),
                   nh, hd)
        v = _heads(memory @ ap["v_w"].astype(dt) + ap["v_b"].astype(dt),
                   nh, hd)
        out.append((k, v))
    return out


def _decode_step(params, cfg, tok, pos, caches, cross_kvs, mem_bias):
    """One incremental decoder step. tok: [B] int32. Returns (logits [B,V],
    new caches)."""
    x = jnp.take(params["tgt_embed"], tok, axis=0) * math.sqrt(cfg.hidden)
    x = (x + _sinusoid(cfg.max_seq, cfg.hidden)[pos]).astype(cfg.dtype)
    x = x[:, None, :]  # [B,1,H]
    # mask future cache slots
    valid = (jnp.arange(cfg.max_seq) <= pos)[None, None, None, :]
    self_bias = jnp.where(valid, 0.0, -1e9)
    new_caches = []
    for lp, cache, ckv in zip(params["dec"], caches, cross_kvs):
        x, nc = _dec_layer(lp, x, self_bias, None, mem_bias, cfg,
                           cache=cache, pos=pos, cross_kv=ckv)
        new_caches.append(nc)
    x = _layer_norm(x, params["dec_ln"])
    logits = x[:, 0].astype(jnp.float32) @ params["tgt_embed"].T
    return logits, new_caches


@functools.partial(jax.jit, static_argnums=(1, 4))
def greedy_decode(params, cfg, src_ids, src_mask, max_len=None):
    """Greedy argmax decode via lax.scan; returns [B, max_len] int32."""
    max_len = max_len or cfg.max_seq
    if max_len > cfg.max_seq:
        raise ValueError(
            f"max_len={max_len} exceeds cfg.max_seq={cfg.max_seq}: the "
            f"K/V cache and sinusoid table are sized to max_seq")
    B = src_ids.shape[0]
    memory = encode(params, cfg, src_ids, src_mask)
    cross_kvs = _cross_kv(params, cfg, memory)
    mem_bias = jnp.where(src_mask[:, None, None, :] > 0, 0.0, -1e9)
    caches = _init_cache(cfg, B)

    def body(carry, pos):
        tok, caches, done = carry
        logits, caches = _decode_step(params, cfg, tok, pos, caches,
                                      cross_kvs, mem_bias)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        nxt = jnp.where(done, cfg.eos_id, nxt)
        done = done | (nxt == cfg.eos_id)
        return (nxt, caches, done), nxt

    init = (jnp.full((B,), cfg.bos_id, jnp.int32), caches,
            jnp.zeros((B,), bool))
    _, toks = jax.lax.scan(body, init, jnp.arange(max_len))
    return toks.T  # [B, max_len]


@functools.partial(jax.jit, static_argnums=(1, 4, 5))
def beam_search_decode(params, cfg, src_ids, src_mask, beam_size=4,
                       max_len=None, alpha=0.6):
    """Batched beam search under jit (ref: operators/beam_search_op.cc +
    beam_search_decode_op.cc, rebuilt as a static lax.scan over length with
    top-k beam pruning each step). Returns (tokens [B, beam, max_len],
    scores [B, beam]) sorted best-first with GNMT length penalty."""
    max_len = max_len or cfg.max_seq
    if max_len > cfg.max_seq:
        raise ValueError(
            f"max_len={max_len} exceeds cfg.max_seq={cfg.max_seq}: the "
            f"K/V cache and sinusoid table are sized to max_seq")
    B = src_ids.shape[0]
    K = beam_size
    V = cfg.tgt_vocab
    memory = encode(params, cfg, src_ids, src_mask)
    # expand to B*K rows; cross K/V projected once then row-repeated
    cross_kvs = [(jnp.repeat(k, K, axis=0), jnp.repeat(v, K, axis=0))
                 for k, v in _cross_kv(params, cfg, memory)]
    mbias = jnp.where(jnp.repeat(src_mask, K, axis=0)[:, None, None, :] > 0,
                      0.0, -1e9)
    caches = _init_cache(cfg, B * K)

    neg_inf = -1e9
    # beam 0 live at score 0, others dead so the first expansion picks
    # distinct tokens, not K copies of beam 0
    scores0 = jnp.tile(jnp.array([0.0] + [neg_inf] * (K - 1), jnp.float32),
                       (B, 1))

    def body(carry, pos):
        tok, caches, scores, done = carry          # tok [B,K]
        logits, caches = _decode_step(params, cfg, tok.reshape(B * K), pos,
                                      caches, cross_kvs, mbias)
        logp = jax.nn.log_softmax(logits, axis=-1).reshape(B, K, V)
        # finished beams only extend with EOS at no cost
        eos_only = jnp.full((V,), neg_inf).at[cfg.eos_id].set(0.0)
        logp = jnp.where(done[..., None], eos_only[None, None], logp)
        cand = scores[..., None] + logp            # [B,K,V]
        flat = cand.reshape(B, K * V)
        new_scores, idx = jax.lax.top_k(flat, K)   # [B,K]
        beam_src = idx // V
        new_tok = (idx % V).astype(jnp.int32)
        # reorder caches + done along beam dim
        gather_rows = (jnp.arange(B)[:, None] * K + beam_src).reshape(-1)
        caches = jax.tree.map(lambda c: c[gather_rows], caches)
        done = jnp.take_along_axis(done, beam_src, axis=1) \
            | (new_tok == cfg.eos_id)
        return (new_tok, caches, new_scores, done), (new_tok, beam_src)

    init = (jnp.full((B, K), cfg.bos_id, jnp.int32), caches, scores0,
            jnp.zeros((B, K), bool))
    (_, _, scores, _), (toks, srcs) = jax.lax.scan(
        body, init, jnp.arange(max_len))

    # backtrace: follow beam_src pointers from the last step
    def backtrace(carry, t):
        beam_idx = carry                           # [B,K]
        tok_t, src_t = t
        tok = jnp.take_along_axis(tok_t, beam_idx, axis=1)
        beam_idx = jnp.take_along_axis(src_t, beam_idx, axis=1)
        return beam_idx, tok

    last = jnp.tile(jnp.arange(K)[None], (B, 1))
    _, rev = jax.lax.scan(backtrace, last, (toks[::-1], srcs[::-1]))
    seqs = rev[::-1].transpose(1, 2, 0)            # [B,K,max_len]
    # GNMT length penalty on final scores
    lengths = jnp.sum((seqs != cfg.eos_id).astype(jnp.float32), axis=-1) + 1.0
    lp = jnp.power((5.0 + lengths) / 6.0, alpha)
    final = scores / lp
    order = jnp.argsort(-final, axis=1)
    seqs = jnp.take_along_axis(seqs, order[..., None], axis=1)
    final = jnp.take_along_axis(final, order, axis=1)
    return seqs, final


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------
def flops_per_step(cfg, batch, src_len, tgt_len):
    """Approximate training matmul FLOPs per step (fwd+bwd ~= 3x fwd),
    for MFU accounting (same convention as bert.flops_per_token)."""
    h, f = cfg.hidden, cfg.ffn
    S, T = src_len, tgt_len
    # every term below already counts multiply-adds as 2 FLOPs.
    # encoder/layer: qkvo 8h^2 per token + ffn 4hf per token +
    # scores+ctx einsums 4*S^2*h
    enc = cfg.enc_layers * (S * (8 * h * h + 4 * h * f) + 4 * S * S * h)
    # decoder/layer: self qkvo + ffn per tgt token, self attn 4*T^2*h
    # (full, not the causal half — conservative MFU), cross q/o
    # 4h^2 per tgt token, cross k/v 4h^2 per SRC token, cross attn
    # 4*T*S*h
    dec = cfg.dec_layers * (
        T * (8 * h * h + 4 * h * f) + 4 * T * T * h
        + S * 4 * h * h + 4 * T * S * h)
    logits = 2 * h * cfg.tgt_vocab * T
    return 3 * batch * (enc + dec + logits)


def synthetic_batch(cfg, batch_size, src_len=None, tgt_len=None, seed=0):
    src_len = src_len or cfg.max_seq
    tgt_len = tgt_len or cfg.max_seq
    rng = np.random.RandomState(seed)
    src = rng.randint(2, cfg.src_vocab, (batch_size, src_len), dtype=np.int32)
    tgt = rng.randint(2, cfg.tgt_vocab, (batch_size, tgt_len), dtype=np.int32)
    tgt_in = np.concatenate(
        [np.full((batch_size, 1), cfg.bos_id, np.int32), tgt[:, :-1]], axis=1)
    return {"src_ids": src, "src_mask": np.ones_like(src),
            "tgt_in": tgt_in, "tgt_out": tgt,
            "tgt_mask": np.ones_like(tgt)}
