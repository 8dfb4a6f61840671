"""The decoder skeleton: what the language models built from
``models/blocks.py`` have in common, written once. A model file
(``olmoe.py``, ``kimi_linear.py``, ``laguna.py``, ``qwen3_next.py``,
``lfm2.py``, ``deepseek_v3.py``, ``nemotron_h.py``, ``evabyte.py``) keeps
its config, its parameters and its mixers and hands them over as a
:class:`Decoder`, whose
methods are that module's ``forward``, ``stages``, ``lm_loss``,
``routing_stats`` and ``make_train_step``. A layer is a mixer and a
feed-forward, or a mixer alone; where the parameters hold a
multi-token-prediction module (``params["mtp"]``) the pass runs it behind
the last layer and the loss gains its term. A model with no expert layer
says so (``routed=False``: no counts leave its step and nothing moves after
the update); a config may carry the residual stream in a dtype of its own
(``cfg.stream_dtype``, where the layers compute in ``cfg.dtype``) and name
several prediction heads (``cfg.pred_heads``: head i at position t predicts
the id i + 1 positions on).
``models/bert.py`` and ``models/transformer.py`` carry their own pass and
step (ROADMAP C, "one trainer shape").
"""

import dataclasses
import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from paddle_tpu.models import blocks
from paddle_tpu.ops.pallas import softmax_cross_entropy
from paddle_tpu.ops.pallas.registry import mesh_scope
from paddle_tpu.parallel import moe
from paddle_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS, get_mesh
from paddle_tpu.profiler import RecordEvent

__all__ = ["Decoder", "feed_forward", "make_train_step", "synthetic_batch"]


def feed_forward(lp, x, cfg, mesh=None):
    """(a layer's feed-forward of ``x``, its aux terms): the dense gated one
    where the layer holds ``ffn_gate`` (aux None), its experts otherwise."""
    with jax.named_scope("ffn"):
        if "ffn_gate" in lp:
            return blocks.gated_ffn(x, lp["ffn_gate"], lp["ffn_up"],
                                    lp["ffn_down"]), None
        return moe.dropless_moe_ffn(lp, x, cfg.experts_per_token, mesh=mesh,
                                    scoring=cfg.scoring,
                                    held=cfg.experts_held)


def untied_head(params, hidden):
    """Float32 logits of the hidden states through ``head_w``."""
    return jnp.dot(hidden, params["head_w"].astype(hidden.dtype),
                   preferred_element_type=jnp.float32)


def _stacked(auxes):
    """The layers' aux terms, each stacked over the layers; none where no
    layer returned any."""
    return jax.tree.map(lambda *a: jnp.stack(a), *auxes) if auxes else {}


def _ce_ahead(labels, ahead, logits):
    """The mean cross-entropy of ``logits()`` [B, S, V] at position t against
    the id ``ahead`` positions after its label, ``labels[t + ahead]``, over
    the S - ``ahead`` positions that have one: the masking the
    multi-token-prediction term and the prediction heads share. ``logits``
    is called behind the labels' shift."""
    if not ahead:
        return jnp.mean(softmax_cross_entropy(logits(), labels))
    # position t's label lies ``ahead`` further on; the last have none
    further = jnp.roll(labels, -ahead, axis=1)
    nll = softmax_cross_entropy(logits(), further)
    has_one = jnp.arange(nll.shape[1]) < nll.shape[1] - ahead
    return jnp.sum(nll * has_one) / (nll.shape[0] * (nll.shape[1] - ahead))


def _shard_act(x, mesh):
    if mesh is None or mesh.shape.get(DATA_AXIS, 1) == 1:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(DATA_AXIS, None, None)))


@dataclasses.dataclass(frozen=True)
class Decoder:
    """What a model file brings, as plain functions, and the skeleton around
    them as methods; ``cfg`` is the model's frozen config everywhere."""
    init_params: Callable   # (rng, cfg) -> float32 parameters
    param_specs: Callable   # cfg -> their PartitionSpecs over ("model",)
    #: (lp, x, cfg, layer, rotary, mesh) -> (the stream after the layer's
    #: mixer, after its feed-forward, the expert layer's aux terms or None);
    #: a layer that is one part (a mixer alone: Nemotron-H's) returns (the
    #: stream after it, its aux terms or None)
    block: Callable
    #: (cfg, positions) -> what ``block`` takes as ``rotary``, made once a
    #: pass: a table of angles, one a layer kind, or None
    rotary: Callable
    final_gain: Callable = lambda params: params["final_norm_g"]
    logits: Callable = untied_head      # (params, hidden) -> float32 logits
    #: (cfg, mean cross-entropy, aux terms stacked over the layers) -> the
    #: loss: the cross-entropy and what this model's loss adds to it
    add_aux: Callable = lambda cfg, ce, aux: ce
    #: whether a layer has a router: its counts leave the step, and its
    #: selection bias moves after the update
    routed: bool = True

    def _layers(self, layers, first, x, cfg, rotary, mesh):
        """``x`` through ``layers``, numbered from ``first``: (the stream
        after every part of them, a list; the aux terms of the layers that
        returned some, a list)."""
        auxes, stream = [], []
        for layer, lp in enumerate(layers, first):
            *parts, aux = self.block(lp, x, cfg, layer, rotary, mesh)
            x = parts[-1] = _shard_act(parts[-1], mesh)
            stream += parts
            if aux is not None:
                auxes.append(aux)
        return stream, auxes

    def _pass(self, params, cfg, input_ids, mesh=None, next_ids=None):
        """(final normed hidden states [B, S, H], the aux terms stacked over
        the layers that returned some, the residual stream after the
        embedding and after every part of every layer (a mixer and a
        feed-forward, or a mixer alone), a list; what a
        multi-token-prediction module hands on, a list (``_predict_further``:
        with ``next_ids`` [B, S], the id that follows each position, and such
        a module in the parameters, ``params["mtp"]``; its expert layers' aux
        terms are then stacked behind the main ones), else empty)."""
        with jax.named_scope("embed"):
            x = jnp.take(params["embed"], input_ids, axis=0).astype(
                getattr(cfg, "stream_dtype", cfg.dtype))
        x = _shard_act(x, mesh)
        rotary = self.rotary(cfg, input_ids.shape[1])
        stream, auxes = self._layers(params["layers"], 0, x, cfg, rotary,
                                     mesh)
        stream = [x] + stream
        # a head's operand is in cfg.dtype, whatever carries the stream
        hidden = blocks.rms_norm(stream[-1], self.final_gain(params),
                                 cfg.rms_eps).astype(cfg.dtype)
        further = []
        if next_ids is not None and "mtp" in params:
            further, more = self._predict_further(
                params, cfg, stream[-1], next_ids, rotary, mesh)
            auxes = auxes + more
        return hidden, _stacked(auxes), stream, further

    def _predict_further(self, params, cfg, x, next_ids, rotary, mesh):
        """The multi-token-prediction module (DeepSeek-V3, arXiv:2412.19437,
        sec. 2.2, one module deep): the main model's last state ``x``,
        before its final norm, and the embedding of the NEXT token, each
        RMS-normed, merged by ``eh_w`` [2 H, H] (the state's rows first),
        through the module's own layers (numbered on from the main ones, so
        the model's ``block`` finds their kinds) and its own final norm;
        the embedding, and the head in ``_loss_and_counts``, are the main
        model's. Returns (what the module hands on, a list: the merged
        state, the stream after every part of its layers, its final normed
        hidden states, whose logits predict the token after next; its
        layers' aux terms, a list)."""
        mp = params["mtp"]
        with jax.named_scope("embed"):
            e = jnp.take(params["embed"], next_ids, axis=0).astype(cfg.dtype)
        with jax.named_scope("mtp_merge"):
            # two products and no [B, S, 2 H] concatenation
            w = mp["eh_w"].astype(cfg.dtype)
            h = x.shape[-1]
            x = blocks.rms_normalize(x, mp["hnorm_g"], cfg.rms_eps) @ w[:h] \
                + blocks.rms_normalize(e, mp["enorm_g"], cfg.rms_eps) @ w[h:]
        x = _shard_act(x, mesh)
        stream, auxes = self._layers(mp["layers"], len(params["layers"]), x,
                                     cfg, rotary, mesh)
        stream = [x] + stream
        return stream + [blocks.rms_norm(stream[-1], mp["final_norm_g"],
                                         cfg.rms_eps)], auxes

    def forward(self, params, cfg, input_ids, mesh=None):
        """Decoder forward; returns the final normed hidden states [B, S, H]
        in cfg.dtype (the head is applied in ``lm_loss``)."""
        return self._pass(params, cfg, input_ids, mesh)[0]

    def stages(self, params, cfg, input_ids, mesh=None, next_ids=None):
        """(what every part of the forward pass hands on, [parts, B, S, H]
        in cfg.dtype: the embedding, the residual stream after each layer's
        mixer and after its feed-forward (2 layers + 2 parts; a layer that
        is a mixer alone is one part), then the final normed hidden states
        (``forward``'s), and, with ``next_ids`` and a multi-token-prediction
        module, what that module hands on behind them: its merged state, the
        stream after its layers' parts, its own final normed hidden states;
        the expert layers' aux terms of that same pass, stacked over those
        layers, the module's last: ``counts`` [layers, E], ``choice``
        [layers, T, k] and what else the model's router returns). For a
        check that holds each part to a reference on that part's own input:
        the choices are those made on the states returned, which two
        separately compiled passes do not promise."""
        hidden, aux, stream, further = self._pass(params, cfg, input_ids,
                                                  mesh, next_ids)
        return jnp.stack(stream + [hidden] + further), aux

    def _head_losses(self, params, cfg, hidden, labels):
        """[cfg.pred_heads] the cross-entropy of each prediction head: the
        head's product views as [B, S, heads, V], head i at position t
        against ``labels[t + i]``, the mean over its own S - i positions.
        Under the scope ``multibyte_head``, inside the caller's ``loss``."""
        with jax.named_scope("multibyte_head"):
            logits = self.logits(params, hidden)
            logits = logits.reshape(*labels.shape, cfg.pred_heads, -1)
            return jnp.stack([
                _ce_ahead(labels, i, lambda i=i: logits[:, :, i])
                for i in range(cfg.pred_heads)])

    def head_losses(self, params, cfg, batch, mesh=None):
        """The ``cfg.pred_heads`` cross-entropies ``lm_loss`` is the mean
        of, [heads] float32."""
        hidden = self._pass(params, cfg, batch["input_ids"], mesh)[0]
        with jax.named_scope("loss"), mesh_scope(mesh):
            return self._head_losses(params, cfg, hidden, batch["labels"])

    def _loss_and_counts(self, params, cfg, batch, mesh=None):
        """(``lm_loss``, the counts each expert took [expert layers, E],
        None where no layer has a router); with a multi-token-prediction
        module the second is a pair: the counts, the module's layers last,
        and the two cross-entropies [2] (next token, token after next) the
        loss is made of."""
        hidden, aux, _, further = self._pass(
            params, cfg, batch["input_ids"], mesh, batch["labels"])
        with jax.named_scope("loss"), mesh_scope(mesh):
            if getattr(cfg, "pred_heads", 1) > 1:
                ce = jnp.mean(self._head_losses(params, cfg, hidden,
                                                batch["labels"]))
                return self.add_aux(cfg, ce, aux), aux.get("counts")
            logits = self.logits(params, hidden)
            ce = _ce_ahead(batch["labels"], 0, lambda: logits)
            loss = self.add_aux(cfg, ce, aux)
            if not further:
                return loss, aux.get("counts")
            # position t's merged state predicts id t + 2, the label of
            # position t + 1
            further_ce = _ce_ahead(
                batch["labels"], 1,
                lambda: self.logits(params, further[-1]))
            return loss + cfg.mtp_weight * further_ce, (
                aux["counts"], jnp.stack([ce, further_ce]))

    def lm_loss(self, params, cfg, batch, mesh=None):
        """Mean next-token cross-entropy over every position of
        dict(input_ids, labels) [B, S], over ``cfg.vocab_size`` ids, plus
        what the model's ``add_aux`` adds, plus, where the parameters hold a
        multi-token-prediction module, ``cfg.mtp_weight`` times the mean
        cross-entropy of the token after next over the S - 1 positions that
        have one. With ``cfg.pred_heads`` heads the cross-entropy is the
        mean of theirs (``head_losses``). Logits and loss in float32."""
        return self._loss_and_counts(params, cfg, batch, mesh)[0]

    def routing_stats(self, params, cfg, batch, mesh=None, choices=False):
        """Assignments per expert of a batch over all the router's experts,
        [expert layers, experts] on the host: each row sums to
        ``experts_per_token`` times the batch's tokens; where a model holds
        a share of the experts, the columns of ``experts_held`` are the
        rows this chip computes. The counter a reader takes the experts'
        load from. With ``choices`` also the experts of each token, [expert
        layers, tokens, experts_per_token]."""
        aux = jax.jit(
            lambda p, ids, next_ids: self._pass(p, cfg, ids, mesh,
                                                next_ids)[1])(
            params, batch["input_ids"],
            batch["labels"] if "mtp" in params else None)
        counts = np.asarray(aux["counts"])
        return (counts, np.asarray(aux["choice"])) if choices else counts

    def make_train_step(self, cfg, optimizer, mesh=None):
        """(init_fn, step_fn) of ``make_train_step`` below for this model.
        After the optimizer's update every router's selection bias takes one
        step on the load of this batch (``move_biases``), and the step hands
        the routers' counts out, the counter of a step's load; a model with
        no router (``routed`` false) has neither."""
        if not self.routed:
            return make_train_step(cfg, optimizer, mesh, self.init_params,
                                   self.param_specs, self.lm_loss)
        return make_train_step(
            cfg, optimizer, mesh, self.init_params, self.param_specs,
            self._loss_and_counts,
            after_update=functools.partial(move_biases, cfg))


def move_biases(cfg, params, counts):
    """``params`` with every router's selection bias one step of
    ``moe.bias_step`` on; ``counts`` [expert layers, E] in the layers'
    order, a multi-token-prediction module's layers behind the main ones.
    Only a layer that holds a ``router_bias`` moves."""
    routers = iter(counts)

    def moved(layers):
        return [dict(lp, router_bias=moe.bias_step(
            lp["router_bias"], next(routers), cfg.bias_rate))
            if "router_bias" in lp else lp for lp in layers]

    params = dict(params, layers=moved(params["layers"]))
    if "mtp" in params:       # its routers' counts are the last rows
        params["mtp"] = dict(params["mtp"],
                             layers=moved(params["mtp"]["layers"]))
    return params


def make_train_step(cfg, optimizer, mesh, init_params, param_specs, loss_fn,
                    after_update=None):
    """Returns (init_fn, step_fn) jitted over the mesh with dp/tp shardings
    pinned. ``init_params(rng, cfg=cfg)`` makes the float32 parameters,
    ``param_specs(cfg)`` their PartitionSpecs over ("model",),
    ``loss_fn(params, cfg, batch, mesh=mesh)`` the scalar loss. With
    ``after_update`` the loss function returns (loss, aux) and
    ``after_update(new_params, aux)`` the parameters the step hands back:
    for a rule that moves a parameter outside the gradient (a router's
    selection bias, by the load it saw).
    step(params, opt_state, batch) -> (loss, params, opt_state); params and
    opt_state are donated. ``step_fn.jitted`` and ``step_fn.place`` as
    ``bert.make_train_step`` hands them out. With ``after_update`` the
    jitted step returns the aux as a fourth result and ``step_fn.aux`` holds
    that of the last step enqueued (device arrays: the counter a reader
    takes a step's routing from; reading it waits for that step). Where the
    aux is a tuple, ``after_update`` is given its first and the step
    returns each as a result of its own, ``step_fn.aux`` the list of them
    (a decoder with a multi-token-prediction module: the routers' counts,
    then the two cross-entropies of its loss)."""
    mesh = mesh or get_mesh()
    pspecs = param_specs(cfg)
    if mesh.shape.get(MODEL_AXIS, 1) == 1:
        pspecs = jax.tree.map(lambda s: P(), pspecs,
                              is_leaf=lambda s: isinstance(s, P))
    pshard = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                          is_leaf=lambda s: isinstance(s, P))

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
            lambda p: loss_fn(p, cfg, batch, mesh=mesh),
            has_aux=after_update is not None)(params)
        if after_update is not None:
            loss, aux = loss
        new_params, new_opt = optimizer.apply_gradients(
            params, grads, opt_state)
        if after_update is None:
            return loss, new_params, new_opt
        # a loss function may hand out more than the rule reads: the first
        # of a tuple is the rule's, the others follow it out of the step
        aux = aux if isinstance(aux, tuple) else (aux,)
        return loss, after_update(new_params, aux[0]), new_opt, *aux

    jit_step = jax.jit(step, donate_argnums=(0, 1))
    dshard = NamedSharding(mesh, P(DATA_AXIS))

    def place(batch):
        """Put a host batch on the mesh: rows over "data"."""
        return {name: jax.device_put(v, dshard) for name, v in batch.items()}

    def step_fn(params, opt_state, batch):
        with RecordEvent("trainer/place"):
            batch = place(batch)
        with RecordEvent("trainer/enqueue"):
            loss, params, opt_state, *step_fn.aux = jit_step(
                params, opt_state, batch)
        return loss, params, opt_state

    step_fn.aux = []
    step_fn.place = place
    step_fn.jitted = jit_step
    return init_fn, step_fn


def synthetic_batch(cfg, batch_size, seq_len, seed=0):
    """Random next-token batch: ``seq_len + 1`` uniform ids a row, inputs
    the first ``seq_len``, labels the last."""
    ids = np.random.RandomState(seed).randint(
        0, cfg.vocab_size, (batch_size, seq_len + 1), dtype=np.int32)
    return {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
