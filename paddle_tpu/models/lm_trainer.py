"""The decoder skeleton: what the language models built from
``models/blocks.py`` have in common, written once. A model file
(``olmoe.py``, ``kimi_linear.py``, ``laguna.py``, ``qwen3_next.py``,
``lfm2.py``) keeps its config, its parameters and its mixers and hands them
over as a :class:`Decoder`, whose methods are that module's ``forward``,
``stages``, ``lm_loss``, ``routing_stats`` and ``make_train_step``.
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
    #: mixer, after its feed-forward, the expert layer's aux terms or None)
    block: Callable
    #: (cfg, positions) -> what ``block`` takes as ``rotary``, made once a
    #: pass: a table of angles, one a layer kind, or None
    rotary: Callable
    final_gain: Callable = lambda params: params["final_norm_g"]
    logits: Callable = untied_head      # (params, hidden) -> float32 logits
    #: (cfg, mean cross-entropy, aux terms stacked over the layers) -> the
    #: loss: the cross-entropy and what this model's loss adds to it
    add_aux: Callable = lambda cfg, ce, aux: ce

    def _pass(self, params, cfg, input_ids, mesh=None):
        """(final normed hidden states [B, S, H], the aux terms stacked over
        the layers that returned some, the residual stream after the
        embedding and after every mixer and feed-forward, a list of
        2 layers + 1)."""
        with jax.named_scope("embed"):
            x = jnp.take(params["embed"], input_ids,
                         axis=0).astype(cfg.dtype)
        x = _shard_act(x, mesh)
        rotary = self.rotary(cfg, input_ids.shape[1])
        auxes, stream = [], [x]
        for layer, lp in enumerate(params["layers"]):
            h, x, aux = self.block(lp, x, cfg, layer, rotary, mesh)
            x = _shard_act(x, mesh)
            stream += [h, x]
            if aux is not None:
                auxes.append(aux)
        hidden = blocks.rms_norm(x, self.final_gain(params), cfg.rms_eps)
        return hidden, jax.tree.map(lambda *a: jnp.stack(a), *auxes), stream

    def forward(self, params, cfg, input_ids, mesh=None):
        """Decoder forward; returns the final normed hidden states [B, S, H]
        in cfg.dtype (the head is applied in ``lm_loss``)."""
        return self._pass(params, cfg, input_ids, mesh)[0]

    def stages(self, params, cfg, input_ids, mesh=None):
        """(what every part of the forward pass hands on, [2 layers + 2, B,
        S, H] in cfg.dtype: the embedding, the residual stream after each
        layer's mixer and after its feed-forward, and last the final normed
        hidden states (``forward``'s); the expert layers' aux terms of that
        same pass, stacked over those layers: ``counts`` [layers, E],
        ``choice`` [layers, T, k] and what else the model's router returns).
        For a check that holds each part to a reference on that part's own
        input: the choices are those made on the states returned, which two
        separately compiled passes do not promise."""
        hidden, aux, stream = self._pass(params, cfg, input_ids, mesh)
        return jnp.stack(stream + [hidden]), aux

    def _loss_and_counts(self, params, cfg, batch, mesh=None):
        """(``lm_loss``, the counts each expert took [expert layers, E])."""
        hidden, aux, _ = self._pass(params, cfg, batch["input_ids"], mesh)
        with jax.named_scope("loss"), mesh_scope(mesh):
            logits = self.logits(params, hidden)
            nll = softmax_cross_entropy(logits, batch["labels"])
            return self.add_aux(cfg, jnp.mean(nll), aux), aux["counts"]

    def lm_loss(self, params, cfg, batch, mesh=None):
        """Mean next-token cross-entropy over every position of
        dict(input_ids, labels) [B, S], over ``cfg.vocab_size`` ids, plus
        what the model's ``add_aux`` adds. Logits and loss in float32."""
        return self._loss_and_counts(params, cfg, batch, mesh)[0]

    def routing_stats(self, params, cfg, batch, mesh=None, choices=False):
        """Assignments per expert of a batch over all the router's experts,
        [expert layers, experts] on the host: each row sums to
        ``experts_per_token`` times the batch's tokens; where a model holds
        a share of the experts, the columns of ``experts_held`` are the
        rows this chip computes. The counter a reader takes the experts'
        load from. With ``choices`` also the experts of each token, [expert
        layers, tokens, experts_per_token]."""
        aux = jax.jit(lambda p, ids: self._pass(p, cfg, ids, mesh)[1])(
            params, batch["input_ids"])
        counts = np.asarray(aux["counts"])
        return (counts, np.asarray(aux["choice"])) if choices else counts

    def make_train_step(self, cfg, optimizer, mesh=None):
        """(init_fn, step_fn) of ``make_train_step`` below for this model.
        After the optimizer's update every router's selection bias takes one
        step on the load of this batch (``move_biases``), and the step hands
        the routers' counts out, the counter of a step's load."""
        return make_train_step(
            cfg, optimizer, mesh, self.init_params, self.param_specs,
            self._loss_and_counts,
            after_update=functools.partial(move_biases, cfg))


def move_biases(cfg, params, counts):
    """``params`` with every router's selection bias one step of
    ``moe.bias_step`` on; ``counts`` [expert layers, E] in the layers'
    order. Only a layer that holds a ``router_bias`` moves."""
    routers = iter(counts)
    layers = [dict(lp, router_bias=moe.bias_step(
        lp["router_bias"], next(routers), cfg.bias_rate))
        if "router_bias" in lp else lp for lp in params["layers"]]
    return dict(params, layers=layers)


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
    takes a step's routing from; reading it waits for that step)."""
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
        return loss, after_update(new_params, aux), new_opt, aux

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
