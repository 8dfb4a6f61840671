"""Runner for next-token language-model training, with the multi-token
prediction module's second loss, through
``paddle_tpu.models.nemotron_h.make_train_step``.

``build(config, traffic, devices)`` returns the :class:`Job` of
``runners/train_lm.py``, as ``runners/train_deepseek_v3.py`` does and with the
same traffic: ``seq_len + 1`` Zipf ids a row over the slice of the vocabulary
the configuration holds. The probe asks the program once, during set-up, how
it routed the reference sample and what every part of its forward pass handed
on (``nemotron_h.stages`` with the next ids: the embedding, the stream after
each of the layers, which are one part each, the final normed hidden states,
then the MTP module's merged state, its two layers and its own final normed
hidden states: ``layers + 6`` parts), and leaves on the job and in the
configuration dict what ``train_deepseek_v3``'s leaves (``routing_counts``,
``held_rows``, ``config["probe"]``; ``program_choice`` and ``program_stream``
on the sample, the stream as the program's own bfloat16, on the host), for
the same readers and for ``reference/nemotron_h.py``.

**What is compared stays off the chip.** The cell's weights and Adam moments
are 10.2 GiB of the chip's 15.75, and ``layers + 6`` parts of [8192, 4096] in
float32 are 2.1 GiB a side of the comparison: the parts are divided by their
norms on the host and handed to the harness's ``compare`` as arrays of jax's
CPU device (where that backend is there; as host arrays otherwise), so its
one jitted norm runs beside the host's memory and not beside the step's.

**The selection biases start at rest**, as ``train_deepseek_v3``'s do and
for its reason (``router_bias_settle``, ``settled``): the six routers'
biases, the MTP module's among them, are moved by ``moe.bias_step`` during
set-up until the load of a few sequences of the cell's own law is even over
the 512 experts; the step itself is the model's.
"""

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.runners import train_step
from chipbench.runners.train_lm import Job
from paddle_tpu import optimizer as pt_optimizer
from paddle_tpu.models import blocks, nemotron_h
from paddle_tpu.parallel import mesh as mesh_mod
from paddle_tpu.parallel import moe


def model_config(config):
    """The program's NemotronHConfig of a configuration file, every width
    as the file gives it; the head counts and ``n_routed_experts`` there
    count what is held here."""
    if config["model_type"] != "nemotron_h" or config["mamba_proj_bias"] \
            or config["attention_bias"] or config["mlp_bias"] \
            or config["use_bias"] or not config["use_conv_bias"] \
            or config["tie_word_embeddings"]:
        raise ValueError("nemotron_h.py has a convolution bias and no "
                         "other, and an untied head")
    if config["n_group"] != 1 or config["topk_group"] != 1 \
            or not config["norm_topk_prob"] or config["n_shared_experts"] != 1:
        raise ValueError("nemotron_h.py has a renormalised sigmoid router "
                         "behind a selection bias, one group of routed "
                         "experts and one shared expert")
    if config["mamba_hidden_act"] != "silu" \
            or config["sliding_window"] is not None:
        raise ValueError("nemotron_h.py has SiLU in a Mamba mixer and no "
                         "attention window")
    if len(config["hybrid_override_pattern"]) != config["num_hidden_layers"]:
        raise ValueError("hybrid_override_pattern names a mixer a layer")
    if config["num_nextn_predict_layers"] not in (0, 1):
        raise ValueError("lm_trainer.py has one multi-token-prediction "
                         "module")
    first, held = config["experts_held"]
    if held != config["n_routed_experts"]:
        raise ValueError("n_routed_experts counts the experts held here")
    return nemotron_h.NemotronHConfig(
        vocab_size=config["vocab_size"], hidden=config["hidden_size"],
        pattern=config["hybrid_override_pattern"],
        mamba_heads=config["mamba_num_heads"],
        mamba_head_dim=config["mamba_head_dim"],
        mamba_groups=config["n_groups"],
        state_size=config["ssm_state_size"],
        conv_kernel=config["conv_kernel"], chunk_size=config["chunk_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], num_experts=config["router_width"],
        experts_per_token=config["num_experts_per_tok"],
        latent_size=config["moe_latent_size"],
        expert_width=config["moe_intermediate_size"],
        shared_width=config["moe_shared_expert_intermediate_size"],
        expert_act=config["mlp_hidden_act"],
        routed_scale=float(config["routed_scaling_factor"]),
        bias_rate=config["router_bias_update_rate"],
        experts_held=(first, held),
        mtp_pattern=config["mtp_hybrid_override_pattern"]
        if config["num_nextn_predict_layers"] else "",
        mtp_weight=config["mtp_loss_weight"],
        rms_eps=config["layer_norm_epsilon"])


def off_the_chip(array):
    """A host array as one of jax's CPU device, so that a jitted function
    of it runs there; the host array itself where jax has no such backend."""
    try:
        return jax.device_put(array, jax.devices("cpu")[0])
    except RuntimeError:
        return array


def build(config, traffic, devices):
    mesh = mesh_mod.make_mesh(mesh_mod.MeshConfig(**traffic["mesh"]),
                              devices=devices)
    if mesh.size != len(devices):
        raise ValueError(f"mesh {traffic['mesh']} wants {mesh.size} devices, "
                         f"the cell has {len(devices)}")
    if traffic["batch"] % mesh.shape[mesh_mod.DATA_AXIS]:
        raise ValueError("the batch does not divide over the data axis")
    o = dict(config["optimizer"])
    opt = getattr(pt_optimizer, o.pop("name"))(**o)
    cfg = model_config(config)
    init_fn, step_fn = nemotron_h.make_train_step(cfg, opt, mesh)
    seq = int(traffic["seq_len"])
    law = 1.0 / np.arange(1, cfg.vocab_size + 1) ** traffic["zipf_exponent"]
    law /= law.sum()
    first, held = cfg.experts_held

    def draw_batch(rs, rows):
        ids = rs.choice(cfg.vocab_size, size=(rows, seq + 1),
                        p=law).astype(np.int32)
        return {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}

    @jax.jit
    def loss_parts_routing(params, batch):
        # one compiled pass: the choices are made on the states handed on
        parts, aux = nemotron_h.stages(params, cfg, batch["input_ids"],
                                       mesh=mesh, next_ids=batch["labels"])
        return (nemotron_h.lm_loss(params, cfg, batch, mesh=mesh), parts,
                aux)

    def probe(params, batch):
        loss, parts, aux = loss_parts_routing(params, batch)
        job.routing_counts = np.asarray(aux["counts"])
        job.held_rows = job.routing_counts[:, first:first + held].sum(axis=1)
        config["probe"] = {"held_rows": [int(n) for n in job.held_rows],
                           "tokens": int(batch["input_ids"].size)}
        # on the host, in the program's own bfloat16: the sample outlives
        # the check, the device's memory is the step's
        stream = np.asarray(parts)
        if job.reference_sample is not None:
            job.reference_sample["program_choice"] = np.asarray(
                aux["choice"]).reshape(aux["choice"].shape[0],
                                       *batch["input_ids"].shape, -1)
            job.reference_sample["program_stream"] = stream
        del parts, aux
        # each part over its norm, on the host, a part at a time
        outputs = np.empty(stream.shape, np.float32)
        for i, part in enumerate(stream):
            outputs[i] = part
            outputs[i] /= np.sqrt(np.sum(np.square(outputs[i],
                                                   dtype=np.float64)))
        return loss, off_the_chip(outputs)

    settle = config.get("router_bias_settle")
    layers = cfg.num_layers
    # (where a router's parameters are, the part its layer reads): the
    # main E layers read the part before them (part l is layer l's input),
    # the module's layer i the module's part i (its merged state first)
    routers = [(("layers", layer), layer) for layer in range(layers)
               if cfg.kind(layer) == "E"] \
        + [(("mtp", "layers", i), layers + 2 + i)
           for i, kind in enumerate(cfg.mtp_pattern) if kind == "E"]

    def of(params, path):
        for key in path:
            params = params[key]
        return params

    @jax.jit
    def router_scores(params, parts):
        """Every router's scores of the pass whose parts these are,
        [expert layers, T, E] float32: the router's own arithmetic on what
        the layer before handed on."""
        def one(path, part):
            lp = of(params, path)
            h = blocks.rms_norm(parts[part], lp["ln_g"],
                                cfg.rms_eps).reshape(-1, cfg.hidden)
            return moe.route(h.astype(jnp.float32), lp["router_w"],
                             cfg.experts_per_token, cfg.scoring)[1]
        return jnp.stack([one(path, part) for path, part in routers])

    @jax.jit
    def at_rest(bias, scores):
        """``bias`` [expert layers, E] after ``settle["steps"]`` steps of
        ``moe.bias_step`` on the load these scores give, the step shrinking
        from ``first_rate`` to ``last_rate``."""
        steps, first, last = (settle[k] for k in ("steps", "first_rate",
                                                  "last_rate"))

        def one(i, bias):
            rate = first * (last / first) ** (i / (steps - 1))
            _, top_e = jax.lax.top_k(scores + bias[:, None, :],
                                     cfg.experts_per_token)
            counts = jax.vmap(lambda e: jnp.bincount(
                e.reshape(-1), length=cfg.num_experts))(top_e)
            return moe.bias_step(bias, counts, rate)

        return jax.lax.fori_loop(0, steps, one, bias)

    def with_bias(params, path, row):
        """``params`` with the router at ``path`` given the bias ``row``."""
        if not path:
            old = params["router_bias"]
            if not isinstance(old, jax.core.Tracer):
                row = jax.device_put(row, old.sharding)
            return dict(params, router_bias=row)
        if isinstance(params, list):
            return [with_bias(p, path[1:], row) if i == path[0] else p
                    for i, p in enumerate(params)]
        return dict(params, **{path[0]: with_bias(params[path[0]], path[1:],
                                                  row)})

    def settled(key):
        """``init_fn``'s state with the selection biases at rest on
        ``settle["sequences"]`` draws of the cell's law (one stream for
        every seed: the law is fitted, not the window's batches). A round is
        one forward pass a draw with the biases so far, then the rule on
        those scores; the second round takes in what the first one's
        choices changed downstream."""
        params, opt_state = init_fn(key)
        rs = np.random.RandomState(0)
        draws = [step_fn.place(draw_batch(rs, traffic["sample_sequences"]))
                 for _ in range(settle["sequences"])]
        for _ in range(settle["rounds"]):
            scores = jnp.concatenate(
                [router_scores(params, loss_parts_routing(params, b)[1])
                 for b in draws], axis=1)
            bias = at_rest(jnp.stack([of(params, path)["router_bias"]
                                      for path, _ in routers]), scores)
            for row, (path, _) in zip(bias, routers):
                params = with_bias(params, path, row)
        return params, opt_state

    job = Job(
        mesh=mesh, optimizer=opt, init_fn=settled if settle else init_fn,
        step_fn=step_fn,
        jitted=step_fn.jitted, place=step_fn.place, draw_batch=draw_batch,
        probe=probe, batch=traffic["batch"],
        tokens_per_step=train_step.TOKENS[traffic["token"]](traffic),
        pool_batches=traffic["pool_batches"],
        sample_sequences=traffic["sample_sequences"])
    job.held_rows = None
    return job
