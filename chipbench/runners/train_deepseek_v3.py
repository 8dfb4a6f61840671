"""Runner for next-token language-model training through
``paddle_tpu.models.deepseek_v3.make_train_step``.

``build(config, traffic, devices)`` returns the :class:`Job` of
``runners/train_lm.py``, as ``runners/train_laguna.py`` does and with the same
traffic: ``seq_len + 1`` Zipf ids a row over the slice of the vocabulary the
configuration holds. The probe asks the program once, during set-up, how it
routed the reference sample and what every part of its forward pass handed on
(``deepseek_v3.stages``), and leaves on the job and in the configuration dict
what ``train_laguna``'s leaves (``routing_counts``, ``held_rows``,
``config["probe"]``; ``program_choice`` and ``program_stream`` on the sample,
the stream as the program's own bfloat16), for the same readers and for
``reference/deepseek_v3.py``; the outputs it returns are on the host (1.6 GB
at the cell's size, which the reference needs on the device).

**The selection biases start at rest**, as ``train_laguna``'s do and for its
reason: where the configuration has ``router_bias_settle``, ``init_fn``
returns the seed's weights with every router's selection bias moved by
``moe.bias_step`` until the load of a few sequences of the cell's own law is
even over the 128 experts (``settled``). The ids are Zipf, a tenth of the
positions are one id, and a router drawn from the seed sends that id's rows to
6 experts of its own choosing: the share of the assignments that falls on the
16 held experts is the seed's accident before the bias has moved (12.5% at
par), the rows of the expert layer follow it, and at the rule's own 0.001 a
step the bias needs hundreds of steps to even it out. The cell times a router
at par, so the steps the rule would need are taken here, during set-up, on
the scores of one forward pass a round; the step itself is the model's.
"""

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.runners import train_step
from chipbench.runners.train_lm import Job
from paddle_tpu import optimizer as pt_optimizer
from paddle_tpu.models import blocks, deepseek_v3
from paddle_tpu.parallel import mesh as mesh_mod
from paddle_tpu.parallel import moe


def model_config(config):
    """The program's DeepseekV3Config of a configuration file, every width
    as the file gives it; ``n_routed_experts`` there counts the experts
    held, and the rotation's pairing is the file's ``rope_interleave``."""
    if config["q_lora_rank"] is not None or config["rope_scaling"] is not None:
        raise ValueError("deepseek_v3.py has no query latent and no rotary "
                         "scaling")
    if config["num_key_value_heads"] != config["num_attention_heads"] \
            or config["attention_bias"] or config["tie_word_embeddings"]:
        raise ValueError("deepseek_v3.py has one expanded key/value head a "
                         "query head, no attention bias and an untied head")
    if config["scoring_func"] != "sigmoid" or not config["norm_topk_prob"] \
            or config["topk_method"] != "noaux_tc" \
            or config["n_group"] != 1 or config["topk_group"] != 1 \
            or config["moe_layer_freq"] != 1:
        raise ValueError("deepseek_v3.py has a renormalised sigmoid router "
                         "behind a selection bias, one group of routed "
                         "experts, and experts in every layer after the "
                         "dense ones")
    if config["qk_head_dim"] != config["qk_nope_head_dim"] \
            + config["qk_rope_head_dim"]:
        raise ValueError("a score head is its nope and rope channels")
    first, held = config["experts_held"]
    if held != config["n_routed_experts"]:
        raise ValueError("n_routed_experts counts the experts held here")
    return deepseek_v3.DeepseekV3Config(
        vocab_size=config["vocab_size"], hidden=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        rope_theta=float(config["rope_theta"]),
        rope_interleave=config["rope_interleave"],
        dense_width=config["intermediate_size"],
        first_dense=config["first_k_dense_replace"],
        expert_width=config["moe_intermediate_size"],
        shared_experts=config["n_shared_experts"],
        num_experts=config["router_width"],
        experts_per_token=config["num_experts_per_tok"],
        routed_scale=config["routed_scaling_factor"],
        bias_rate=config["router_bias_update_rate"],
        experts_held=(first, held), rms_eps=config["rms_norm_eps"])


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
    init_fn, step_fn = deepseek_v3.make_train_step(cfg, opt, mesh)
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
        parts, aux = deepseek_v3.stages(params, cfg, batch["input_ids"],
                                        mesh=mesh)
        parts32 = parts.astype(jnp.float32)
        norms = jnp.sqrt(jnp.sum(jnp.square(parts32), axis=(1, 2, 3),
                                 keepdims=True))
        return (deepseek_v3.lm_loss(params, cfg, batch, mesh=mesh), parts,
                parts32 / norms, aux["counts"], aux["choice"])

    def probe(params, batch):
        loss, parts, outputs, counts, choice = loss_parts_routing(params,
                                                                  batch)
        job.routing_counts = np.asarray(counts)
        job.held_rows = job.routing_counts[:, first:first + held].sum(axis=1)
        config["probe"] = {"held_rows": [int(n) for n in job.held_rows],
                           "tokens": int(batch["input_ids"].size)}
        if job.reference_sample is not None:
            job.reference_sample["program_choice"] = np.asarray(
                choice).reshape(choice.shape[0], *batch["input_ids"].shape,
                                -1)
            # on the host, in the program's own bfloat16: the sample
            # outlives the check, the device's memory is the step's
            job.reference_sample["program_stream"] = np.asarray(parts)
        # the parts over their norms go to the host too: the reference is
        # computed beside the step's weights and Adam's moments, and the
        # comparison brings both sides back
        return loss, np.asarray(outputs)

    settle = config.get("router_bias_settle")
    expert_layers = range(cfg.first_dense, cfg.num_layers)

    @jax.jit
    def router_scores(params, parts):
        """Every router's scores of the pass whose parts these are,
        [expert layers, T, E] float32: the router's own arithmetic on what
        the layer's mixer handed on (``deepseek_v3.stages``: part 1 + 2 l)."""
        def one(layer):
            lp = params["layers"][layer]
            h = blocks.rms_norm(parts[1 + 2 * layer], lp["ln2_g"],
                                cfg.rms_eps).reshape(-1, cfg.hidden)
            return moe.route(h.astype(jnp.float32), lp["router_w"],
                             cfg.experts_per_token, cfg.scoring)[1]
        return jnp.stack([one(layer) for layer in expert_layers])

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
            bias = at_rest(jnp.stack([params["layers"][layer]["router_bias"]
                                      for layer in expert_layers]), scores)
            layers = list(params["layers"])
            for row, layer in zip(bias, expert_layers):
                old = layers[layer]["router_bias"]
                if not isinstance(old, jax.core.Tracer):
                    row = jax.device_put(row, old.sharding)
                layers[layer] = dict(layers[layer], router_bias=row)
            params = dict(params, layers=layers)
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
