"""Runner for next-token language-model training through
``paddle_tpu.models.kimi_linear.make_train_step``.

``build(config, traffic, devices)`` returns the :class:`Job` of
``runners/train_lm.py`` (imported from there), so the harness drives it
unchanged. Token ids follow a Zipf law (the traffic file's
``zipf_exponent``) over the slice of the vocabulary the configuration
holds: ``seq_len + 1`` ids a row, inputs the first ``seq_len``, labels the
last. The probe also asks the program, once, during set-up, how it routed
the reference sample (the aux terms of ``kimi_linear.stages``, the counts
``kimi_linear.routing_stats`` returns, from the pass whose states are
compared) and leaves the host numbers where they are read without running
anything: the assignments per expert over all the router's experts on the
job as ``job.routing_counts`` [expert layers, experts], how many of them
fell on the experts held here as ``job.held_rows`` [expert layers] (the
reader of ``moe_held_rows_per_expert``) and, with the sample's tokens, in
the configuration dict under ``"probe"`` (``flops/kimi_linear.py`` counts the
held assignments as the probe counted them; it is given the dict and not the
job), and, on the sample itself, for the reference: the experts chosen for
each token as ``program_choice`` [expert layers, B, S, k], which it checks
against its own scores before it computes with them, and what every part of
the program's forward pass handed on as ``program_stream`` [2 layers + 2, B,
S, H] (``kimi_linear.stages``), from which it computes each part on the
program's own input (``reference/kimi_linear.py`` says why). The outputs the
probe returns are those parts, each over its norm.
"""

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.runners import train_step
from chipbench.runners.train_lm import Job
from paddle_tpu import optimizer as pt_optimizer
from paddle_tpu.models import kimi_linear
from paddle_tpu.parallel import mesh as mesh_mod


def model_config(config, traffic):
    """The program's KimiLinearConfig of a configuration file, every width
    as the file gives it; ``num_experts`` there counts the experts held."""
    linear = config["linear_attn_config"]
    if config["num_key_value_heads"] != config["num_attention_heads"]:
        raise ValueError("kimi_linear.py has one key/value head a query head")
    if config["num_shared_experts"] != 1 or config["num_expert_group"] != 1:
        raise ValueError("kimi_linear.py has one shared expert and one "
                         "group of routed experts")
    first, held = config["experts_held"]
    if held != config["num_experts"]:
        raise ValueError("num_experts counts the experts held here")
    return kimi_linear.KimiLinearConfig(
        vocab_size=config["vocab_size"], hidden=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        kda_layers=tuple(linear["kda_layers"]),
        full_attn_layers=tuple(linear["full_attn_layers"]),
        kda_heads=linear["num_heads"], kda_head_dim=linear["head_dim"],
        conv_size=linear["short_conv_kernel_size"],
        num_heads=config["num_attention_heads"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        dense_width=config["intermediate_size"],
        first_dense=config["first_k_dense_replace"],
        expert_width=config["moe_intermediate_size"],
        num_experts=config["router_width"],
        experts_per_token=config["num_experts_per_token"],
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
    cfg = model_config(config, traffic)
    init_fn, step_fn = kimi_linear.make_train_step(cfg, opt, mesh)
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
        parts, aux = kimi_linear.stages(params, cfg, batch["input_ids"],
                                        mesh=mesh)
        parts = parts.astype(jnp.float32)
        norms = jnp.sqrt(jnp.sum(jnp.square(parts), axis=(1, 2, 3),
                                 keepdims=True))
        return (kimi_linear.lm_loss(params, cfg, batch, mesh=mesh), parts,
                parts / norms, aux["counts"], aux["choice"])

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
            # on the host: the sample outlives the check, the device's
            # memory is the step's
            job.reference_sample["program_stream"] = np.asarray(parts)
        return loss, outputs

    job = Job(
        mesh=mesh, optimizer=opt, init_fn=init_fn, step_fn=step_fn,
        jitted=step_fn.jitted, place=step_fn.place, draw_batch=draw_batch,
        probe=probe, batch=traffic["batch"],
        tokens_per_step=train_step.TOKENS[traffic["token"]](traffic),
        pool_batches=traffic["pool_batches"],
        sample_sequences=traffic["sample_sequences"])
    job.held_rows = None
    return job
