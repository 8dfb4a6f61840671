"""Runner for next-token language-model training through
``paddle_tpu.models.qwen3_next.make_train_step``.

``build(config, traffic, devices)`` returns the :class:`Job` of
``runners/train_lm.py``, as ``runners/train_laguna.py`` does and with the
same traffic: ``seq_len + 1`` Zipf ids a row over the slice of the
vocabulary the configuration holds. The probe asks the program once, during
set-up, how it routed the reference sample and what every part of its forward
pass handed on (``qwen3_next.stages``), and leaves on the job and in the
configuration dict what ``train_laguna``'s leaves (``routing_counts``,
``held_rows``, ``config["probe"]``; ``program_choice`` and ``program_stream``
on the sample, the stream as the program's own bfloat16), for the same
readers and for ``reference/qwen3_next.py``; the outputs it returns are on the
host. The router has no selection bias, so nothing is settled before the
window: the share of the assignments the held experts take is the seed's
(``moe_e512_share_pct`` reports it).
"""

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.runners import train_step
from chipbench.runners.train_lm import Job
from paddle_tpu import optimizer as pt_optimizer
from paddle_tpu.models import qwen3_next
from paddle_tpu.parallel import mesh as mesh_mod


def model_config(config):
    """The program's Qwen3NextConfig of a configuration file, every width as
    the file gives it; ``num_experts`` there counts the experts held."""
    if config["tie_word_embeddings"] or not config["norm_topk_prob"] \
            or config["mlp_only_layers"] or config["decoder_sparse_step"] != 1 \
            or config["rope_scaling"] or config["use_sliding_window"] \
            or config["hidden_act"] != "silu":
        raise ValueError("qwen3_next.py has an untied head, renormalised "
                         "router weights, experts in every layer, plain "
                         "rotary positions, no window and SiLU")
    first, held = config["experts_held"]
    if held != config["num_experts"]:
        raise ValueError("num_experts counts the experts held here")
    return qwen3_next.Qwen3NextConfig(
        vocab_size=config["vocab_size"], hidden=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        full_attention_interval=config["full_attention_interval"],
        linear_key_heads=config["linear_num_key_heads"],
        linear_value_heads=config["linear_num_value_heads"],
        linear_key_dim=config["linear_key_head_dim"],
        linear_value_dim=config["linear_value_head_dim"],
        conv_size=config["linear_conv_kernel_dim"],
        num_heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        rotary_factor=config["partial_rotary_factor"],
        rope_theta=float(config["rope_theta"]),
        expert_width=config["moe_intermediate_size"],
        shared_width=config["shared_expert_intermediate_size"],
        num_experts=config["router_width"],
        experts_per_token=config["num_experts_per_tok"],
        balance_weight=config["router_aux_loss_coef"],
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
    init_fn, step_fn = qwen3_next.make_train_step(cfg, opt, mesh)
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
        parts, aux = qwen3_next.stages(params, cfg, batch["input_ids"],
                                       mesh=mesh)
        parts32 = parts.astype(jnp.float32)
        norms = jnp.sqrt(jnp.sum(jnp.square(parts32), axis=(1, 2, 3),
                                 keepdims=True))
        return (qwen3_next.lm_loss(params, cfg, batch, mesh=mesh), parts,
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

    job = Job(
        mesh=mesh, optimizer=opt, init_fn=init_fn, step_fn=step_fn,
        jitted=step_fn.jitted, place=step_fn.place, draw_batch=draw_batch,
        probe=probe, batch=traffic["batch"],
        tokens_per_step=train_step.TOKENS[traffic["token"]](traffic),
        pool_batches=traffic["pool_batches"],
        sample_sequences=traffic["sample_sequences"])
    job.held_rows = None
    return job
