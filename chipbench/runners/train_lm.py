"""Runner for next-token language-model training through
``paddle_tpu.models.olmoe.make_train_step``.

``build(config, traffic, devices)`` returns the same :class:`Job` as
``runners/train_step.py`` (imported from there), so the harness drives it
unchanged. Token ids follow a Zipf law (the traffic file's
``zipf_exponent``): ``seq_len + 1`` ids a row, inputs the first ``seq_len``,
labels the last. The probe also asks the program, once, during set-up, how
it routed the reference sample (``olmoe.routing_stats``) and leaves the
host numbers where they are read without running anything: the assignments
per expert on the job as ``job.routing_counts`` [layers, experts], for the
reader of ``moe_load_max_over_mean``, and the experts chosen for each token
on the sample itself as ``program_choice`` [layers, B, S, k], for the
reference, which checks every choice against its own probabilities before it
computes with them (``reference/olmoe.py`` says why).
"""

import dataclasses

import jax
import numpy as np

from chipbench.runners import train_step
from paddle_tpu import optimizer as pt_optimizer
from paddle_tpu.models import olmoe
from paddle_tpu.parallel import mesh as mesh_mod


@dataclasses.dataclass
class Job(train_step.Job):
    """A training job that remembers the sample the reference is run on."""
    reference_sample: object = None
    routing_counts: object = None

    def sample(self, seed):
        self.reference_sample = super().sample(seed)
        return self.reference_sample


def model_config(config, traffic):
    """The program's OlmoeConfig of a configuration file, every width as the
    file gives it."""
    if config["num_key_value_heads"] != config["num_attention_heads"]:
        raise ValueError("olmoe.py has one key/value head a query head")
    return olmoe.OlmoeConfig(
        vocab_size=config["vocab_size"], hidden=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        head_dim=config["head_dim"],
        expert_width=config["intermediate_size"],
        num_experts=config["num_experts"],
        experts_per_token=config["num_experts_per_tok"],
        max_seq=max(config["max_position_embeddings"], traffic["seq_len"]),
        rope_theta=float(config["rope_theta"]),
        rms_eps=config["rms_norm_eps"],
        balance_weight=config["router_aux_loss_coef"],
        z_weight=config["router_z_loss_coef"])


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
    init_fn, step_fn = olmoe.make_train_step(cfg, opt, mesh)
    seq = int(traffic["seq_len"])
    law = 1.0 / np.arange(1, cfg.vocab_size + 1) ** traffic["zipf_exponent"]
    law /= law.sum()

    def draw_batch(rs, rows):
        ids = rs.choice(cfg.vocab_size, size=(rows, seq + 1),
                        p=law).astype(np.int32)
        return {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}

    @jax.jit
    def loss_and_hidden(params, batch):
        return (olmoe.lm_loss(params, cfg, batch, mesh=mesh),
                olmoe.forward(params, cfg, batch["input_ids"], mesh=mesh))

    def probe(params, batch):
        job.routing_counts, choice = olmoe.routing_stats(
            params, cfg, batch, mesh=mesh, choices=True)
        if job.reference_sample is not None:
            job.reference_sample["program_choice"] = choice.reshape(
                cfg.num_layers, *batch["input_ids"].shape, -1)
        return loss_and_hidden(params, batch)

    job = Job(
        mesh=mesh, optimizer=opt, init_fn=init_fn, step_fn=step_fn,
        jitted=step_fn.jitted, place=step_fn.place, draw_batch=draw_batch,
        probe=probe, batch=traffic["batch"],
        tokens_per_step=train_step.TOKENS[traffic["token"]](traffic),
        pool_batches=traffic["pool_batches"],
        sample_sequences=traffic["sample_sequences"])
    return job
