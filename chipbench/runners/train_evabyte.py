"""Runner for multi-byte language-model training through
``paddle_tpu.models.evabyte.make_train_step``.

``build(config, traffic, devices)`` returns the :class:`Job` of
``runners/train_lm.py``, as the other decoders' runners do and with their
traffic: ``seq_len + 1`` Zipf ids a row over the configuration's vocabulary
(all 320 bytes and specials: nothing is sliced). There is no router, so no
counts, no settled biases and nothing fitted to the cell.

The probe asks the program once, during set-up, what every part of its
forward pass handed on for the reference sample (``evabyte.stages``: the
embedding, the float32 stream after each layer's mixer and after its
feed-forward, the final normed hidden states: ``2 layers + 2`` parts), the
eight heads' float32 logits and the eight cross-entropies, and leaves them
on the sample for ``reference/evabyte.py`` (``program_stream`` a list of host
arrays, ``program_logits``) and on the job (``head_losses``). The outputs it
returns are those parts on the host, each over the reference file's own
``norms`` of the program's parts (a stream part over the norm of its update),
as one flat array of jax's CPU device: the cell's weights and Adam moments
are 9.2 GiB of the chip's 15.75, and eleven parts of [16384, 4096] in
float32 are 2.8 GB a side of the comparison.

It also leaves the counter ``job.eva_tiles_visited_pct``: the share of a
causal call's score tiles the EVA kernels visit at this sequence length,
which the op computes from the bounds its kernels loop over
(``ops/pallas/eva.eva_tiles_visited_pct``).
"""

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.evabyte import norms, over_norms
from chipbench.runners import train_step
from chipbench.runners.train_lm import Job
from paddle_tpu import optimizer as pt_optimizer
from paddle_tpu.models import evabyte
from paddle_tpu.ops.pallas.eva import eva_tiles_visited_pct
from paddle_tpu.parallel import mesh as mesh_mod


def off_the_chip(array):
    """A host array as one of jax's CPU device, so that a jitted function
    of it runs there; the host array itself where jax has no such backend."""
    try:
        return jax.device_put(array, jax.devices("cpu")[0])
    except RuntimeError:
        return array


def model_config(config, traffic):
    """The program's EvaByteConfig of a configuration file, every width as
    the file gives it."""
    if config["model_type"] != "evabyte" or config["attention_class"] != "eva" \
            or config["attention_bias"] or config["tie_word_embeddings"] \
            or config["hidden_act"] != "silu" \
            or config["rope_scaling"] is not None:
        raise ValueError("evabyte.py has EVA attention with no bias, plain "
                         "rotary positions, a SiLU-gated feed-forward and an "
                         "untied head")
    if config["num_key_value_heads"] != config["num_attention_heads"]:
        raise ValueError("evabyte.py has one key/value head a query head")
    if not (config["norm_add_unit_offset"] and config["fp32_skip_add"]
            and config["fp32_logits"] and config["mixedp_attn"]):
        raise ValueError("evabyte.py keeps a norm's gain as 1 + w, the "
                         "residual stream and the logits in float32 and the "
                         "attention's operands in bfloat16")
    if config["window_size"] % config["chunk_size"]:
        raise ValueError("a window is whole chunks")
    return evabyte.EvaByteConfig(
        vocab_size=config["vocab_size"], hidden=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        head_dim=config["hidden_size"] // config["num_attention_heads"],
        ffn_width=config["intermediate_size"],
        window=config["window_size"], chunk=config["chunk_size"],
        pred_heads=config["num_pred_heads"],
        max_seq=max(config["max_position_embeddings"], traffic["seq_len"]),
        rope_theta=float(config["rope_theta"]),
        rms_eps=config["rms_norm_eps"], init_std=config["init_std"])


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
    init_fn, step_fn = evabyte.make_train_step(cfg, opt, mesh)
    seq = int(traffic["seq_len"])
    law = 1.0 / np.arange(1, cfg.vocab_size + 1) ** traffic["zipf_exponent"]
    law /= law.sum()
    parts = 2 * cfg.num_layers + 2

    def draw_batch(rs, rows):
        ids = rs.choice(cfg.vocab_size, size=(rows, seq + 1),
                        p=law).astype(np.int32)
        return {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}

    @jax.jit
    def loss_parts_logits(params, batch):
        # the parts a result each, not one stacked array: at the cell's size
        # they are 256 MiB each beside the step's state
        stream, _ = evabyte.stages(params, cfg, batch["input_ids"],
                                   mesh=mesh)
        # the loss is the mean of the heads' (``lm_trainer._loss_and_counts``)
        losses = evabyte.head_losses(params, cfg, batch, mesh=mesh)
        logits = evabyte.DECODER.logits(params, stream[-1].astype(cfg.dtype))
        return (jnp.mean(losses), losses,
                tuple(stream[i] for i in range(parts)), logits)

    def probe(params, batch):
        loss, losses, stream, logits = loss_parts_logits(params, batch)
        job.head_losses = np.asarray(losses)
        # on the host: the sample outlives the check, the device's memory is
        # the step's
        stream = [np.asarray(part, np.float32) for part in stream]
        logits = np.asarray(logits)
        if job.reference_sample is not None:
            job.reference_sample["program_stream"] = stream
            job.reference_sample["program_logits"] = logits
        handed = stream + [logits.reshape(*logits.shape[:2],
                                          cfg.pred_heads, -1)]
        return loss, off_the_chip(over_norms(handed, norms(handed)))

    job = Job(
        mesh=mesh, optimizer=opt, init_fn=init_fn, step_fn=step_fn,
        jitted=step_fn.jitted, place=step_fn.place, draw_batch=draw_batch,
        probe=probe, batch=traffic["batch"],
        tokens_per_step=train_step.TOKENS[traffic["token"]](traffic),
        pool_batches=traffic["pool_batches"],
        sample_sequences=traffic["sample_sequences"])
    job.head_losses = None
    job.eva_tiles_visited_pct = eva_tiles_visited_pct(seq, cfg.window,
                                                      cfg.chunk)
    return job
