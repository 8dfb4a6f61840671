"""Runner for training jobs through a functional trainer
(``paddle_tpu.models.<family>.make_train_step``).

``build(config, traffic, devices)`` returns a :class:`Job`: the trainer's own
``init_fn`` and ``step_fn`` on the mesh the traffic file names, a pool of host
batches drawn from the seed, and the probe the reference is compared with.
The harness (``chipbench/run.py``) drives it and knows nothing about models.

A family is one entry of ``FAMILIES`` below. A runner for another kind of job
(serving, decoding) is a file of its own beside this one.
"""

import dataclasses

import jax
import numpy as np

from paddle_tpu import optimizer as pt_optimizer
from paddle_tpu.models import bert, transformer
from paddle_tpu.parallel import mesh as mesh_mod


@dataclasses.dataclass
class Job:
    mesh: object
    optimizer: object
    init_fn: object            # rng -> (params, opt_state), on the mesh
    step_fn: object            # (params, opt_state, host batch) -> (loss, ..)
    jitted: object             # the jitted step inside step_fn
    place: object              # host batch -> batch on the mesh
    draw_batch: object         # (numpy RandomState, rows) -> host batch
    probe: object              # (params, placed batch) -> (loss, outputs)
    batch: int
    tokens_per_step: int
    pool_batches: int
    sample_sequences: int

    def pool(self, seed):
        """The host batches the window cycles through, from the seed."""
        rs = np.random.RandomState(seed % 2**32)
        return [self.draw_batch(rs, self.batch)
                for _ in range(self.pool_batches)]

    def sample(self, seed):
        """The few sequences the reference is run on (their own stream)."""
        return self.draw_batch(
            np.random.RandomState((seed + 1_000_003) % 2**32),
            self.sample_sequences)

    def abstract_args(self):
        """ShapeDtypeStructs of (params, opt_state, batch), shardings
        included: what ``jitted.lower`` needs, with no array made."""
        params, opt_state = jax.eval_shape(self.init_fn,
                                           jax.random.PRNGKey(0))
        batch = jax.eval_shape(self.place,
                               self.draw_batch(np.random.RandomState(0),
                                               self.batch))
        return params, opt_state, batch


def _bert(config, traffic, mesh, opt):
    seq = int(traffic["seq_len"])
    preds = int(traffic["max_predictions"])
    prog = config["program"]
    cfg = bert.bert_base(
        vocab_size=config["vocab_size"], hidden=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        intermediate=config["intermediate_size"],
        max_seq=max(config["max_position_embeddings"], seq),
        type_vocab=config["type_vocab_size"], remat=prog["remat"],
        softmax_dtype=prog["softmax_dtype"],
        attention_impl=prog["attention_impl"])
    init_fn, step_fn = bert.make_train_step(
        cfg, opt, mesh, steps_per_call=traffic["steps_per_call"])

    def draw_batch(rs, rows):
        ids = rs.randint(0, cfg.vocab_size, (rows, seq), dtype=np.int32)
        pos = np.argsort(rs.random_sample((rows, seq)), axis=1)[:, :preds]
        return {
            "input_ids": ids,
            "token_type_ids": np.zeros_like(ids),
            "attention_mask": np.ones_like(ids),
            "masked_positions": np.sort(pos, axis=1).astype(np.int32),
            "masked_labels": rs.randint(0, cfg.vocab_size, (rows, preds),
                                        dtype=np.int32),
            "masked_weights": np.ones((rows, preds), np.float32),
        }

    @jax.jit
    def probe(params, batch):
        hidden = bert.forward(params, cfg, batch["input_ids"],
                              batch["token_type_ids"],
                              batch["attention_mask"], mesh=mesh)
        return bert.mlm_loss(params, cfg, batch, mesh=mesh), hidden

    return init_fn, step_fn, step_fn.jitted, step_fn.place, draw_batch, probe


def _transformer(config, traffic, mesh, opt):
    src_len, tgt_len = int(traffic["src_len"]), int(traffic["tgt_len"])
    cfg = transformer.TransformerConfig(
        src_vocab=config["src_vocab_size"],
        tgt_vocab=config["tgt_vocab_size"], hidden=config["d_model"],
        num_heads=config["num_attention_heads"], ffn=config["d_ff"],
        enc_layers=config["encoder_layers"],
        dec_layers=config["decoder_layers"],
        max_seq=max(src_len, tgt_len),
        label_smoothing=config["label_smoothing"],
        remat=config["program"]["remat"])
    init_fn, step_fn = transformer.make_train_step(cfg, opt, mesh)
    # this trainer does not hand out its jitted step or its placement (the
    # BERT one does, as .jitted and .place): take the jit from the closure,
    # and place as its step_fn does (rows over "data")
    jitted = [c.cell_contents for c in step_fn.__closure__
              if hasattr(c.cell_contents, "lower")]
    if len(jitted) != 1:
        raise RuntimeError("transformer.make_train_step no longer closes "
                           "over exactly one jitted function")
    dsh = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec(mesh_mod.DATA_AXIS))

    def place(batch):
        return {k: jax.device_put(v, dsh) for k, v in batch.items()}

    def draw_batch(rs, rows):
        src = rs.randint(2, cfg.src_vocab, (rows, src_len), dtype=np.int32)
        tgt = rs.randint(2, cfg.tgt_vocab, (rows, tgt_len), dtype=np.int32)
        bos = np.full((rows, 1), cfg.bos_id, np.int32)
        return {"src_ids": src, "src_mask": np.ones_like(src),
                "tgt_in": np.concatenate([bos, tgt[:, :-1]], axis=1),
                "tgt_out": tgt, "tgt_mask": np.ones_like(tgt)}

    @jax.jit
    def probe(params, batch):
        logits = transformer.forward(params, cfg, batch["src_ids"],
                                     batch["tgt_in"], batch["src_mask"],
                                     batch["tgt_mask"])
        return transformer.nmt_loss(params, cfg, batch), logits

    return init_fn, step_fn, jitted[0], place, draw_batch, probe


FAMILIES = {"bert": _bert, "transformer": _transformer}

#: what one "token" of the rate counts, by the traffic file's "token"
TOKENS = {
    "input_positions": lambda t: t["batch"] * t["seq_len"],
    "target_positions": lambda t: t["batch"] * t["tgt_len"],
}


def build(config, traffic, devices):
    axes = traffic["mesh"]
    mesh = mesh_mod.make_mesh(mesh_mod.MeshConfig(**axes), devices=devices)
    if mesh.size != len(devices):
        raise ValueError(f"mesh {axes} wants {mesh.size} devices, the cell "
                         f"has {len(devices)}")
    if traffic["batch"] % mesh.shape[mesh_mod.DATA_AXIS]:
        raise ValueError("the batch does not divide over the data axis")
    o = dict(config["optimizer"])
    opt = getattr(pt_optimizer, o.pop("name"))(**o)
    init_fn, step_fn, jitted, place, draw_batch, probe = \
        FAMILIES[config["family"]](config, traffic, mesh, opt)
    return Job(mesh=mesh, optimizer=opt, init_fn=init_fn, step_fn=step_fn,
               jitted=jitted, place=place, draw_batch=draw_batch,
               probe=probe, batch=traffic["batch"],
               tokens_per_step=TOKENS[traffic["token"]](traffic),
               pool_batches=traffic["pool_batches"],
               sample_sequences=traffic["sample_sequences"])
