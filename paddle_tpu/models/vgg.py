"""VGG-11/13/16/19 — parity with benchmark/fluid/models/vgg.py (ref) and
the fp16 benchmark tables (ref: paddle/contrib/float16/float16_benchmark.md).

NHWC + bf16, same conventions as models/resnet.py. BN variant matches the
reference's conv_block w/ batch_norm. One jitted train step.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from paddle_tpu.models.resnet import _bn, _bn_init, _conv, _conv_init, \
    _maxpool, _merge_bn_stats, synthetic_batch as _resnet_synthetic_batch
from paddle_tpu.parallel.mesh import DATA_AXIS, get_mesh

__all__ = ["VGGConfig", "vgg11", "vgg13", "vgg16", "vgg19", "init_params",
           "forward", "loss_fn", "make_train_step", "synthetic_batch"]

_PLANS = {
    11: (1, 1, 2, 2, 2),
    13: (2, 2, 2, 2, 2),
    16: (2, 2, 3, 3, 3),
    19: (2, 2, 4, 4, 4),
}
_CHANNELS = (64, 128, 256, 512, 512)


@dataclasses.dataclass(frozen=True)
class VGGConfig:
    depth: int = 16
    num_classes: int = 1000
    image_size: int = 224
    fc_dim: int = 4096
    batch_norm: bool = True
    dropout: float = 0.5
    dtype: object = jnp.bfloat16
    bn_momentum: float = 0.9
    bn_eps: float = 1e-5


def vgg11(**kw):
    return VGGConfig(depth=11, **kw)


def vgg13(**kw):
    return VGGConfig(depth=13, **kw)


def vgg16(**kw):
    return VGGConfig(depth=16, **kw)


def vgg19(**kw):
    return VGGConfig(depth=19, **kw)


def init_params(rng, cfg):
    n_convs = sum(_PLANS[cfg.depth])
    keys = iter(jax.random.split(rng, n_convs + 3))
    p = {"convs": [], "bns": []}
    cin = 3
    for reps, ch in zip(_PLANS[cfg.depth], _CHANNELS):
        for _ in range(reps):
            p["convs"].append(_conv_init(next(keys), 3, 3, cin, ch))
            p["bns"].append(_bn_init(ch))
            cin = ch
    # five SAME-padded stride-2 maxpools ceil-divide the spatial dims
    side = cfg.image_size
    for _ in range(5):
        side = -(-side // 2)
    feat = cin * side ** 2
    def fc(key, i, o):
        return {"w": (jax.random.normal(key, (i, o)) * np.sqrt(2.0 / i)
                      ).astype(jnp.float32), "b": jnp.zeros((o,), jnp.float32)}
    p["fc1"] = fc(next(keys), feat, cfg.fc_dim)
    p["fc2"] = fc(next(keys), cfg.fc_dim, cfg.fc_dim)
    p["head"] = fc(next(keys), cfg.fc_dim, cfg.num_classes)
    return p


def forward(params, cfg, images, train=True, rng=None):
    x = images.astype(cfg.dtype)
    new = jax.tree.map(lambda v: v, params)
    i = 0
    for reps, _ in zip(_PLANS[cfg.depth], _CHANNELS):
        for _ in range(reps):
            x = _conv(x, params["convs"][i])
            if cfg.batch_norm:
                y, upd = _bn(x, params["bns"][i], train, cfg.bn_momentum,
                             cfg.bn_eps)
                if upd is not None:
                    new["bns"][i] = upd
                x = y
            x = jax.nn.relu(x)
            i += 1
        x = _maxpool(x, window=2, stride=2)
    x = x.reshape(x.shape[0], -1).astype(jnp.float32)

    def drop(x, key):
        if not train or cfg.dropout <= 0 or key is None:
            return x
        keep = 1.0 - cfg.dropout
        m = jax.random.bernoulli(key, keep, x.shape)
        return jnp.where(m, x / keep, 0.0)

    k1 = k2 = None
    if rng is not None:
        k1, k2 = jax.random.split(rng)
    x = drop(jax.nn.relu(x @ params["fc1"]["w"] + params["fc1"]["b"]), k1)
    x = drop(jax.nn.relu(x @ params["fc2"]["w"] + params["fc2"]["b"]), k2)
    logits = x @ params["head"]["w"] + params["head"]["b"]
    return logits, (new if train else params)


def loss_fn(params, cfg, images, labels, train=True, rng=None):
    logits, new_params = forward(params, cfg, images, train=train, rng=rng)
    logp = jax.nn.log_softmax(logits, axis=-1)
    loss = -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))
    return loss, (new_params, logits)


def make_train_step(cfg, optimizer, mesh=None, steps_per_call=1):
    """(init_fn, step_fn): data-parallel over the "data" axis.

    steps_per_call > 1 scans that many optimizer steps inside ONE
    jitted dispatch (models/resnet.py's train_from_dataset pattern —
    amortizes the per-dispatch host gap; see docs/PERFORMANCE.md).
    step_fn then accepts one batch (reused every inner step) or
    stacked batches with a leading [steps_per_call] axis; dropout rng
    splits per inner step so masks stay fresh inside the scan."""
    mesh = mesh or get_mesh()
    rep = NamedSharding(mesh, P())
    dsh = NamedSharding(mesh, P(DATA_AXIS))
    dsh_k = NamedSharding(mesh, P(None, DATA_AXIS))

    def init_fn(rng):
        params = jax.jit(functools.partial(init_params, cfg=cfg),
                         out_shardings=rep)(rng)
        opt_state = optimizer.init(params)
        opt_state = jax.device_put(opt_state,
                                   jax.tree.map(lambda _: rep, opt_state))
        return params, opt_state

    def step(params, opt_state, images, labels, rng):
        (loss, (bn_params, logits)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, cfg, images, labels, True, rng)
        new_params, new_opt = optimizer.apply_gradients(
            params, grads, opt_state)
        new_params = _merge_bn_stats(new_params, bn_params)
        acc = jnp.mean((jnp.argmax(logits, -1) == labels).astype(jnp.float32))
        return loss, acc, new_params, new_opt

    def multi(params, opt_state, images, labels, rng):
        stacked = images.ndim == 5      # [K, B, H, W, 3]

        def body(carry, xs):
            p, o, k = carry
            im, lb = xs if stacked else (images, labels)
            k, sub = jax.random.split(k)
            loss, acc, p, o = step(p, o, im, lb, sub)
            return (p, o, k), (loss, acc)

        (p, o, _), (losses, accs) = jax.lax.scan(
            body, (params, opt_state, rng),
            (images, labels) if stacked else None,
            length=None if stacked else steps_per_call)
        return losses[-1], accs[-1], p, o

    jit_step = jax.jit(step if steps_per_call == 1 else multi,
                       donate_argnums=(0, 1))

    step_counter = [0]

    def step_fn(params, opt_state, images, labels, rng=None):
        # fold the step count so default-rng callers still get a fresh
        # dropout mask every step
        if rng is None:
            rng = jax.random.fold_in(jax.random.PRNGKey(0), step_counter[0])
            step_counter[0] += 1
        stacked = np.ndim(images) == 5
        if stacked and np.shape(images)[0] != steps_per_call:
            raise ValueError(
                f"stacked batch leading axis {np.shape(images)[0]} != "
                f"steps_per_call {steps_per_call}")
        images = jax.device_put(images, dsh_k if stacked else dsh)
        labels = jax.device_put(labels, dsh_k if stacked else dsh)
        return jit_step(params, opt_state, images, labels, rng)

    return init_fn, step_fn


synthetic_batch = _resnet_synthetic_batch
