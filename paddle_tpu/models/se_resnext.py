"""SE-ResNeXt — the reference's distributed-test flagship vision model.

Parity targets: python/paddle/fluid/tests/unittests/dist_se_resnext.py
(SE_ResNeXt model used by the TestDistBase family) and the SE-ResNeXt
configs in the reference's image-classification suites. TPU-native like
models/resnet.py: NHWC/HWIO layouts, bf16 compute with fp32 BN stats,
grouped (cardinality) 3x3 convs via feature_group_count, SE
squeeze-excite as two tiny MXU matmuls over the pooled vector.
"""

import dataclasses

import numpy as np

import jax
import jax.numpy as jnp

from paddle_tpu.models.resnet import (_bn, _bn_init, _conv,
                                      _conv_init, _maxpool,
                                      _merge_bn_stats)

__all__ = ["SEResNeXtConfig", "se_resnext50", "se_resnext_tiny",
           "init_params", "forward", "loss_fn", "make_train_step",
           "synthetic_batch"]


@dataclasses.dataclass(frozen=True)
class SEResNeXtConfig:
    num_classes: int = 1000
    image_size: int = 224
    cardinality: int = 32            # groups in the 3x3 conv
    group_width: int = 4             # channels per group at stage 1
    stage_depths: tuple = (3, 4, 6, 3)
    reduction: int = 16              # SE bottleneck ratio
    width: int = 64                  # stem channels
    dtype: object = jnp.bfloat16
    bn_momentum: float = 0.9
    bn_eps: float = 1e-5
    label_smoothing: float = 0.1


def se_resnext50(**kw):
    return SEResNeXtConfig(**kw)


def se_resnext_tiny(**kw):
    """Small config for tests/CI."""
    kw.setdefault("num_classes", 10)
    kw.setdefault("image_size", 32)
    kw.setdefault("cardinality", 4)
    kw.setdefault("group_width", 4)
    kw.setdefault("stage_depths", (1, 1))
    kw.setdefault("width", 16)
    return SEResNeXtConfig(**kw)


def _stage_channels(cfg):
    """Per-stage (group channels, output channels): ResNeXt doubles the
    grouped width each stage; expansion to 2x grouped width."""
    chans = []
    for s in range(len(cfg.stage_depths)):
        gw = cfg.cardinality * cfg.group_width * (2 ** s)
        chans.append((gw, gw * 2))
    return chans


def _fc_init(key, shape):
    fan_in = shape[0]
    return (jax.random.normal(key, shape)
            * np.sqrt(2.0 / fan_in)).astype(jnp.float32)


def init_params(rng, cfg):
    keys = iter(jax.random.split(rng, 4 + 8 * sum(cfg.stage_depths)))
    p = {"stem": {"w": _conv_init(next(keys), 7, 7, 3, cfg.width),
                  "bn": _bn_init(cfg.width)},
         "stages": [], "head": {}}
    cin = cfg.width
    for (gw, cout), depth in zip(_stage_channels(cfg), cfg.stage_depths):
        stage = []
        for bi in range(depth):
            blk = {
                "conv1": _conv_init(next(keys), 1, 1, cin, gw),
                "bn1": _bn_init(gw),
                # grouped 3x3: HWIO with I = gw/cardinality
                "conv2": _conv_init(next(keys), 3, 3,
                                    gw // cfg.cardinality, gw),
                "bn2": _bn_init(gw),
                "conv3": _conv_init(next(keys), 1, 1, gw, cout),
                "bn3": _bn_init(cout),
                "se_w1": _fc_init(next(keys),
                                  (cout, cout // cfg.reduction)),
                "se_b1": jnp.zeros((cout // cfg.reduction,), jnp.float32),
                "se_w2": _fc_init(next(keys),
                                  (cout // cfg.reduction, cout)),
                "se_b2": jnp.zeros((cout,), jnp.float32),
            }
            if bi == 0 and cin != cout:
                blk["proj"] = _conv_init(next(keys), 1, 1, cin, cout)
                blk["proj_bn"] = _bn_init(cout)
            stage.append(blk)
            cin = cout
        p["stages"].append(stage)
    p["head"]["w"] = _fc_init(next(keys), (cin, cfg.num_classes)) * 0.1
    p["head"]["b"] = jnp.zeros((cfg.num_classes,), jnp.float32)
    return p


def _group_conv(x, w, groups, stride=1):
    return jax.lax.conv_general_dilated(
        x, w.astype(x.dtype), window_strides=(stride, stride),
        padding="SAME", feature_group_count=groups,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _se(x, blk):
    """Squeeze-and-excite: pooled fp32 vector -> 2 fc -> sigmoid scale."""
    z = jnp.mean(x.astype(jnp.float32), axis=(1, 2))       # [B, C]
    z = jax.nn.relu(z @ blk["se_w1"] + blk["se_b1"])
    z = jax.nn.sigmoid(z @ blk["se_w2"] + blk["se_b2"])
    return x * z[:, None, None, :].astype(x.dtype)


def forward(params, cfg, images, train=True):
    """images [B, H, W, 3] -> (logits fp32, new_params)."""
    new = jax.tree.map(lambda v: v, params)

    def bn_apply(y, bn, path):
        y, upd = _bn(y, bn, train, cfg.bn_momentum, cfg.bn_eps)
        if upd is not None:
            node = new
            for k in path[:-1]:
                node = node[k]
            node[path[-1]] = upd
        return y

    x = images.astype(cfg.dtype)
    x = _conv(x, params["stem"]["w"], stride=2)
    x = jax.nn.relu(bn_apply(x, params["stem"]["bn"], ("stem", "bn")))
    x = _maxpool(x)
    for si, stage in enumerate(params["stages"]):
        for bi, blk in enumerate(stage):
            s = 2 if (bi == 0 and si > 0) else 1
            sc = x
            if "proj" in blk:
                sc = _conv(x, blk["proj"], stride=s)
                sc = bn_apply(sc, blk["proj_bn"],
                              ("stages", si, bi, "proj_bn"))
            else:
                # stage boundaries always change channels, so every
                # strided block has a proj (init_params invariant)
                assert s == 1
            y = jax.nn.relu(bn_apply(_conv(x, blk["conv1"]), blk["bn1"],
                                     ("stages", si, bi, "bn1")))
            y = jax.nn.relu(bn_apply(
                _group_conv(y, blk["conv2"], cfg.cardinality, stride=s),
                blk["bn2"], ("stages", si, bi, "bn2")))
            y = bn_apply(_conv(y, blk["conv3"]), blk["bn3"],
                         ("stages", si, bi, "bn3"))
            y = _se(y, blk)
            x = jax.nn.relu(y + sc)
    x = jnp.mean(x.astype(jnp.float32), axis=(1, 2))
    logits = x @ params["head"]["w"] + params["head"]["b"]
    return logits, (new if train else params)


def loss_fn(params, cfg, images, labels, train=True):
    logits, new = forward(params, cfg, images, train=train)
    n = cfg.num_classes
    eps = cfg.label_smoothing
    onehot = jax.nn.one_hot(labels, n, dtype=jnp.float32)
    soft = onehot * (1 - eps) + eps / n
    logp = jax.nn.log_softmax(logits)
    loss = -jnp.mean(jnp.sum(soft * logp, axis=-1))
    acc = jnp.mean((jnp.argmax(logits, -1) == labels).astype(jnp.float32))
    return loss, (acc, new)


def make_train_step(cfg, optimizer, mesh=None):
    """Mirrors resnet.make_train_step: data-parallel over the "data"
    axis; BN running stats are spliced in AFTER the optimizer update so
    regularizers/clipping never touch them."""
    import functools

    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddle_tpu.parallel.mesh import DATA_AXIS, get_mesh

    mesh = mesh or get_mesh()
    rep = NamedSharding(mesh, P())
    dsh = NamedSharding(mesh, P(DATA_AXIS))

    def init_fn(rng):
        params = jax.jit(functools.partial(init_params, cfg=cfg),
                         out_shardings=rep)(rng)
        opt_state = optimizer.init(params)
        opt_state = jax.device_put(opt_state, jax.tree.map(
            lambda _: rep, opt_state))
        return params, opt_state

    def step(params, opt_state, images, labels):
        (loss, (acc, new)), grads = jax.value_and_grad(
            lambda p: loss_fn(p, cfg, images, labels), has_aux=True)(
                params)
        new_params, new_opt = optimizer.apply_gradients(
            params, grads, opt_state)
        new_params = _merge_bn_stats(new_params, new)
        return loss, acc, new_params, new_opt

    jit_step = jax.jit(step, donate_argnums=(0, 1))

    def step_fn(params, opt_state, images, labels):
        images = jax.device_put(images, dsh)
        labels = jax.device_put(labels, dsh)
        return jit_step(params, opt_state, images, labels)

    return init_fn, step_fn


def synthetic_batch(cfg, batch_size, seed=0):
    from paddle_tpu.models import resnet as _rn
    return _rn.synthetic_batch(cfg, batch_size, seed=seed)
