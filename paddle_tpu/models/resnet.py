"""ResNet family (18/34/50/101/152 + CIFAR variants) — the image headline.

Parity target: benchmark/fluid/models/resnet.py (ref: BASELINE.json config 1,
layers built from fluid.layers.conv2d/batch_norm/pool2d — ref:
python/paddle/fluid/layers/nn.py conv2d/batch_norm) and the book test
`image_classification` (ref: python/paddle/fluid/tests/book/
test_image_classification.py).

TPU-first design notes:
- NHWC activations / HWIO weights: the native TPU conv layout (the
  reference is NCHW-cuDNN; layout is a free choice here, so pick the one
  the MXU tiles best);
- bf16 activations + conv compute, fp32 master params and BN statistics;
- batch norm in training computes batch stats with plain jnp.mean over the
  (possibly "data"-sharded) batch axis — under pjit GSPMD turns that into
  a cross-replica reduction, i.e. sync-BN for free (contrast ref:
  operators/sync_batch_norm_op.cu + build_strategy.h:102);
- one jitted train step = fwd+bwd+momentum update (no per-op loop, ref:
  framework/executor.cc:417);
- dp sharding over the "data" mesh axis only — ResNet-50 fits one chip;
  GSPMD inserts the gradient all-reduce (replaces
  details/all_reduce_op_handle.cc:86).
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from paddle_tpu.parallel.mesh import DATA_AXIS, get_mesh

__all__ = ["ResNetConfig", "resnet18", "resnet34", "resnet50", "resnet101",
           "resnet152", "resnet_cifar10", "init_params", "forward", "loss_fn",
           "make_train_step", "synthetic_batch", "flops_per_image"]

# (block fn, stage depths)
_DEPTHS = {
    18: ("basic", (2, 2, 2, 2)),
    34: ("basic", (3, 4, 6, 3)),
    50: ("bottleneck", (3, 4, 6, 3)),
    101: ("bottleneck", (3, 4, 23, 3)),
    152: ("bottleneck", (3, 8, 36, 3)),
}


@dataclasses.dataclass(frozen=True)  # hashable: jit-static
class ResNetConfig:
    depth: int = 50
    num_classes: int = 1000
    image_size: int = 224
    width: int = 64                  # stem channels
    cifar: bool = False              # 3x3 stem, no maxpool (ref resnet_cifar10)
    cifar_n: int = 3                 # blocks per stage in the CIFAR variant
    dtype: object = jnp.bfloat16
    bn_momentum: float = 0.9
    bn_eps: float = 1e-5
    label_smoothing: float = 0.1
    # HBM-traffic experiment (r5, VERDICT r4 #4): "block" wraps each
    # residual block in jax.checkpoint saving ONLY conv outputs + BN
    # statistics — backward recomputes the BN-apply/ReLU elementwise
    # chain instead of reading stored post-activation tensors, trading
    # (cheap, fusable) recompute FLOPs for stored-activation reads on
    # a model the roofline note shows is HBM-bound. Measured numbers
    # in BASELINE.md "ResNet-50 remat experiment".
    remat: str = "none"              # "none" | "block"

    def __post_init__(self):
        if self.remat not in ("none", "block"):
            raise ValueError(
                f"remat must be 'none' or 'block', got {self.remat!r}")

    @property
    def block(self):
        return _DEPTHS[self.depth][0]

    @property
    def stage_depths(self):
        return _DEPTHS[self.depth][1]


def resnet18(**kw):
    return ResNetConfig(depth=18, **kw)


def resnet34(**kw):
    return ResNetConfig(depth=34, **kw)


def resnet50(**kw):
    return ResNetConfig(depth=50, **kw)


def resnet101(**kw):
    return ResNetConfig(depth=101, **kw)


def resnet152(**kw):
    return ResNetConfig(depth=152, **kw)


def resnet_cifar10(depth=20, **kw):
    """CIFAR-10 ResNet (ref: benchmark/fluid/models/resnet.py cifar path).
    depth in {20, 32, 44, 56, 110}: 3 stages of n basic blocks, 16/32/64ch."""
    kw.setdefault("num_classes", 10)
    kw.setdefault("image_size", 32)
    kw.setdefault("width", 16)
    return ResNetConfig(depth=18, cifar=True, cifar_n=(depth - 2) // 6, **kw)


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------
def _conv_init(key, kh, kw, cin, cout):
    """He-normal fan-out (the reference's MSRA initializer,
    ref: python/paddle/fluid/initializer.py MSRAInitializer)."""
    std = np.sqrt(2.0 / (kh * kw * cout))
    return (std * jax.random.normal(key, (kh, kw, cin, cout))
            ).astype(jnp.float32)


def _bn_init(c):
    return {"g": jnp.ones((c,), jnp.float32),
            "b": jnp.zeros((c,), jnp.float32),
            "mean": jnp.zeros((c,), jnp.float32),
            "var": jnp.ones((c,), jnp.float32)}


def _stages(cfg):
    """Yields (stage_channels, depth, stride) per stage."""
    if cfg.cifar:
        n = cfg.cifar_n
        return [(16, n, 1), (32, n, 2), (64, n, 2)]
    w = cfg.width
    return [(w, cfg.stage_depths[0], 1), (2 * w, cfg.stage_depths[1], 2),
            (4 * w, cfg.stage_depths[2], 2), (8 * w, cfg.stage_depths[3], 2)]


def _expansion(cfg):
    return 4 if (cfg.block == "bottleneck" and not cfg.cifar) else 1


def init_params(rng, cfg):
    keys = iter(jax.random.split(rng, 4 + 4 * sum(d for _, d, _ in
                                                  _stages(cfg))))
    exp = _expansion(cfg)
    stem_k = 3 if cfg.cifar else 7
    p = {"stem": {"w": _conv_init(next(keys), stem_k, stem_k, 3, cfg.width),
                  "bn": _bn_init(cfg.width)},
         "stages": []}
    cin = cfg.width
    for ch, depth, stride in _stages(cfg):
        stage = []
        for i in range(depth):
            s = stride if i == 0 else 1
            blk = {}
            if cfg.block == "bottleneck" and not cfg.cifar:
                blk["conv1"] = _conv_init(next(keys), 1, 1, cin, ch)
                blk["bn1"] = _bn_init(ch)
                blk["conv2"] = _conv_init(next(keys), 3, 3, ch, ch)
                blk["bn2"] = _bn_init(ch)
                blk["conv3"] = _conv_init(next(keys), 1, 1, ch, ch * exp)
                blk["bn3"] = _bn_init(ch * exp)
            else:
                blk["conv1"] = _conv_init(next(keys), 3, 3, cin, ch)
                blk["bn1"] = _bn_init(ch)
                blk["conv2"] = _conv_init(next(keys), 3, 3, ch, ch * exp)
                blk["bn2"] = _bn_init(ch * exp)
            if s != 1 or cin != ch * exp:
                blk["proj"] = _conv_init(next(keys), 1, 1, cin, ch * exp)
                blk["proj_bn"] = _bn_init(ch * exp)
            stage.append(blk)
            cin = ch * exp
        p["stages"].append(stage)
    p["head"] = {
        "w": (jax.random.normal(next(keys), (cin, cfg.num_classes))
              * np.sqrt(1.0 / cin)).astype(jnp.float32),
        "b": jnp.zeros((cfg.num_classes,), jnp.float32),
    }
    return p


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
_DN = ("NHWC", "HWIO", "NHWC")


def _conv(x, w, stride=1, dilation=1):
    return jax.lax.conv_general_dilated(
        x, w.astype(x.dtype), window_strides=(stride, stride),
        padding="SAME", rhs_dilation=(dilation, dilation),
        dimension_numbers=_DN)


def _bn(x, bn, train, momentum, eps):
    """Returns (y, new_stats|None). Batch stats in fp32; under pjit the
    batch-axis mean is a global (cross-replica) mean — sync BN."""
    from jax.ad_checkpoint import checkpoint_name
    x32 = x.astype(jnp.float32)
    if train:
        mean = jnp.mean(x32, axis=(0, 1, 2))
        var = jnp.mean(jnp.square(x32), axis=(0, 1, 2)) - jnp.square(mean)
        # tiny per-channel vectors: naming them keeps the remat-block
        # policy from re-reducing the whole activation in backward
        mean = checkpoint_name(mean, "bn_stat")
        var = checkpoint_name(var, "bn_stat")
        new = {"g": bn["g"], "b": bn["b"],
               "mean": momentum * bn["mean"] + (1 - momentum) * mean,
               "var": momentum * bn["var"] + (1 - momentum) * var}
    else:
        mean, var = bn["mean"], bn["var"]
        new = None
    inv = jax.lax.rsqrt(var + eps) * bn["g"]
    y = (x32 - mean) * inv + bn["b"]
    return y.astype(x.dtype), new


def _maxpool(x, window=3, stride=2):
    # -inf init (not finfo.min): lax only recognizes the max monoid — and
    # hence its reverse-mode rule — with the identity element.
    # An r3 experiment replaced this with a 9-way elementwise max over
    # strided slices (backward = fused compare-selects, no
    # select-and-scatter): MEASURED WORSE on v5e — 2,158 img/s / MFU
    # 0.254 vs 2,549 / 0.300 for reduce_window on back-to-back bs=256
    # runs. The strided slice reads + padded copy cost more than the
    # select-and-scatter they remove; keep reduce_window.
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max,
        (1, window, window, 1), (1, stride, stride, 1), "SAME")


def _block_fwd(x, blk, cfg, stride, train):
    """One residual block, PURE: returns (out, {bn_key: new_stats}).
    Purity (updates as return values, not closure mutation) is what
    lets cfg.remat wrap it in jax.checkpoint."""
    from jax.ad_checkpoint import checkpoint_name

    def conv(h, w, s=1):
        return checkpoint_name(_conv(h, w, stride=s), "conv_out")

    upds = {}

    def bn_apply(h, bn, key):
        y, upd = _bn(h, bn, train, cfg.bn_momentum, cfg.bn_eps)
        if upd is not None:
            upds[key] = upd
        return y

    sc = x
    if "proj" in blk:
        sc = bn_apply(conv(x, blk["proj"], stride), blk["proj_bn"],
                      "proj_bn")
    if "conv3" in blk:   # bottleneck
        y = jax.nn.relu(bn_apply(conv(x, blk["conv1"]), blk["bn1"],
                                 "bn1"))
        y = jax.nn.relu(bn_apply(conv(y, blk["conv2"], stride),
                                 blk["bn2"], "bn2"))
        y = bn_apply(conv(y, blk["conv3"]), blk["bn3"], "bn3")
    else:                # basic
        y = jax.nn.relu(bn_apply(conv(x, blk["conv1"], stride),
                                 blk["bn1"], "bn1"))
        y = bn_apply(conv(y, blk["conv2"]), blk["bn2"], "bn2")
    return jax.nn.relu(y + sc), upds


def forward(params, cfg, images, train=True):
    """images: [B, H, W, 3] float. Returns (logits fp32, new_params with
    updated BN stats when train else params)."""
    x = images.astype(cfg.dtype)
    new = jax.tree.map(lambda v: v, params)  # shallow-ish structural copy

    block_fn = _block_fwd
    if cfg.remat == "block" and train:
        # save only conv outputs + (tiny) BN stats; backward recomputes
        # the BN-apply/ReLU elementwise chain instead of reading stored
        # post-activation tensors — an HBM-traffic experiment on a
        # model the roofline shows is bandwidth-bound (BASELINE.md)
        block_fn = jax.checkpoint(
            _block_fwd, static_argnums=(2, 3, 4),
            policy=jax.checkpoint_policies.save_only_these_names(
                "conv_out", "bn_stat"))

    def bn_apply(x, bn, path):
        y, upd = _bn(x, bn, train, cfg.bn_momentum, cfg.bn_eps)
        if upd is not None:
            d = new
            for k in path[:-1]:
                d = d[k]
            d[path[-1]] = upd
        return y

    x = _conv(x, params["stem"]["w"], stride=1 if cfg.cifar else 2)
    x = jax.nn.relu(bn_apply(x, params["stem"]["bn"], ("stem", "bn")))
    if not cfg.cifar:
        x = _maxpool(x)
    for si, stage in enumerate(params["stages"]):
        _, _, stage_stride = _stages(cfg)[si]
        for bi, blk in enumerate(stage):
            s = stage_stride if bi == 0 else 1
            x, upds = block_fn(x, blk, cfg, s, train)
            for key, upd in upds.items():
                new["stages"][si][bi][key] = upd
    x = jnp.mean(x.astype(jnp.float32), axis=(1, 2))  # global avg pool
    logits = x @ params["head"]["w"] + params["head"]["b"]
    return logits, (new if train else params)


def loss_fn(params, cfg, images, labels, train=True):
    """Label-smoothed softmax CE (ref: operators/
    softmax_with_cross_entropy_op.cc + layers label_smooth). Returns
    (loss, (new_params, logits))."""
    logits, new_params = forward(params, cfg, images, train=train)
    logp = jax.nn.log_softmax(logits, axis=-1)
    eps = cfg.label_smoothing
    n = cfg.num_classes
    onehot = jax.nn.one_hot(labels, n, dtype=jnp.float32)
    soft = onehot * (1 - eps) + eps / n
    loss = -jnp.mean(jnp.sum(soft * logp, axis=-1))
    return loss, (new_params, logits)


# ---------------------------------------------------------------------------
# train step
# ---------------------------------------------------------------------------
def make_train_step(cfg, optimizer, mesh=None, steps_per_call=1):
    """(init_fn, step_fn): data-parallel over the "data" axis. BN stats are
    carried in params (non-grad leaves get their fwd-updated values).

    steps_per_call > 1 runs that many optimizer steps inside ONE jitted
    dispatch via lax.scan — the train_from_dataset pattern (ref:
    executor.py:927 runs the whole dataset per call, amortizing the
    host's per-dispatch gap). step_fn then accepts either one batch (reused every
    inner step — the benchmark's --use_fake_data shape) or stacked
    batches with a leading [steps_per_call] axis."""
    mesh = mesh or get_mesh()
    rep = NamedSharding(mesh, P())
    dsh = NamedSharding(mesh, P(DATA_AXIS))
    dsh_k = NamedSharding(mesh, P(None, DATA_AXIS))

    def init_fn(rng):
        params = jax.jit(functools.partial(init_params, cfg=cfg),
                         out_shardings=rep)(rng)
        opt_state = optimizer.init(params)
        opt_state = jax.device_put(opt_state, jax.tree.map(
            lambda _: rep, opt_state))
        return params, opt_state

    def step(params, opt_state, images, labels):
        (loss, (bn_params, logits)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, cfg, images, labels)
        new_params, new_opt = optimizer.apply_gradients(
            params, grads, opt_state)
        # splice updated BN running stats (they are not optimizer targets)
        new_params = _merge_bn_stats(new_params, bn_params)
        acc = jnp.mean((jnp.argmax(logits, -1) == labels).astype(jnp.float32))
        return loss, acc, new_params, new_opt

    def multi(params, opt_state, images, labels):
        stacked = images.ndim == 5  # [K, B, H, W, 3]

        def body(carry, xs):
            p, o = carry
            im, lb = xs if stacked else (images, labels)
            loss, acc, p, o = step(p, o, im, lb)
            return (p, o), (loss, acc)

        (p, o), (losses, accs) = jax.lax.scan(
            body, (params, opt_state),
            (images, labels) if stacked else None,
            length=None if stacked else steps_per_call)
        return losses[-1], accs[-1], p, o

    jit_step = jax.jit(step if steps_per_call == 1 else multi,
                       donate_argnums=(0, 1))

    def step_fn(params, opt_state, images, labels):
        stacked = np.ndim(images) == 5
        if stacked and np.shape(images)[0] != steps_per_call:
            raise ValueError(
                f"stacked batch leading axis {np.shape(images)[0]} != "
                f"steps_per_call {steps_per_call}")
        images = jax.device_put(images, dsh_k if stacked else dsh)
        labels = jax.device_put(labels, dsh_k if stacked else dsh)
        return jit_step(params, opt_state, images, labels)

    return init_fn, step_fn


def _merge_bn_stats(params, bn_params):
    """Take mean/var leaves from bn_params, everything else from params."""
    flat_p, treedef = jax.tree.flatten_with_path(params)
    flat_b = jax.tree.leaves(bn_params)

    def pick(item, bleaf):
        path, pleaf = item
        last = path[-1]
        key = getattr(last, "key", getattr(last, "idx", None))
        return bleaf if key in ("mean", "var") else pleaf

    leaves = [pick(it, b) for it, b in zip(flat_p, flat_b)]
    return jax.tree.unflatten(treedef, leaves)


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------
def synthetic_batch(cfg, batch_size, seed=0):
    rng = np.random.RandomState(seed)
    images = rng.rand(batch_size, cfg.image_size, cfg.image_size, 3) \
        .astype(np.float32)
    labels = rng.randint(0, cfg.num_classes, (batch_size,), dtype=np.int32)
    return images, labels


def flops_per_image(cfg):
    """Training FLOPs/image ≈ 3x forward conv FLOPs (analytic)."""
    fwd = 0
    size = cfg.image_size if cfg.cifar else cfg.image_size // 2
    stem_k = 3 if cfg.cifar else 7
    fwd += 2 * stem_k * stem_k * 3 * cfg.width * size * size
    if not cfg.cifar:
        size //= 2
    cin = cfg.width
    exp = _expansion(cfg)
    for ch, depth, stride in _stages(cfg):
        for i in range(depth):
            if i == 0 and stride == 2:
                size //= 2
            hw = size * size
            if cfg.block == "bottleneck" and not cfg.cifar:
                fwd += 2 * hw * (cin * ch + 9 * ch * ch + ch * ch * exp)
            else:
                fwd += 2 * hw * (9 * cin * ch + 9 * ch * ch * exp)
            if i == 0 and cin != ch * exp:
                fwd += 2 * hw * cin * ch * exp
            cin = ch * exp
    fwd += 2 * cin * cfg.num_classes
    return 3 * fwd
