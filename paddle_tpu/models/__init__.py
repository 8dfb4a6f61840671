"""Model zoo.

Parity targets: the reference's benchmark models
(ref: benchmark/fluid/models/{mnist,resnet,vgg,stacked_dynamic_lstm,
machine_translation}.py), its distributed-test models
(dist_se_resnext.py -> se_resnext) and book examples (ref:
python/paddle/fluid/tests/book/). BERT/transformer is the flagship
(north-star config in BASELINE.json) — not in the reference's zoo but its
ERNIE/transformer tests (dist_transformer.py) set the shape.
"""

from paddle_tpu.models import (bert, blocks, deepfm, olmoe, resnet,
                               se_resnext, transformer, vgg)

__all__ = ["bert", "blocks", "deepfm", "olmoe", "resnet", "se_resnext",
           "transformer", "vgg"]
