"""Functional op library.

TPU-native analog of paddle/fluid/operators (475 REGISTER_OPERATOR sites,
ref: SURVEY §2.4). Ops here are pure functions over jax arrays that lower
to XLA HLO; there is no (place × dtype × layout) kernel registry — XLA's
compiler plays that role (ref: framework/operator.cc:986 ChooseKernel).
Gradients come from JAX autodiff instead of per-op GradOpDescMakers
(ref: framework/grad_op_desc_maker.h).

Naming follows the reference op names so `fluid.layers.*` parity is a thin
re-export (see paddle_tpu/layers.py).
"""

from paddle_tpu.ops.math import *            # noqa: F401,F403
from paddle_tpu.ops.activation import *      # noqa: F401,F403
from paddle_tpu.ops.nn import *              # noqa: F401,F403
from paddle_tpu.ops.loss import *            # noqa: F401,F403
from paddle_tpu.ops.reduce import *          # noqa: F401,F403
from paddle_tpu.ops.tensor_ops import *      # noqa: F401,F403
from paddle_tpu.ops.sequence import *        # noqa: F401,F403
from paddle_tpu.ops.random_ops import *      # noqa: F401,F403
from paddle_tpu.ops.control_flow import *    # noqa: F401,F403
from paddle_tpu.ops.metric_ops import *      # noqa: F401,F403
from paddle_tpu.ops.rnn import *             # noqa: F401,F403
from paddle_tpu.ops.crf import *             # noqa: F401,F403
from paddle_tpu.ops.ctc import *             # noqa: F401,F403
from paddle_tpu.ops.detection import *       # noqa: F401,F403
from paddle_tpu.ops.quantize import *        # noqa: F401,F403
from paddle_tpu.ops.misc import *            # noqa: F401,F403
from paddle_tpu.ops.aliases import *         # noqa: F401,F403
from paddle_tpu.ops.tensor_array import *    # noqa: F401,F403
from paddle_tpu.ops.selected_rows import *   # noqa: F401,F403
from paddle_tpu.ops import pallas            # noqa: F401  (package: perf
# primitives — flash_attention, fused_layer_norm, softmax_cross_entropy,
# grouped_matmul — not part of the fluid.layers parity surface)
