"""Pallas kernel layer: registry + kernel modules (docs/PERFORMANCE.md
"Pallas kernel layer").

Importing this package registers every built-in kernel, one module each:
fused_matmul / fused_matmul_int8 (matmul.py), embedding_scatter_add
(embedding.py), grouped_matmul (grouped_matmul.py), flash_attention
(flash_attention.py), fused_layer_norm (layer_norm.py),
softmax_cross_entropy (softmax_xent.py), kda_chunked (kda.py; its entry
point is ``paddle_tpu.ops.kda.kda_chunked``, beside the reference body and
the recurrence it stands for) and the two passes around it, short_conv_norm
and gated_head_norm (delta_glue.py), gated_short_conv (gated_conv.py),
the LFM2 family's double-gated convolution, and moe_combine
(moe_combine.py), the way back of an expert layer that holds a share of the
experts, and ssd (ssd.py; its entry point is
``paddle_tpu.ops.ssd.ssd_chunked``, beside the reference body and the
recurrence it stands for), Mamba-2's chunked state-space scan, and
eva_attention (eva.py; its entry point is
``paddle_tpu.ops.eva.eva_attention``, beside the reference body and the
chunk summaries), EvaByte's aggregation over a window's tokens and the
earlier windows' summaries. Of
moe_combine: a pass's rows, each times its float32 weight, summed into their
tokens' rows. Its reference body is XLA's scatter-add (the CPU, and any mesh
of more than one device); its Pallas body puts the rows in token order and
sums them on the MXU, for ``y`` [T, D] float32 with D in whole lane tiles
and T in whole sublane tiles (any other shape: the reference body). The
other entry points the models call
are names of this package; ``flash_attention`` and ``grouped_matmul`` here
are therefore the functions, not the modules of the same name (import a
module's own names with ``from paddle_tpu.ops.pallas.<module> import ...``)."""

from paddle_tpu.ops.pallas.registry import (  # noqa: F401
    DEFAULT_VMEM_BUDGET, register_kernel, get_kernel, list_kernels,
    dispatch, get_body, selected_body, use_pallas, selection_mode,
    override, mesh_scope, platform, within_vmem_budget,
)
from paddle_tpu.ops.pallas.delta_glue import gated_head_norm, short_conv_norm
from paddle_tpu.ops.pallas.gated_conv import gated_short_conv
from paddle_tpu.ops.pallas import embedding as _embedding  # noqa: F401
from paddle_tpu.ops.pallas import eva as _eva  # noqa: F401
from paddle_tpu.ops.pallas.flash_attention import flash_attention
from paddle_tpu.ops.pallas.grouped_matmul import grouped_matmul
from paddle_tpu.ops.pallas import kda as _kda  # noqa: F401
from paddle_tpu.ops.pallas.layer_norm import fused_layer_norm
from paddle_tpu.ops.pallas.moe_combine import moe_combine
from paddle_tpu.ops.pallas.matmul import try_fused_matmul
from paddle_tpu.ops.pallas.softmax_xent import softmax_cross_entropy
from paddle_tpu.ops.pallas import ssd as _ssd  # noqa: F401

__all__ = [
    "register_kernel", "get_kernel", "list_kernels", "dispatch",
    "get_body", "selected_body", "use_pallas", "selection_mode",
    "override", "mesh_scope", "platform", "try_fused_matmul",
    "within_vmem_budget", "DEFAULT_VMEM_BUDGET",
    "flash_attention", "fused_layer_norm", "softmax_cross_entropy",
    "grouped_matmul", "short_conv_norm", "gated_head_norm",
    "gated_short_conv", "moe_combine",
]
