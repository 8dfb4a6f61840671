"""Pallas kernel layer: registry + kernel modules (docs/PERFORMANCE.md
"Pallas kernel layer").

Importing this package registers every built-in kernel:
fused_matmul / fused_matmul_int8 (matmul.py), embedding_scatter_add
(embedding.py), grouped_matmul (grouped_matmul.py), and — via
ops/pallas_kernels.py —
flash_attention / fused_layer_norm / softmax_cross_entropy."""

from paddle_tpu.ops.pallas.registry import (  # noqa: F401
    DEFAULT_VMEM_BUDGET, register_kernel, get_kernel, list_kernels,
    dispatch, get_body, selected_body, use_pallas, selection_mode,
    override, mesh_scope, platform, within_vmem_budget,
)
from paddle_tpu.ops.pallas import matmul as _matmul  # noqa: F401
from paddle_tpu.ops.pallas import embedding as _embedding  # noqa: F401
from paddle_tpu.ops.pallas import grouped_matmul as _grouped_matmul  # noqa: F401,E501
from paddle_tpu.ops.pallas.matmul import try_fused_matmul  # noqa: F401

# the three legacy entry points register themselves when
# ops/pallas_kernels.py executes; import it so `import paddle_tpu.ops.pallas`
# alone yields the complete registry. Guarded: pallas_kernels imports this
# package for the platform probe, so during ops/__init__'s own import of
# pallas_kernels this is a benign partially-initialized no-op.
try:
    from paddle_tpu.ops import pallas_kernels as _legacy  # noqa: F401
except ImportError:  # pragma: no cover - circular during package init
    pass

__all__ = [
    "register_kernel", "get_kernel", "list_kernels", "dispatch",
    "get_body", "selected_body", "use_pallas", "selection_mode",
    "override", "mesh_scope", "platform", "try_fused_matmul",
    "within_vmem_budget", "DEFAULT_VMEM_BUDGET",
]
