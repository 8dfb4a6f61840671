"""paddle_tpu — a TPU-native deep learning framework.

A from-scratch rebuild of the capabilities of PaddlePaddle Fluid
(reference: /root/reference, Fluid 1.5 era) designed idiomatically for TPU:

- compute lowers to XLA through JAX; one compiled computation per training
  step instead of the reference's per-op interpreter loop
  (ref: paddle/fluid/framework/executor.cc:417 hot loop),
- SPMD parallelism over `jax.sharding.Mesh` with XLA collectives replacing
  ParallelExecutor + NCCL (ref: paddle/fluid/framework/parallel_executor.cc),
- ragged sequences via dense padding + segment metadata replacing LoD
  (ref: paddle/fluid/framework/lod_tensor.h),
- Pallas kernels for hot ops; a native C++ host data pipeline.

Public surface mirrors the reference's `paddle.fluid` so users can migrate:
``paddle_tpu.layers``, ``paddle_tpu.optimizer``, ``paddle_tpu.static``
(Program/Executor), eager by default (the reference's dygraph).
"""

import time as _time

_IMPORT_T0 = _time.perf_counter()       # the span startup/import begins

from paddle_tpu.core import dtypes
from paddle_tpu.core.dtypes import (
    float32, float64, float16, bfloat16, int8, int16, int32, int64, bool_,
    uint8,
)
from paddle_tpu.core.enforce import EnforceNotMet, enforce, enforce_eq
from paddle_tpu.core.flags import flags, get_flag, set_flags
from paddle_tpu.core.place import (
    CPUPlace, TPUPlace, Place, default_place, is_compiled_with_tpu,
    is_compiled_with_cuda, device_count, set_device, get_device,
    cpu_places, cuda_places, cuda_pinned_places, tpu_places,
    CUDAPlace, CUDAPinnedPlace,
)

from paddle_tpu import ops
from paddle_tpu import install_check
from paddle_tpu import transpiler
from paddle_tpu import layers
from paddle_tpu import nn
from paddle_tpu import initializer
from paddle_tpu import optimizer
from paddle_tpu import regularizer
from paddle_tpu import clip
from paddle_tpu import metrics
from paddle_tpu import static
from paddle_tpu.static import (
    Program, program_guard, default_main_program, default_startup_program,
    Executor, data, enable_static, disable_static,
)
from paddle_tpu import io
from paddle_tpu import amp
from paddle_tpu import parallel
from paddle_tpu import distributed
from paddle_tpu import dataio
from paddle_tpu import reader
from paddle_tpu import profiler
from paddle_tpu.framework import (
    ParamAttr, Variable, to_variable, no_grad, grad,
)
from paddle_tpu import backward
from paddle_tpu import nets
from paddle_tpu import dygraph
from paddle_tpu import incubate
from paddle_tpu import compiler
from paddle_tpu.compiler import (
    CompiledProgram, ExecutionStrategy, BuildStrategy,
)
in_dygraph_mode = dygraph.enabled   # fluid.in_dygraph_mode parity
from paddle_tpu.dataio.feeder import DataFeeder
# the two most common top-level paddle.* calls in fluid scripts:
# paddle.batch(reader, bs) and paddle.dataset.mnist.train().
# io.batch keeps paddle.batch's drop_last=False default (the raw
# batch_reader helper defaults True, which would silently drop the
# final partial batch of a migrated eval loop)
from paddle_tpu.io import batch
from paddle_tpu.dataio import dataset
from paddle_tpu.framework import WeightNormParamAttr
from paddle_tpu import lod_tensor
from paddle_tpu.lod_tensor import (
    create_lod_tensor, create_random_int_lodtensor,
)
from paddle_tpu import recordio_writer
from paddle_tpu import distributions
from paddle_tpu import contrib
from paddle_tpu import inference

from paddle_tpu.version import __version__  # noqa: E402

# the package's own import, first line to last, on the start-up timeline
# (profiler.startup): what lies before it is the interpreter, jax where the
# caller imported it first, and the caller's other imports
profiler.record_span("startup/import", _IMPORT_T0, _time.perf_counter())
