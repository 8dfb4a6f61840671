"""Core data model: dtypes, places, flags, diagnostics, ragged metadata.

TPU-native analog of the reference's layer 0/1
(paddle/fluid/platform + paddle/fluid/framework core data model).
"""

from paddle_tpu.core import dtypes
from paddle_tpu.core import enforce
from paddle_tpu.core import flags
from paddle_tpu.core import place
from paddle_tpu.core import lod
from paddle_tpu.core import compile_cache
from paddle_tpu.core.enforce import EnforceNotMet, EOFException  # noqa: F401
# fluid.core.EOFException is the reader-protocol loop terminator; users
# catch it as core.EOFException, so expose it here

# persistent XLA compilation cache: with JAX_COMPILATION_CACHE_DIR in the
# environment (whoever runs the program placed the cache; the launcher
# exports it to its workers) jax already points at that directory —
# enable() sets none, it zeroes jax's caching thresholds and starts the
# hit/miss counters before any jit compiles.
import os as _os

if _os.environ.get(compile_cache.ENV_VAR):
    compile_cache.enable()
