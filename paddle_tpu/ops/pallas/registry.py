"""Pallas kernel registry: ONE selection/fallback home.

Mirrors the op-registry pattern (``register_op`` in
``static/opt_passes.py``): each registered kernel declares a stock-jnp
**reference** body and an optional **Pallas** body. Selection happens at
trace/compile time, from what the code observes:

- the platform: the Pallas body on an accelerator, the stock reference on
  the CPU — tier-1 stays on the exact jnp semantics it always had;
- :func:`mesh_scope`: inside a mesh of more than one device the
  reference, because Mosaic calls are not partitioned by GSPMD (the
  lowering refuses them), so a step traced for a multi-device mesh takes
  the bodies XLA can partition. One exception, for a kernel that declares
  which of its operands lead with the batch (``batch_leading``): where
  the mesh splits only the batch (``parallel.mesh.DATA_AXIS`` is its one
  axis above 1, and divides the operands' batch) the Pallas body runs a
  shard at a time inside ``jax.shard_map`` over that axis, under the name
  ``pallas_per_shard``. ``flash_attention`` declares it, and on ``data=4``
  at 512 positions the BERT-base step is 14% shorter for it (PERF.md
  section 6, PR 34); the delta-rule mixers' two passes
  (``delta_glue.py``) declare it too, their taps and gain ``whole``:
  arrays every shard reads whole.

Nothing a user sets takes part: no flag, no environment variable. A body
that loses on the chip is deleted, not switched off. Tests and A/B
harnesses force a body for their own thread with :func:`override`:
``on`` is the Pallas body everywhere (on the CPU in Pallas interpreter
mode, the same kernel code the TPU compiles), ``off`` the reference
everywhere, ``auto`` the selection above.

Every selection change is published through the
``pallas_kernels_selected{kernel,body}`` gauge so a running job's kernel
selection is inspectable from the metrics snapshot
(docs/OBSERVABILITY.md). Where a kernel's Pallas body has blocks for more
than one layout of its operands (``flash_attention``: heads-major, and
rows-major, the projections' own), the body's name there says which the
last call took: ``pallas_rows_major``, ``pallas_per_shard_rows_major``.
"""

import contextlib
import functools
import inspect
import threading

import jax
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec

__all__ = [
    "register_kernel", "get_kernel", "list_kernels", "dispatch",
    "get_body", "selected_body", "use_pallas", "selection_mode",
    "override", "mesh_scope", "platform", "within_vmem_budget",
    "vmem_spec", "traced_once", "lowered_once", "DEFAULT_VMEM_BUDGET",
]

#: fp32 elements one operand may hold whole in VMEM: 8 MiB, half of
#: Mosaic's 16 MiB scoped-VMEM limit per kernel (the limit stands unless
#: the call raises it with pltpu.CompilerParams). The other half is for
#: the streamed, double-buffered blocks and the body's intermediates; at
#: 16 MiB the compiler refuses the kernel (tests/test_tpu_aot_compile.py
#: compiles the budget's edge).
DEFAULT_VMEM_BUDGET = 2 << 20

_REGISTRY = {}
_lock = threading.Lock()
_tls = threading.local()

_MODES = ("auto", "on", "off")


class Kernel:
    """One registered kernel: a stock-jnp reference body and an optional
    Pallas body. Both bodies share one signature; the Pallas body must
    additionally accept ``interpret=`` (bool) — the registry injects it
    from the platform probe. ``batch_leading`` names the parameters whose
    leading dimension is the batch, the first of them never ``None``; the
    result leads with the batch too, and no row of it reads another row's
    operands. A kernel that says so can run a shard of the batch at a
    time; ``whole`` names its array parameters without a batch (weights),
    which every shard reads whole. ``layout``, of a kernel whose Pallas body
    has blocks for more than one layout of its operands, takes a call's
    arguments and names the layout they run in ("" for the first there
    was): the gauge's body name carries it, and nothing else reads it."""

    __slots__ = ("name", "reference", "pallas", "doc", "batch_leading",
                 "whole", "layout")

    def __init__(self, name, reference, pallas=None, doc="",
                 batch_leading=(), whole=(), layout=None):
        self.name = name
        self.reference = reference
        self.pallas = pallas
        self.doc = doc
        self.batch_leading = tuple(batch_leading)
        self.whole = tuple(whole)
        self.layout = layout

    def __repr__(self):
        bodies = "reference+pallas" if self.pallas else "reference"
        return f"Kernel({self.name!r}, {bodies})"


def register_kernel(name, reference, pallas=None, doc="",
                    batch_leading=(), whole=(), layout=None):
    """Register (or re-register) a kernel. Mirrors ``register_op``:
    last registration wins, so tests can shadow a body."""
    k = Kernel(name, reference, pallas, doc, batch_leading, whole, layout)
    with _lock:
        _REGISTRY[name] = k
    return k


def get_kernel(name):
    return _REGISTRY[name]


def list_kernels():
    return sorted(_REGISTRY)


@functools.lru_cache(maxsize=None)
def platform():
    """Per-process cached device-platform probe. jax.devices() walks the
    backend registry on every call — on the per-step hot path (every
    kernel invocation) the probe must be paid exactly once."""
    import jax
    return jax.devices()[0].platform


def selection_mode():
    """Effective mode: the innermost :func:`override`, else 'auto'."""
    ov = getattr(_tls, "override", None)
    return ov[-1] if ov else "auto"


@contextlib.contextmanager
def override(mode):
    """Force selection for the current thread: 'on' | 'off' | 'auto'.
    Nestable; used by the parity tests, ``tools/op_tester.py --pallas``
    and ``chip_smoke.py``."""
    if mode not in _MODES:
        raise ValueError(f"override mode {mode!r}: one of {_MODES}")
    stack = getattr(_tls, "override", None)
    if stack is None:
        stack = _tls.override = []
    stack.append(mode)
    try:
        yield
    finally:
        stack.pop()


@contextlib.contextmanager
def mesh_scope(mesh):
    """Declare that the code traced inside is lowered for ``mesh`` by
    GSPMD (a jitted step with mesh-sharded operands). Entered by the
    trainers and the executor INSIDE the function they jit, so it is
    active while that function is traced. ``None`` and one-device meshes
    change nothing. Not for shard_map bodies: there a Mosaic call runs
    per shard and stays legal, which is how :func:`dispatch` itself runs a
    ``batch_leading`` kernel under a mesh that splits only the batch."""
    stack = getattr(_tls, "meshes", None)
    if stack is None:
        stack = _tls.meshes = []
    stack.append(mesh)
    try:
        yield
    finally:
        stack.pop()


def _partitioning_mesh():
    """The innermost :func:`mesh_scope`'s mesh if GSPMD has something to
    partition over it (more than one device), else None."""
    stack = getattr(_tls, "meshes", None)
    mesh = stack[-1] if stack else None
    return mesh if mesh is not None and mesh.size > 1 else None


def _splits_only_batch(mesh, batch):
    """True where the data axis is the one axis of ``mesh`` above 1 and
    divides ``batch`` (None: a question asked before the operands are
    there, which leaves the batch out), and the code traced is not already
    a ``shard_map`` body over that axis (a trainer's, that passed its mesh
    on to the model: there is no second split to make)."""
    from paddle_tpu.parallel.mesh import DATA_AXIS
    shape = dict(mesh.shape)
    n = shape.pop(DATA_AXIS, 1)
    return all(size == 1 for size in shape.values()) \
        and (batch is None or batch % n == 0) \
        and DATA_AXIS not in jax.sharding.get_abstract_mesh().manual_axes


def selected_body(name, batch=None):
    """Which body a dispatch of ``name`` would run right now, on operands
    of ``batch`` rows where that is known: 'pallas' (compiled),
    'pallas_per_shard' (compiled, a shard of the batch at a time inside
    ``shard_map`` over the mesh's data axis), either with '_interpret'
    behind it (CPU interpreter mode), or 'reference'."""
    k = _REGISTRY[name]
    mode = selection_mode()
    cpu = platform() == "cpu"
    if k.pallas is None or mode == "off" or (mode == "auto" and cpu):
        return "reference"
    mesh = _partitioning_mesh()
    if mesh is None:
        body = "pallas"
    elif k.batch_leading and _splits_only_batch(mesh, batch):
        body = "pallas_per_shard"
    elif mode == "on":
        body = "pallas"
    else:
        return "reference"
    return body + "_interpret" if cpu else body


def use_pallas(name):
    """True when dispatch would run the Pallas body — call sites that
    keep their stock code inline (bit-identical flag-off path) gate on
    this instead of always routing through :func:`dispatch`."""
    return selected_body(name) != "reference"


_last_selection = {}


def _note_selection(name, body):
    """Publish selection changes to the pallas_kernels_selected gauge.
    Only on change: dispatch sits on the hot path."""
    if _last_selection.get(name) == body:
        return
    prev = _last_selection.get(name)
    _last_selection[name] = body
    try:
        from paddle_tpu.monitor.registry import gauge
        g = gauge("pallas_kernels_selected",
                  "Which body the Pallas kernel registry selected "
                  "(1 = active), per kernel",
                  labels=("kernel", "body"))
        if prev is not None:
            g.set(0, kernel=name, body=prev)
        g.set(1, kernel=name, body=body)
    except Exception:  # pragma: no cover - telemetry must never fail a step
        pass


def _in_layout(body, kernel, args, kwargs):
    """The gauge's name for ``body`` on these arguments: a Pallas body with
    the operand layout the kernel says they run in, where it names one,
    before ``_interpret`` (``pallas_rows_major``,
    ``pallas_per_shard_rows_major_interpret``)."""
    layout = kernel.layout(*args, **kwargs) \
        if kernel.layout and body != "reference" else ""
    if not layout:
        return body
    stem, interpret, _ = body.partition("_interpret")
    return f"{stem}_{layout}{interpret}"


def vmem_spec(*args, **kwargs):
    """A ``pl.BlockSpec`` whose block lives in VMEM unless told otherwise:
    what the kernel modules' in_specs and out_specs are made of."""
    kwargs.setdefault("memory_space", pltpu.VMEM)
    return pl.BlockSpec(*args, **kwargs)


_NO_MESH = jax.sharding.AbstractMesh((), ())


def traced_once(jitted, *args):
    """``jitted(*args)`` under one trace context wherever no mesh is in
    scope: for a kernel module's jitted call of its ``pallas_call``. jax keys
    a jitted function's trace on the context it is called in, and the JVP of
    a ``jax.checkpoint`` (the delta-rule mixers are under one) sets an empty
    abstract mesh where the pass itself has none, so a forward kernel was
    traced and lowered to Mosaic once for each: 0.2 s a kernel on the chip's
    host, in every set-up (PERF.md section 6, PR 39). Inside a ``shard_map``
    the mesh in scope stays the key, as it has to. Both facts are jax
    0.9.0's and no part of its interface: the counts of kernel bodies
    entered in ``tests/test_kimi_linear.py`` and ``tests/test_qwen3_next.py``
    are what says so when jax moves."""
    if not jax.sharding.get_abstract_mesh().empty:
        return jitted(*args)
    with jax.sharding.use_abstract_mesh(_NO_MESH):
        return jitted(*args)


def lowered_once(jitted, arrays, static=()):
    """``traced_once(jitted, *arrays, *static)`` as one equation no transform
    opens: for a forward kernel's jitted call inside a ``custom_vjp`` forward
    rule that a ``jax.checkpoint`` differentiates (``blocks.recomputed``).
    The checkpoint's partial evaluation has a rule for a ``jit`` equation and
    applies it to one whose inputs are all known too: it cuts the inner jaxpr
    into a known and a staged part anew at every call site, and the lowering,
    which keys a jitted function on its jaxpr, then lowers the kernel to
    Mosaic once a layer where the backward call, untouched, is one function
    called from every layer (0.15 s a call site on the chip's host: PERF.md
    section 6, PR 50). A ``custom_jvp`` call has no such rule and is lowered
    inline, so the ``jit`` inside reaches the lowering with the jaxpr it was
    traced to. It is never differentiated: the ``custom_vjp`` around it is.
    jax 0.9.0's behaviour; ``tests/test_nemotron_h_aot.py`` counts the
    functions when jax moves."""
    @jax.custom_jvp
    def call(*arrays):
        return traced_once(jitted, *arrays, *static)

    @call.defjvp
    def _(primals, tangents):
        raise NotImplementedError(
            f"{jitted} is the forward call of a custom_vjp: differentiate "
            "that")

    return call(*arrays)


def within_vmem_budget(kernel, elements, budget=None):
    """True when a kernel body planning to hold ``elements`` fp32
    elements whole in VMEM fits under ``budget`` (default
    :data:`DEFAULT_VMEM_BUDGET`). The shared guard every Pallas body
    calls BEFORE committing to its VMEM-resident strategy: a False
    means "fall back to the reference body", and every such rejection
    counts in ``pallas_vmem_budget_rejections_total{kernel}`` so
    budget fallbacks are visible per kernel instead of silently
    vanishing into the reference path."""
    if budget is None:
        budget = DEFAULT_VMEM_BUDGET
    if int(elements) <= int(budget):
        return True
    try:
        from paddle_tpu.monitor.registry import counter
        counter("pallas_vmem_budget_rejections_total",
                "Pallas kernel dispatches that fell back to the "
                "stock reference body because the planned "
                "VMEM-resident working set exceeded the budget "
                "(fp32 elements, ops/pallas/registry.py "
                "within_vmem_budget)",
                labels=("kernel",)).inc(kernel=str(kernel))
    except Exception:  # pragma: no cover - telemetry must never fail a step
        pass
    return False


def get_body(name, which):
    """Raw body access for A/B harnesses: which = 'reference'|'pallas'."""
    k = _REGISTRY[name]
    return k.reference if which == "reference" else k.pallas


@functools.lru_cache(maxsize=64)
def _per_shard_call(kernel, mesh, arrays, whole, static):
    """``kernel``'s Pallas body on the operands named ``arrays``, a shard
    of the batch at a time over ``mesh``'s data axis, and on those named
    ``whole``, which every shard reads as they are; ``static`` holds its
    other arguments. A jitted function of its own, and one a (kernel, mesh,
    arguments): a model's layers call it with the same shapes and share one
    trace of the ``shard_map`` and of what it holds, as they share the
    kernels' own (``flash_attention._flash_fwd``)."""
    from paddle_tpu.parallel.mesh import DATA_AXIS
    rows = PartitionSpec(DATA_AXIS)

    def shard(*operands):
        return kernel.pallas(**dict(zip(arrays + whole, operands)),
                             **dict(static))

    shard.__name__ = kernel.name + "_per_shard"
    # check_vma off: a pallas_call says nothing of how its results vary
    # over the mesh; every operand and result here varies over the data axis
    return jax.jit(jax.shard_map(
        shard, mesh=mesh,
        in_specs=(rows,) * len(arrays) + (PartitionSpec(),) * len(whole),
        out_specs=rows, check_vma=False))


def _dispatch_per_shard(kernel, interpret, args, kwargs):
    bound = inspect.signature(kernel.pallas).bind(
        *args, interpret=interpret, **kwargs).arguments
    arrays = {n: bound.pop(n) for n in kernel.batch_leading
              if bound.get(n) is not None}
    whole = {n: bound.pop(n) for n in kernel.whole}
    call = _per_shard_call(kernel, _partitioning_mesh(), tuple(arrays),
                           tuple(whole), tuple(sorted(bound.items())))
    return call(*arrays.values(), *whole.values())


def dispatch(name, *args, **kwargs):
    """Run the selected body. The Pallas body receives ``interpret=``
    resolved from the platform probe; the reference body has no such
    keyword. A ``batch_leading`` kernel's first operand tells the batch."""
    k = _REGISTRY[name]
    body = selected_body(
        name, np.shape(args[0])[0] if k.batch_leading and args else None)
    _note_selection(name, _in_layout(body, k, args, kwargs))
    if body == "reference":
        return k.reference(*args, **kwargs)
    interpret = body.endswith("_interpret")
    if body.startswith("pallas_per_shard"):
        return _dispatch_per_shard(k, interpret, args, kwargs)
    return k.pallas(*args, interpret=interpret, **kwargs)
