"""Pallas kernel registry: ONE selection/fallback/flag home.

Mirrors the op-registry pattern (``register_op`` in
``static/opt_passes.py``): each registered kernel declares a stock-jnp
**reference** body and an optional **Pallas** body. Selection happens at
trace/compile time:

- ``auto`` (default): Pallas body on an accelerator, stock reference on
  CPU — tier-1 stays on the exact jnp semantics it always had. Inside a
  :func:`mesh_scope` of more than one device ``auto`` also selects the
  reference: Mosaic calls are not partitioned by GSPMD (the lowering
  refuses them), so a step traced for a multi-device mesh takes the
  bodies XLA can partition. Which body is faster there is not measured.
- ``on``: force the Pallas body everywhere; on CPU it runs in Pallas
  interpreter mode (the same kernel code path the TPU compiles).
- ``off``: force the stock reference everywhere.

Override via ``FLAGS_use_pallas_kernels=auto|on|off`` (core/flags.py),
the short env ``PADDLE_TPU_PALLAS=0|1``, or the :func:`override` context
manager for in-process A/B (bench.py kernels mode, parity tests).

Every selection change is published through the
``pallas_kernels_selected{kernel,body}`` gauge so a running job's kernel
selection is inspectable from the metrics snapshot
(docs/OBSERVABILITY.md).
"""

import contextlib
import functools
import os
import threading

from paddle_tpu.core.flags import define_flag, get_flag

__all__ = [
    "register_kernel", "get_kernel", "list_kernels", "dispatch",
    "get_body", "selected_body", "use_pallas", "selection_mode",
    "override", "mesh_scope", "platform", "within_vmem_budget",
    "DEFAULT_VMEM_BUDGET",
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

# PADDLE_TPU_PALLAS=0|1 is the short A/B switch; FLAGS_use_pallas_kernels
# (read by define_flag from the env) wins when both are set, matching the
# flag system's precedence for every other flag.
_env_short = os.environ.get("PADDLE_TPU_PALLAS")
define_flag(
    "use_pallas_kernels",
    {"0": "off", "1": "on"}.get(_env_short, "auto"),
    "Pallas kernel registry selection: 'auto' = Pallas bodies on an "
    "accelerator, stock jnp reference on CPU; 'on' = force Pallas "
    "(interpreter mode on CPU); 'off' = force the stock reference. "
    "Short env form: PADDLE_TPU_PALLAS=0|1 (ops/pallas/registry.py)")

_MODE_ALIASES = {
    "auto": "auto", "": "auto", "default": "auto",
    "on": "on", "1": "on", "true": "on", "yes": "on",
    "off": "off", "0": "off", "false": "off", "no": "off",
}


class Kernel:
    """One registered kernel: a stock-jnp reference body and an optional
    Pallas body. Both bodies share one signature; the Pallas body must
    additionally accept ``interpret=`` (bool) — the registry injects it
    from the platform probe."""

    __slots__ = ("name", "reference", "pallas", "doc")

    def __init__(self, name, reference, pallas=None, doc=""):
        self.name = name
        self.reference = reference
        self.pallas = pallas
        self.doc = doc

    def __repr__(self):
        bodies = "reference+pallas" if self.pallas else "reference"
        return f"Kernel({self.name!r}, {bodies})"


def register_kernel(name, reference, pallas=None, doc=""):
    """Register (or re-register) a kernel. Mirrors ``register_op``:
    last registration wins, so tests can shadow a body."""
    k = Kernel(name, reference, pallas, doc)
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
    """Effective mode: an :func:`override` beats the flag."""
    ov = getattr(_tls, "override", None)
    if ov:
        return ov[-1]
    return _MODE_ALIASES.get(str(get_flag("use_pallas_kernels")).lower(),
                             "auto")


@contextlib.contextmanager
def override(mode):
    """Force selection for the current thread: 'on' | 'off' | 'auto'.
    Nestable; used by the bench kernels mode and the parity tests."""
    mode = _MODE_ALIASES[str(mode).lower()]
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
    per shard and stays legal."""
    stack = getattr(_tls, "mesh_sizes", None)
    if stack is None:
        stack = _tls.mesh_sizes = []
    stack.append(1 if mesh is None else int(mesh.size))
    try:
        yield
    finally:
        stack.pop()


def _partitioned():
    stack = getattr(_tls, "mesh_sizes", None)
    return bool(stack) and stack[-1] > 1


def selected_body(name):
    """Which body a dispatch of ``name`` would run right now:
    'pallas' (compiled), 'pallas_interpret' (CPU interpreter mode), or
    'reference'."""
    k = _REGISTRY[name]
    if k.pallas is None:
        return "reference"
    mode = selection_mode()
    if mode == "off":
        return "reference"
    cpu = platform() == "cpu"
    if mode == "on":
        return "pallas_interpret" if cpu else "pallas"
    return "reference" if cpu or _partitioned() else "pallas"


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


def dispatch(name, *args, **kwargs):
    """Run the selected body. The Pallas body receives ``interpret=``
    resolved from the platform probe (unless the caller already forced
    it)."""
    k = _REGISTRY[name]
    body = selected_body(name)
    _note_selection(name, body)
    if body == "reference":
        return k.reference(*args, **kwargs)
    kwargs.setdefault("interpret", body == "pallas_interpret")
    return k.pallas(*args, **kwargs)
