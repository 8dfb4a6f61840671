"""Mamba-2's state-space layer in its chunked dual form (Dao & Gu 2024,
"Transformers are SSMs", arXiv:2405.21060, sec. 6: the SSD algorithm).

Per head, with a state ``h`` in R^{P x N} that starts at zero, a scalar
decay a head and position and **no delta rule** (``ops/kda.py`` multiplies
its state by ``I - beta k k^T``; nothing is subtracted here)::

    h_t = exp(a_t) h_{t-1} + dt_t x_t B_t^T        a_t <= 0
    y_t = h_t C_t + D x_t

``x`` is [b, S, H, P], ``dt`` (the step, after its softplus) and ``a`` (the
log of the decay, ``-exp(A_log) dt``) are [b, S, H] float32, ``B`` and ``C``
are [b, S, G, N] with G dividing H: head ``h`` reads group ``h // (H / G)``,
so a group's ``C_i . B_j`` is formed once for its heads. ``D`` [H] is the
skip, or None.

``ssd_recurrent`` is that recurrence, one position a step, in float32: what
the chunked form is held against. ``ssd_chunked`` is what a training step
runs. It cuts the positions into chunks of ``chunk``; with ``G`` the running
sum of ``a`` inside a chunk (its own position included) and ``h_0`` the
state the chunk starts from,

    y_i  = sum_{j<=i} (C_i . B_j) exp(G_i - G_j) dt_j x_j  +  exp(G_i) h_0 C_i
    h_C  = exp(G_C) h_0 + sum_j exp(G_C - G_j) dt_j x_j B_j^T

Everything that does not need ``h_0`` (the groups' scores, the decay between
the pairs of a head, the state a chunk adds) is computed for all chunks at
once as batched matmuls; the scan over the chunks carries only the state and
does no product; then the states' part of ``y`` is one batched matmul more.

**No exponent is ever positive**: every exponential is ``exp(G_i - G_j)``
with ``j <= i``, ``exp(G_C - G_j)`` or ``exp(G_i)``, formed from the
difference, so a head that forgets fast cannot overflow. ``G``, the
exponentials and the state are float32; the matmul operands are in ``x``'s
dtype (bfloat16 in a model) with float32 accumulation.

A length that is no multiple of the chunk is padded with positions that
change nothing (``a`` = 0, ``dt`` = 0) and the padding's outputs are cut
off.

Two bodies, chosen by the kernel registry (``ops/pallas/registry.py``:
platform and mesh, nothing a user sets), kernel ``ssd``. The ``jax.numpy``
body here, ``_ssd_chunked``, is the reference: the CPU's, a multi-device
mesh's, and that of a shape the kernels cannot tile. Its backward pass is
autodiff through this code, so a caller that cannot keep a layer's [chunks,
H, chunk, chunk] decays wraps the mixer in ``jax.checkpoint``. On one chip
the Mosaic kernels ``ssd_fwd`` and ``ssd_bwd`` of ``ops/pallas/ssd.py`` run
the same sums a chunk at a time with the states in VMEM, read the operands
in the layouts above and keep ``y`` and every second chunk's starting state for
their own backward under a name (``ops/pallas/ssd.KEPT``), which
``models/blocks.recomputed`` saves: the forward runs once a layer.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax

from paddle_tpu.ops.pallas import registry as _registry

__all__ = ["CHUNK", "ssd_chunked", "ssd_recurrent"]

#: positions a chunk where the caller names none: Mamba-2's, and the
#: ``chunk_size`` of the released Nemotron-H configurations. Read by
#: ``chipbench/flops/ssd_core.py``, which counts the op's operations at it.
CHUNK = 128


def ssd_recurrent(x, dt, a, B, C, D=None):
    """The recurrence itself, one position a step, float32 throughout;
    returns y [b, S, H, P] float32."""
    f32 = jnp.float32
    x, dt, a, B, C = (t.astype(f32) for t in (x, dt, a, B, C))
    b, _, h, p = x.shape
    per_group = h // B.shape[2]
    B, C = (jnp.repeat(t, per_group, axis=2) for t in (B, C))

    def step(state, at):
        x_t, dt_t, a_t, b_t, c_t = at
        state = jnp.exp(a_t)[..., None, None] * state \
            + (dt_t[..., None] * x_t)[..., None] * b_t[..., None, :]
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t,
                                 precision=lax.Precision.HIGHEST)

    _, y = lax.scan(step, jnp.zeros((b, h, p, B.shape[-1]), f32),
                    tuple(jnp.moveaxis(t, 1, 0) for t in (x, dt, a, B, C)))
    y = jnp.moveaxis(y, 0, 1)
    return y if D is None else y + D.astype(f32)[:, None] * x


@functools.partial(jax.jit, static_argnums=(6,))
def _ssd_chunked(x, dt, a, B, C, D, chunk):
    f32 = jnp.float32
    b, s, h, p = x.shape
    g, n = B.shape[2:]
    per_group = h // g
    dtype = x.dtype
    pad = -s % chunk
    if pad:
        x, dt, a, B, C = (jnp.pad(t, ((0, 0), (0, pad))
                                  + ((0, 0),) * (t.ndim - 2))
                          for t in (x, dt, a, B, C))
    nc = (s + pad) // chunk
    xc = x.reshape(b, nc, chunk, g, per_group, p).astype(f32)
    dtc = dt.astype(f32).reshape(b, nc, chunk, g, per_group)
    Bc, Cc = (t.reshape(b, nc, chunk, g, n) for t in (B, C))
    # the running log-decay of a head inside its chunk, [b, nc, g, k, chunk]
    G = jnp.cumsum(a.astype(f32).reshape(b, nc, chunk, g, per_group), axis=2)
    Gh = G.transpose(0, 1, 3, 4, 2)
    # inside the chunks: a group's scores once, a head's decay on them
    scores = jnp.einsum("bcign,bcjgn->bcgij", Cc, Bc,
                        preferred_element_type=f32)
    seen = jnp.tril(jnp.ones((chunk, chunk), bool))
    between = jnp.exp(jnp.where(seen, Gh[..., :, None] - Gh[..., None, :],
                                -jnp.inf))
    weights = (scores[:, :, :, None] * between).astype(dtype)
    u = (dtc[..., None] * xc).astype(dtype)
    y = jnp.einsum("bcgkij,bcjgkp->bcigkp", weights, u,
                   preferred_element_type=f32)
    # what a chunk adds to the state, and what it leaves of the state before
    to_end = jnp.exp(G[:, :, -1:] - G)
    added = jnp.einsum(
        "bcjgkp,bcjgn->bcgkpn",
        ((to_end * dtc)[..., None] * xc).astype(dtype), Bc,
        preferred_element_type=f32)
    kept = jnp.exp(G[:, :, -1])                          # [b, nc, g, k]

    def step(state, of_chunk):
        kept_c, added_c = of_chunk
        return kept_c[..., None, None] * state + added_c, state

    _, starts = lax.scan(
        step, jnp.zeros((b, g, per_group, p, n), f32),
        (jnp.moveaxis(kept, 1, 0), jnp.moveaxis(added, 1, 0)))
    starts = jnp.moveaxis(starts, 0, 1)                  # [b, nc, g, k, p, n]
    y = y + jnp.exp(G)[..., None] * jnp.einsum(
        "bcign,bcgkpn->bcigkp", Cc, starts.astype(dtype),
        preferred_element_type=f32)
    if D is not None:
        y = y + D.astype(f32).reshape(g, per_group, 1) * xc
    return y.reshape(b, s + pad, h, p)[:, :s].astype(dtype)


def ssd_chunked(x, dt, a, B, C, D=None, chunk=CHUNK):
    """The chunked form (module docstring); returns y [b, S, H, P] in
    ``x.dtype``, by the body the registry selects (kernel ``ssd``). Either
    body is jitted, so a model's layers, which call it with the same shapes,
    share one trace."""
    if x.shape[2] % B.shape[2]:
        raise ValueError(f"{B.shape[2]} groups of B and C do not divide "
                         f"{x.shape[2]} heads")
    return _registry.dispatch("ssd", x, dt, a, B, C, D, chunk)
