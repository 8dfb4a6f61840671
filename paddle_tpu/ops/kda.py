"""The gated delta rule in its chunked form: Kimi Delta Attention's core,
with a decay per channel (Kimi Linear technical report, Moonshot AI 2025,
arXiv:2510.26692), and Gated DeltaNet's, with a decay per head (Yang et al.
2024, arXiv:2412.06464, whose chunking both use).

Per head, with a state ``S`` in R^{d x d} that starts at zero::

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

**The operands say which rule.** ``g`` of rank 4, ``[B, S, H, d]``, is a
decay a channel; ``g`` of rank 3, ``[B, S, H]``, one number a head and
position, the same on every channel (``Diag(exp(g_t))`` = ``exp(g_t) I``).
``q`` and ``k`` may have fewer heads than ``v``, ``H / n``: value head ``h``
then reads key head ``h // n`` (Gated DeltaNet: 16 key heads under 32 value
heads). Nothing else chooses. With a decay a head the chunk's score
matrices are one product each, masked by ``exp(G_i - G_j)`` on ``[C, C]``
(``_prepare_head_decay``): the exponent is a scalar a pair, formed as the
difference, so it is never positive, and the sub-blocks, the reference rows
and the channel-by-channel diagonal below have nothing to do there. Its
chunk is ``CHUNK_HEAD``.

``kda_recurrent`` is that recurrence, one position a step: what the chunked
form is held against. ``kda_chunked`` is what a training step runs. It cuts
the positions into chunks of ``CHUNK``; with ``G`` the running sum of ``g``
inside a chunk and ``S_0`` the state the chunk starts from,

    A_ij = beta_i sum_c k_ic k_jc exp(G_ic - G_jc)   (j < i, else 0)
    U    = (I + A)^{-1} Diag(beta) (V - (K * exp(G)) S_0)
    o_i  = S_0^T (exp(G_i) * q_i) + sum_{j<=i} u_j sum_c q_ic k_jc
           exp(G_ic - G_jc)
    S_C  = Diag(exp(G_C)) S_0 + sum_j (exp(G_C - G_j) * k_j) u_j^T

Everything that does not need ``S_0`` (the two score matrices, the solve
against ``Diag(beta) [V | K * exp(G)]``) is computed for all chunks at once;
the scan over the chunks carries only ``S`` and does four small matmuls a
chunk.

**No exponent is ever positive.** Every exponential formed is ``exp(G_i -
G_j)`` with ``j <= i`` (or ``exp(G_i)``), at most 1: a channel that forgets
fast (``g`` near -20 a step) would overflow float32 within five positions if
``exp(G_i)`` and ``exp(-G_j)`` were formed apart. Inside a chunk the score
matrices are built from sub-blocks of ``_SUB`` positions: a sub-block below
the diagonal is a matmul of ``a_i exp(G_i - R)`` with ``b_j exp(R - G_j)``
around the reference row ``R`` = the first row of the query's sub-block,
which lies between the two, so both exponents are non-positive; a sub-block
on the diagonal is summed channel by channel (``_diag_scores``).

``G``, the exponentials, the solve and ``S`` are float32; the matmul
operands are in the dtype of ``q`` (bfloat16 in a model) with float32
accumulation.

**Two bodies.** ``kda_chunked`` is a kernel of the Pallas registry
(``ops/pallas/registry.py``: the platform and ``mesh_scope`` choose, nothing
a caller sets). The **reference** body is this file's ``jax.numpy`` code:
what the CPU and a mesh of several devices run, and what the other is held
against. The **Pallas** body (``ops/pallas/kda.py``: the kernels ``kda_fwd``
and ``kda_bwd``, and ``gdn_fwd`` and ``gdn_bwd`` for a decay a head) runs
where a head is a whole lane tile (``d % 128 == 0``) and hands any other
shape back to this one. Both cut the positions into chunks of ``CHUNK``
(``CHUNK_HEAD``) and round the same operands.

**What each keeps for the backward.** The reference body's backward is
autodiff through the scan over chunks with the chunk body recomputed
(``jax.checkpoint``), so the scan keeps the state each chunk starts from and
nothing else of a chunk; ``_diag_scores`` has a backward of its own that
forms the [sub, sub, d] exponentials again instead of keeping them (they
would be 2 GiB a layer at 8192 positions and 32 heads); what the part before
the scan keeps is a dozen arrays of the inputs' size. The Pallas body keeps,
for every 128 positions and head, the state they start from and three
``[128, 128]`` tiles (``a_qk``, ``P_kk`` and the inverse), 402 MB a layer at
8192 positions and 32 heads of 128, and forms everything else again inside
``kda_bwd``. A caller that cannot afford either wraps the call in
``jax.checkpoint`` and keeps the inputs alone (``models/kimi_linear.py``
does).

**Why the Pallas body inverts.** The reference body solves ``(I + A) X =
Diag(beta) [V | K * exp(G)]`` with ``jax.scipy.linalg.solve_triangular``, a
custom call nothing fuses with (2.3 ms a layer). A kernel has matmuls:
``(I + A)^{-1}`` is formed explicitly in float32 by the block formula
``[[L11^-1, 0], [-L22^-1 L21 L11^-1, L22^-1]]``, level by level from blocks
of one row, and multiplied in; ``A`` is strictly lower triangular with
entries under 1, the same substitution in another order.

**Where the Pallas body rounds:** where this one does (the paragraph
above), with one freedom: the pairs of positions inside one sub-block of
``_SUB`` = 16 rows, which this body sums channel by channel in float32, are
matmuls there too, with float32 operands where the pair lies inside a block
of 8 rows and operands in the inputs' dtype from 8 up (this body: from 16
up).

The op is jitted, so a model's layers, which call it with the same shapes,
share one trace and one lowering (PERF.md section 6, PR 29).
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax

from paddle_tpu.ops.pallas import registry as _registry

__all__ = ["CHUNK", "CHUNK_HEAD", "kda_chunked", "kda_recurrent"]

#: positions a chunk, of whichever body runs. Chosen on the chip for the
#: reference body (PERF.md section 6, PR 30: the table of 16 to 128 at 8192
#: positions and 32 heads of 128; forward + backward 36.4 ms a layer at 32,
#: 37.2 at 16, 40.6 at 64, 68.3 at 128): a constant of the op, not an option.
#: Read outside this package by the benchmark: ``chipbench/flops/kda_core.py``
#: counts the op's operations at it (``mfu_pct``, ``kda_core_roofline_pct``)
#: and ``chipbench/tests/test_kimi_linear_cell.py`` pins it, so a change of
#: it is a ``benchmark`` PR's.
CHUNK = 32
#: positions a sub-block of the reference body's score matrices inside a
#: chunk (same table: 8 and 32 both cost 8% more than 16 at chunk 32)
_SUB = 16
#: positions a chunk where the decay is one number a head (``g`` of rank 3):
#: a chunk's scores are two products whatever its size, so what a larger
#: chunk costs is the inverse's levels alone and what it saves is the walk
#: over the state. Chosen on the chip for the Pallas body (PERF.md section 6,
#: PR 38: [1, 16384, 16 | 32, 128], forward + backward 13.5 ms a layer at 64,
#: 14.2 at 32, 13.7 at 128): a constant of the op, not an option. Read by
#: ``chipbench/flops/gdn_core.py``, as ``CHUNK`` is by ``flops/kda_core.py``.
CHUNK_HEAD = 64


# ---------------------------------------------------------------------------
# scores inside a chunk
# ---------------------------------------------------------------------------
def _diag_terms(a, b, G):
    """a_ic b_jc exp(G_ic - G_jc) for j <= i, 0 above: [..., s, s, d]. The
    exponent is clamped at 0 so that the masked half cannot overflow."""
    s = a.shape[-2]
    lower = jnp.tril(jnp.ones((s, s), bool))[..., None]
    decay = jnp.exp(jnp.minimum(G[..., :, None, :] - G[..., None, :, :], 0.0))
    return jnp.where(lower, a[..., :, None, :] * b[..., None, :, :] * decay,
                     0.0)


@jax.custom_vjp
def _diag_scores(a, b, G):
    """``P_ij = sum_c a_ic b_jc exp(G_ic - G_jc)`` for ``j <= i`` and 0
    above, for a, b, G [..., s, d] float32: the sub-block on the diagonal,
    where no reference row lies between query and key."""
    return jnp.sum(_diag_terms(a, b, G), axis=-1)


def _diag_scores_fwd(a, b, G):
    return _diag_scores(a, b, G), (a, b, G)


def _diag_scores_bwd(res, dp):
    a, b, G = res
    ones = jnp.ones_like(a)
    # d P_ij / d a_ic = b_jc E_ijc, / d b_jc = a_ic E_ijc, and the two
    # gradients of G are those times a and -b: the terms are formed again
    da = jnp.sum(dp[..., None] * _diag_terms(ones, b, G), axis=-2)
    db = jnp.sum(dp[..., None] * _diag_terms(a, ones, G), axis=-3)
    return da, db, a * da - b * db


_diag_scores.defvjp(_diag_scores_fwd, _diag_scores_bwd)


def _pair_scores(a, b, G, dt):
    """``P_ij = sum_c a_ic b_jc exp(G_ic - G_jc)`` for ``j <= i`` and 0
    above, over one chunk: a, b, G [..., C, d] float32 -> [..., C, C]
    float32. Sub-blocks of ``_SUB`` rows; the matmuls' operands in ``dt``."""
    c, d = a.shape[-2:]
    sub = min(_SUB, c)
    n = c // sub
    lead = a.shape[:-2]

    def blocks(t):
        return t.reshape(*lead, n, sub, d)

    diag = _diag_scores(blocks(a), blocks(b), blocks(G))   # [..., n, s, s]
    rows = []
    for i in range(n):
        lo = i * sub
        parts = []
        if i:
            ref = G[..., lo:lo + 1, :]
            left = a[..., lo:lo + sub, :] * jnp.exp(G[..., lo:lo + sub, :]
                                                    - ref)
            right = b[..., :lo, :] * jnp.exp(ref - G[..., :lo, :])
            parts.append(jnp.einsum(
                "...id,...jd->...ij", left.astype(dt), right.astype(dt),
                preferred_element_type=jnp.float32))
        parts.append(diag[..., i, :, :])
        if c - lo - sub:
            parts.append(jnp.zeros((*lead, sub, c - lo - sub), jnp.float32))
        rows.append(jnp.concatenate(parts, axis=-1))
    return jnp.concatenate(rows, axis=-2)


# ---------------------------------------------------------------------------
# the chunked form
# ---------------------------------------------------------------------------
def _prepare(q, k, v, g, beta):
    """All that a chunk needs beside the state it starts from, for every
    chunk at once. Inputs [N, B, H, C, d] (beta [N, B, H, C]); returns the
    operands of the scan's matmuls in ``q.dtype`` and the chunk's whole
    decay [N, B, H, d] in float32."""
    dt = q.dtype
    q32, k32 = q.astype(jnp.float32), k.astype(jnp.float32)
    c = q.shape[-2]
    # the running sum as a product with a triangle of ones, at full
    # precision: XLA's cumsum is a windowed reduction, 1.6 ms a layer
    G = jnp.einsum("ij,...jd->...id", jnp.tril(jnp.ones((c, c), jnp.float32)),
                   g, precision=lax.Precision.HIGHEST)
    strict = jnp.tril(jnp.ones((c, c), bool), -1)
    with jax.named_scope("kda_scores"):
        a_kk = beta[..., None] * jnp.where(
            strict, _pair_scores(k32, k32, G, dt), 0.0)
        a_qk = _pair_scores(q32, k32, G, dt).astype(dt)
    with jax.named_scope("kda_solve"):
        rhs = beta[..., None] * jnp.concatenate(
            [v.astype(jnp.float32), k32 * jnp.exp(G)], axis=-1)
        solved = jax.scipy.linalg.solve_triangular(
            a_kk + jnp.eye(c, dtype=jnp.float32), rhs, lower=True,
            unit_diagonal=True)
        u0, w = jnp.split(solved, [v.shape[-1]], axis=-1)
    g_end = G[..., -1:, :]
    return dict(
        u0=u0, w=w.astype(dt), a_qk=a_qk,
        q_in=(q32 * jnp.exp(G)).astype(dt),
        k_out=(k32 * jnp.exp(g_end - G)).astype(dt),
        decay=jnp.exp(g_end[..., 0, :]))


def _prepare_head_decay(q, k, v, g, beta):
    """``_prepare`` for a decay a head: g [N, B, H, C] as beta, q and k [N,
    B, H / n, C, d]. A chunk's scores are one product a key head, operands
    in ``q.dtype``, times ``exp(G_i - G_j)`` a value head."""
    dt = q.dtype
    c = q.shape[-2]
    n = v.shape[2] // q.shape[2]
    G = jnp.einsum("ij,...j->...i", jnp.tril(jnp.ones((c, c), jnp.float32)),
                   g, precision=lax.Precision.HIGHEST)
    lower = jnp.tril(jnp.ones((c, c), bool))
    # the exponent is clamped at 0 so that the masked half cannot overflow
    decay = jnp.where(lower, jnp.exp(jnp.minimum(
        G[..., :, None] - G[..., None, :], 0.0)), 0.0)

    def per_value_head(t):
        return jnp.repeat(t, n, axis=2) if n > 1 else t

    with jax.named_scope("kda_scores"):
        products = jnp.einsum(
            "...id,...jd->...ij", jnp.concatenate([q, k], axis=-2), k,
            preferred_element_type=jnp.float32)             # [.., 2 C, C]
        p_qk, p_kk = (per_value_head(p) * decay
                      for p in jnp.split(products, 2, axis=-2))
        a_kk = beta[..., None] * jnp.where(jnp.tril(lower, -1), p_kk, 0.0)
        a_qk = p_qk.astype(dt)
    q32, k32 = (per_value_head(t.astype(jnp.float32)) for t in (q, k))
    e_in = jnp.exp(G)[..., None]
    with jax.named_scope("kda_solve"):
        rhs = beta[..., None] * jnp.concatenate(
            [v.astype(jnp.float32), k32 * e_in], axis=-1)
        solved = jax.scipy.linalg.solve_triangular(
            a_kk + jnp.eye(c, dtype=jnp.float32), rhs, lower=True,
            unit_diagonal=True)
        u0, w = jnp.split(solved, [v.shape[-1]], axis=-1)
    g_end = G[..., -1:]
    return dict(
        u0=u0, w=w.astype(dt), a_qk=a_qk, q_in=(q32 * e_in).astype(dt),
        k_out=(k32 * jnp.exp(g_end - G)[..., None]).astype(dt),
        decay=jnp.exp(g_end))


@jax.checkpoint
def _chunk_step(state, x):
    """One chunk given the state it starts from: (the next state, the
    chunk's outputs [..., C, d]). Recomputed in the backward pass."""
    dt = x["w"].dtype

    def mm(spec, lhs, rhs):
        return jnp.einsum(spec, lhs, rhs.astype(dt),
                          preferred_element_type=jnp.float32)

    u = x["u0"] - mm("...ck,...kv->...cv", x["w"], state)
    out = mm("...ck,...kv->...cv", x["q_in"], state) \
        + mm("...ij,...jv->...iv", x["a_qk"], u)
    state = x["decay"][..., None] * state \
        + mm("...ck,...cv->...kv", x["k_out"], u)
    return state, out


@functools.partial(jax.jit, static_argnums=(5,))
def _kda_chunked(q, k, v, g, beta, chunk):
    b, s, _, d = q.shape
    h = v.shape[2]
    pad = (-s) % chunk
    if pad:
        # a padded position neither decays (g = 0) nor writes (beta = 0)
        q, k, v, g, beta = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (q, k, v, g, beta))
    n = (s + pad) // chunk
    if g.ndim == 4 and q.shape[2] != h:
        # a decay a channel under grouped key heads: a value head's own copy
        q, k = (jnp.repeat(t, h // t.shape[2], axis=2) for t in (q, k))

    def chunks(t):
        """[B, S, H, ...] -> [N, B, H, C, ...]: one transpose, the chunks
        first, as the scan takes them."""
        t = t.reshape(b, n, chunk, *t.shape[2:])
        return t.transpose(1, 0, 3, 2, *range(4, t.ndim))

    xs = (_prepare if g.ndim == 4 else _prepare_head_decay)(
        chunks(q), chunks(k), chunks(v), chunks(g.astype(jnp.float32)),
        chunks(beta.astype(jnp.float32)))
    state = jnp.zeros((b, h, d, v.shape[-1]), jnp.float32)
    with jax.named_scope("kda_scan"):
        _, out = lax.scan(_chunk_step, state, xs)
    out = out.transpose(1, 0, 3, 2, 4).reshape(b, s + pad, h, v.shape[-1])
    return out[:, :s].astype(v.dtype)


def kda_chunked(q, k, v, g, beta):
    """The gated delta rule over v [B, S, H, d], q and k [B, S, H / n, d]
    (as the rule takes them: normalised, q scaled; value head h reads key
    head ``h // n``), the log decay g (<= 0), [B, S, H, d] a channel or [B,
    S, H] a head, and the write strength beta [B, S, H], from a zero state.
    Returns [B, S, H, d] in ``v.dtype``. Differentiable in all five."""
    if v.shape[2] % q.shape[2] or q.shape != k.shape:
        raise ValueError(f"value heads {v.shape[2]} are no multiple of the "
                         f"key heads of q {q.shape} and k {k.shape}")
    return _registry.dispatch("kda_chunked", q, k, v, g, beta,
                              CHUNK if g.ndim == 4 else CHUNK_HEAD)


def kda_recurrent(q, k, v, g, beta):
    """The same, one position a step in float32: the definition."""
    f32 = jnp.float32
    n = v.shape[2] // q.shape[2]
    if n > 1:
        q, k = (jnp.repeat(t, n, axis=2) for t in (q, k))
    if g.ndim == 3:
        g = g[..., None]
    q, k, v, g, beta = (jnp.moveaxis(t.astype(f32), 1, 0)
                        for t in (q, k, v, g, beta))

    def step(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        state = jnp.exp(g_t)[..., None] * state
        kept = jnp.einsum("...k,...kv->...v", k_t, state)
        state = state + b_t[..., None, None] * k_t[..., None] \
            * (v_t - kept)[..., None, :]
        return state, jnp.einsum("...k,...kv->...v", q_t, state)

    state = jnp.zeros((*q.shape[1:], v.shape[-1]), f32)
    _, out = lax.scan(step, state, (q, k, v, g, beta))
    return jnp.moveaxis(out, 0, 1)
