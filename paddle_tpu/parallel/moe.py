"""Expert-parallel Mixture-of-Experts FFN (GShard/Switch-style).

Beyond the reference (the 2019 codebase has no MoE — SURVEY §2.5 lists
EP alongside TP/SP as TPU-build stretch): a top-k gated expert FFN
whose experts shard over the mesh's "expert" axis
(MeshConfig(expert=N)). Routing uses the dense-dispatch formulation —
one-hot dispatch/combine einsums over a capacity-bucketed layout — so
under pjit/GSPMD the token exchange lowers to all_to_all collectives
on ICI, the TPU-native shape of expert parallelism; there is no
host-side router.

Semantics (Switch/GShard defaults): softmax gate over experts, top-k
(k=1 or 2) selection, per-expert capacity
C = ceil(k * tokens * capacity_factor / num_experts); tokens beyond an
expert's capacity are dropped (their combine weight is zero, the
residual path carries them); combine weights renormalize over the
selected experts. An auxiliary load-balancing loss (mean gate fraction
x mean dispatch fraction x num_experts, Switch eq. 4) is returned for
the caller to add.

``dropless_moe_ffn`` is the other layer: gated experts, top-k, **no
capacity and no dropped token**, for one device or experts replicated under
a ``data`` mesh. The k T (token, expert) assignments are sorted by expert,
the rows gathered in that order, and the experts are three grouped matmuls
over ragged groups (``ops/pallas/grouped_matmul.py``). Both layers take
their gate from ``route`` and their balance term from ``balance_loss``.
The capacity path goes when the sharded dropless exchange lands (ROADMAP
B4).
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from paddle_tpu.parallel.mesh import EXPERT_AXIS

__all__ = ["MoEConfig", "init_moe_params", "moe_ffn",
           "moe_param_specs", "route", "balance_loss", "dropless_moe_ffn"]


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    d_model: int
    d_hidden: int
    num_experts: int
    top_k: int = 2
    capacity_factor: float = 1.25
    dtype: object = jnp.float32

    def capacity(self, tokens):
        return max(int(np.ceil(self.top_k * tokens
                               * self.capacity_factor
                               / self.num_experts)), 1)


def init_moe_params(rng, cfg):
    kg, k1, k2 = jax.random.split(rng, 3)
    s1 = 1.0 / np.sqrt(cfg.d_model)
    s2 = 1.0 / np.sqrt(cfg.d_hidden)
    return {
        "gate_w": (jax.random.normal(kg, (cfg.d_model, cfg.num_experts))
                   * s1).astype(jnp.float32),
        "w1": (jax.random.normal(
            k1, (cfg.num_experts, cfg.d_model, cfg.d_hidden))
            * s1).astype(jnp.float32),
        "b1": jnp.zeros((cfg.num_experts, cfg.d_hidden), jnp.float32),
        "w2": (jax.random.normal(
            k2, (cfg.num_experts, cfg.d_hidden, cfg.d_model))
            * s2).astype(jnp.float32),
        "b2": jnp.zeros((cfg.num_experts, cfg.d_model), jnp.float32),
    }


def moe_param_specs():
    """PartitionSpecs: experts shard over the "expert" axis; the gate
    replicates (every token scores every expert)."""
    return {
        "gate_w": P(),
        "w1": P(EXPERT_AXIS, None, None),
        "b1": P(EXPERT_AXIS, None),
        "w2": P(EXPERT_AXIS, None, None),
        "b2": P(EXPERT_AXIS, None),
    }


def moe_sharding_spec(mesh=None):
    """The MoE placement as the unified ShardingSpec (parallel/spec.py)
    — same entries as ``moe_param_specs``, usable for executor interop
    and ``checkpoint_axes`` (experts tile dim 0 over "expert")."""
    from paddle_tpu.parallel.spec import ShardingSpec
    return ShardingSpec(mesh, params=moe_param_specs())


def route(x32, gate_w, top_k):
    """The gate both layers share: float32 logits ``x gate_w`` [T, E] at
    full precision (on a TPU a float32 product is otherwise rounded to
    bfloat16, which flips close choices), their softmax, and the ``top_k``
    largest probabilities of each token with the experts they belong to.
    Returns (logits, probs, top_p [T, k], top_e [T, k])."""
    logits = jnp.dot(x32, gate_w.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, top_k)
    return logits, probs, top_p, top_e


def balance_loss(probs, counts):
    """Switch eq. 4: ``E * sum_e f_e P_e``, ``f_e`` the share of the
    assignments that went to expert e (``counts`` [E], before any drop) and
    ``P_e`` its mean gate probability. 1 for a uniform router, E for one
    that sends everything to one expert."""
    share = counts.astype(jnp.float32) / jnp.sum(counts)
    return probs.shape[-1] * jnp.sum(share * jnp.mean(probs, axis=0))


def moe_ffn(params, cfg, x, mesh=None):
    """x: [..., T, d_model] (leading dims flattened as tokens).
    Returns (y, aux_loss). Under a mesh with an "expert" axis and
    params placed per moe_param_specs, the ecd/ted einsums lower to
    all_to_all dispatch/combine over ICI."""
    shape = x.shape
    t = int(np.prod(shape[:-1]))
    xt = x.reshape(t, cfg.d_model).astype(jnp.float32)
    e, c = cfg.num_experts, cfg.capacity(t)

    _, gates, _, top_e = route(xt, params["gate_w"], cfg.top_k)  # [T, E]
    sel = jax.nn.one_hot(top_e, e, dtype=gates.dtype)          # [T,K,E]

    # position of each (token, k) inside its expert's capacity bucket:
    # cumulative count of prior claims on that expert. GShard/Switch
    # priority order: ALL top-1 claims outrank any top-2 claim, so the
    # flatten must be k-major ([K,T,E]) before the cumsum — a
    # token-major flatten would let an early token's 2nd choice evict a
    # later token's 1st choice.
    claims = sel.transpose(1, 0, 2).reshape(cfg.top_k * t, e)  # [K*T, E]
    pos = (jnp.cumsum(claims, axis=0) - claims)            # claims before
    pos = jnp.sum(pos * claims, axis=-1).reshape(cfg.top_k, t).T
    within = (pos < c).astype(gates.dtype)                 # capacity drop
    kept = sel * within[..., None]                         # [T, K, E]

    # dispatch tensor [T, E, C]: claim -> capacity slot one-hot
    slot = jax.nn.one_hot(pos.astype(jnp.int32), c,
                          dtype=gates.dtype)               # [T, K, C]
    dispatch = jnp.einsum("tke,tkc->tec", kept, slot)      # [T, E, C]

    # combine weights: gate prob of each kept claim, renormalized over
    # the token's kept experts
    gk = jnp.einsum("te,tke->tk", gates, kept)             # [T, K]
    denom = jnp.maximum(jnp.sum(gk, axis=-1, keepdims=True), 1e-9)
    gk = gk / denom
    combine = jnp.einsum("tk,tke,tkc->tec", gk, kept, slot)

    # route -> expert FFN -> return (all_to_all under GSPMD)
    xin = jnp.einsum("tec,td->ecd", dispatch, xt)          # [E, C, D]
    if mesh is not None and EXPERT_AXIS in mesh.shape:
        xin = jax.lax.with_sharding_constraint(
            xin, NamedSharding(mesh, P(EXPERT_AXIS, None, None)))
    h = jax.nn.relu(jnp.einsum("ecd,edh->ech", xin, params["w1"])
                    + params["b1"][:, None, :])
    out = jnp.einsum("ech,ehd->ecd", h, params["w2"]) \
        + params["b2"][:, None, :]
    if mesh is not None and EXPERT_AXIS in mesh.shape:
        out = jax.lax.with_sharding_constraint(
            out, NamedSharding(mesh, P(EXPERT_AXIS, None, None)))
    y = jnp.einsum("tec,ecd->td", combine, out)            # [T, D]

    # the dispatch fraction uses the PRE-drop assignment (`sel`, as
    # Switch/GShard define it) — computing it post-drop caps the
    # overloaded expert's fraction at C/T, which masks (and slightly
    # rewards) collapse exactly when drops begin.
    aux = balance_loss(gates, jnp.sum(sel, axis=(0, 1)))

    return y.reshape(shape).astype(x.dtype), aux


# ---------------------------------------------------------------------------
# dropless
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _rows_in_expert_order(x, order, inverse, top_k):
    """``x[order // top_k]``: token t's row at each of the ``top_k`` places
    its assignments take in expert order. ``inverse`` is the inverse
    permutation, so the gradient is a gather too and never a scatter."""
    return jnp.take(x, order // top_k, axis=0)


def _rows_fwd(x, order, inverse, top_k):
    return _rows_in_expert_order(x, order, inverse, top_k), inverse


def _rows_bwd(top_k, inverse, dy):
    dx = jnp.take(dy, inverse, axis=0).reshape(-1, top_k, dy.shape[-1])
    return jnp.sum(dx.astype(jnp.float32), axis=1).astype(dy.dtype), \
        None, None


_rows_in_expert_order.defvjp(_rows_fwd, _rows_bwd)


@jax.custom_vjp
def _rows_in_token_order(y, order, inverse):
    """``y[inverse]``: the experts' rows back in (token, choice) order."""
    return jnp.take(y, inverse, axis=0)


_rows_in_token_order.defvjp(
    lambda y, order, inverse: (jnp.take(y, inverse, axis=0), order),
    lambda order, dy: (jnp.take(dy, order, axis=0), None, None))


def dropless_moe_ffn(params, x, top_k, mesh=None):
    """Gated top-k experts with every assignment computed.

    x: [..., d_model], leading dims flattened as T tokens. ``params``:
    ``router_w`` [D, E], ``w_gate`` and ``w_up`` [E, D, F], ``w_down``
    [E, F, D]; no biases. Per token ``sum_e p_e * w_down_e (silu(w_gate_e
    x) * w_up_e x)`` over its ``top_k`` largest softmax probabilities, NOT
    renormalised. Router in float32, experts in ``x.dtype``.

    Returns (y, aux): ``aux["balance"]`` as ``balance_loss``, ``aux["z"]``
    the router z-loss ``mean(logsumexp(logits)^2)``, ``aux["counts"]`` [E]
    the assignments each expert took (they sum to ``top_k * T``),
    ``aux["choice"]`` [T, top_k] the experts of each token.

    The experts are replicated: under a mesh the grouped matmul takes the
    body GSPMD can partition (``mesh_scope``). Named scopes, inside the
    caller's ``ffn``: ``moe_router``, ``moe_dispatch``, ``moe_experts``."""
    from paddle_tpu.models.blocks import gated_ffn
    from paddle_tpu.ops.pallas.grouped_matmul import grouped_matmul
    from paddle_tpu.ops.pallas.registry import mesh_scope

    shape = x.shape
    xt = x.reshape(-1, shape[-1])
    e = params["router_w"].shape[-1]
    with jax.named_scope("moe_router"):
        logits, probs, top_p, top_e = route(
            xt.astype(jnp.float32), params["router_w"], top_k)
        counts = jnp.bincount(top_e.reshape(-1), length=e)
        aux = {"balance": balance_loss(probs, counts),
               "z": jnp.mean(jnp.square(
                   jax.nn.logsumexp(logits, axis=-1))),
               "counts": counts, "choice": top_e}
    with jax.named_scope("moe_dispatch"):
        order = jnp.argsort(top_e.reshape(-1), stable=True)    # [k T]
        inverse = jnp.argsort(order)
        rows = _rows_in_expert_order(xt, order, inverse, top_k)
    with jax.named_scope("moe_experts"), mesh_scope(mesh):
        out = gated_ffn(
            rows, params["w_gate"], params["w_up"], params["w_down"],
            matmul=lambda a, w: grouped_matmul(a, w, counts))
    with jax.named_scope("moe_dispatch"):
        out = _rows_in_token_order(out, order, inverse)
        y = jnp.sum(out.reshape(-1, top_k, shape[-1]).astype(jnp.float32)
                    * top_p[..., None], axis=1)
    return y.reshape(shape).astype(x.dtype), aux
