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

``dropless_moe_ffn`` is the other layer: top-k experts, **no capacity and no
dropped token**. The k T (token, expert) assignments are sorted by expert,
the rows gathered in that order, and the experts are grouped matmuls over
ragged groups (``ops/pallas/grouped_matmul.py``): three where the
parameters hold a gate beside the two projections (``w_gate``: the
SiLU-gated feed-forward of the Llama family), two where they do not
(``W2 act(W1 x)``, the activation named by the call: Nemotron-H's
``relu2``). Where the parameters hold a latent's two projections
(``latent_down``, ``latent_up``: LatentMoE) the experts work on ``x
latent_down`` and their weighted sum goes back through ``latent_up``, so
the rows gathered, multiplied and summed back are the latent's width and
not the hidden size. How a token's scores become its experts and their
weights is data of the call (``Scoring``: a softmax as it is, or a sigmoid
with a selection bias, renormalised and scaled). The layer holds every expert (one device, or
experts replicated under a ``data`` mesh), or the range of experts it is
told it holds, as one chip of an expert-parallel job does: it routes over
all of them, computes the part of the result its own experts give and
leaves the rest out (the exchange that would bring the other chips' parts
is ROADMAP B1; nothing here stands in for it). A shared expert, where the
parameters have one, is applied to every token. Both layers take their gate
from ``route`` and their balance term from ``balance_loss``. The capacity
path goes when the sharded dropless exchange lands (ROADMAP B1).
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from paddle_tpu.parallel.mesh import EXPERT_AXIS

__all__ = ["MoEConfig", "init_moe_params", "moe_ffn",
           "moe_param_specs", "Scoring", "route", "bias_step", "balance_loss",
           "dropless_moe_ffn", "held_passes"]


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


@dataclasses.dataclass(frozen=True)
class Scoring:
    """How router logits become a token's experts and their weights.
    ``activation``: "softmax" over all experts (Switch, OLMoE) or "sigmoid"
    of each logit (DeepSeek-V3, Kimi). ``renormalize``: the chosen scores
    divided by their sum. ``scale``: a constant on the weights. A selection
    bias is a parameter, not part of the rule: ``route`` takes it apart."""
    activation: str = "softmax"
    renormalize: bool = False
    scale: float = 1.0


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _biased_top_k(probs, bias, top_k):
    """The ``top_k`` largest of ``probs + bias`` along the last axis in
    ``lax.top_k``'s order (the total order of floats, the lower index first
    among equals), and ``probs`` at them: (top_p, top_e), bit for bit
    ``take_along_axis(probs, top_e)``. ``lax.top_k`` is a sort of (score,
    index) rows on the chip; this is that sort with ``probs`` carried as one
    more operand, so the chosen scores need no gather of k T scalars (10 ns
    each on a v5e: PERF.md section 6, PR 51 and PR 52). The key is the
    biased score as the integer that orders as it does, inverted: a sort of
    integers compares with one instruction, where jax's order of floats
    (zeros alike, NaNs last: not ``lax.top_k``'s) takes six. The gradient
    reaches ``probs`` alone, through the chosen scores; jax's own rule for a
    carried operand would scatter whole rows."""
    bits = jax.lax.bitcast_convert_type(probs + bias, jnp.int32)
    key = ~jnp.where(bits < 0, bits ^ 0x7FFFFFFF, bits)
    index = jax.lax.broadcasted_iota(jnp.int32, probs.shape, probs.ndim - 1)
    _, scores, index = jax.lax.sort(
        (key, probs, index), dimension=-1, num_keys=1, is_stable=True)
    return scores[..., :top_k], index[..., :top_k]


def _biased_top_k_fwd(probs, bias, top_k):
    top_p, top_e = _biased_top_k(probs, bias, top_k)
    return (top_p, top_e), (top_e, bias)


def _biased_top_k_bwd(top_k, kept, cotangents):
    top_e, bias = kept
    # a row's experts are distinct, so each score's gradient is one term of
    # the sum and the others are zeros: the bits of a scatter-add, and a
    # pass over [T, k, E] in place of XLA's sort and scatter of k T scalars
    chosen = top_e[..., None] == jnp.arange(bias.shape[0], dtype=top_e.dtype)
    d_probs = jnp.sum(jnp.where(chosen, cotangents[0][..., None], 0.0),
                      axis=-2)
    return d_probs, jnp.zeros_like(bias)


_biased_top_k.defvjp(_biased_top_k_fwd, _biased_top_k_bwd)


def route(x32, gate_w, top_k, scoring=Scoring(), bias=None):
    """The gate both layers share: float32 logits ``x gate_w`` [T, E] at
    full precision (on a TPU a float32 product is otherwise rounded to
    bfloat16, which flips close choices), their scores (``scoring``), and
    the ``top_k`` experts of each token with their weights. With ``bias``
    [E] the experts are the largest ``scores + bias`` and the weights are
    the scores without it, carried through the selection's own sort
    (``_biased_top_k``); the bias is outside the gradient.
    Returns (logits, scores, top_p [T, k], top_e [T, k]). Three stage scopes
    (``dropless_moe_ffn`` lists all seven): ``router_logits``,
    ``router_scores``, ``router_select``."""
    with jax.named_scope("router_logits"):
        logits = jnp.dot(x32, gate_w.astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
    with jax.named_scope("router_scores"):
        probs = jax.nn.softmax(logits, axis=-1) \
            if scoring.activation == "softmax" else jax.nn.sigmoid(logits)
    with jax.named_scope("router_select"):
        if bias is None:
            top_p, top_e = jax.lax.top_k(probs, top_k)
        else:
            top_p, top_e = _biased_top_k(probs, bias.astype(jnp.float32),
                                         top_k)
    with jax.named_scope("router_scores"):
        if scoring.renormalize:
            top_p = top_p / (jnp.sum(top_p, axis=-1, keepdims=True) + 1e-20)
        if scoring.scale != 1.0:
            top_p = top_p * scoring.scale
    return logits, probs, top_p, top_e


def _counts(top_e, experts):
    """The assignments each of ``experts`` experts took, [E] int32:
    ``bincount`` of ``top_e`` as a compare against the experts' numbers
    summed over tokens and choices, one fused pass where ``bincount`` is a
    scatter-add of k T indices one at a time (9 ns each on a v5e)."""
    chosen = top_e[..., None] == jnp.arange(experts, dtype=top_e.dtype)
    return jnp.sum(chosen, axis=tuple(range(top_e.ndim)), dtype=jnp.int32)


def bias_step(bias, counts, rate):
    """The selection bias after one step of the rule that balances a router
    without a loss term (DeepSeek-V3, arXiv:2412.19437, sec. 2.1.2): an
    expert that took more than the mean of the assignments loses ``rate``,
    one that took fewer gains it. ``counts`` [..., E] over all the router's
    experts, as ``dropless_moe_ffn`` returns them."""
    counts = counts.astype(jnp.float32)
    return bias + rate * jnp.sign(
        jnp.mean(counts, axis=-1, keepdims=True) - counts)


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


#: what ``activation`` may name beside "silu", the gate of a gated expert:
#: the non-linearity of an expert (and of the shared expert) that has no gate
ACTIVATIONS = {"relu2": lambda h: jnp.square(jax.nn.relu(h))}


def _feed(x, weights, activation, matmul=jnp.matmul):
    """``x`` through one feed-forward, or rows through their experts' where
    ``matmul`` is the grouped one. ``weights`` says which form: (gate, up,
    down) the SiLU-gated one, ``(silu(x gate) * (x up)) down``; (up, down)
    the plain one, ``activation(x up) down``."""
    from paddle_tpu.models.blocks import gated_ffn

    if len(weights) == 3:
        if activation != "silu":
            raise ValueError(f"a gated expert's gate is SiLU, not "
                             f"{activation!r}")
        return gated_ffn(x, *weights, matmul=matmul)
    up, down = (w.astype(x.dtype) for w in weights)
    return matmul(ACTIVATIONS[activation](matmul(x, up)), down)


def _experts(rows, weights, sizes, mesh, activation):
    """Rows sorted by expert through their experts' feed-forward (``_feed``:
    gated, three grouped matmuls over the groups ``sizes``, or plain,
    two)."""
    from paddle_tpu.ops.pallas.grouped_matmul import grouped_matmul
    from paddle_tpu.ops.pallas.registry import mesh_scope

    with jax.named_scope("moe_experts"), mesh_scope(mesh):
        return _feed(rows, weights, activation,
                     matmul=lambda a, w: grouped_matmul(a, w, sizes))


def _sum_back(y, rows, token, weight, mesh):
    """``y`` [T, D] float32 with a pass's ``rows``, each times its float32
    ``weight``, added into the rows of their tokens (``ops/pallas/
    moe_combine.py``: on one chip the rows in token order summed on the MXU,
    XLA's scatter-add under a mesh and on the CPU)."""
    from paddle_tpu.ops.pallas.moe_combine import moe_combine
    from paddle_tpu.ops.pallas.registry import mesh_scope

    with jax.named_scope("moe_dispatch"), \
            jax.named_scope("dispatch_combine"), mesh_scope(mesh):
        return moe_combine(y, rows, token, weight)


#: Rows of the held experts' assignments one pass of ``_held_experts``
#: takes. A pass gathers its rows, runs the grouped matmuls on them (three
#: for gated experts, two for plain ones) and adds their weighted outputs into the tokens' rows; the number of
#: passes follows the rows held (``ceil(rows / HELD_ROW_TILE)``), so nothing
#: is sized for the router's worst case and nothing is dropped. A blocking
#: size, not a limit: what a pass pays whatever its fill (the sort of a tile
#: of keys on the way back, the tiles the grouped matmuls zero, the gate's
#: elementwise pass, the weights' gradients added into their sum; the
#: gathers and the sum back, ``ops/pallas/moe_combine.py``, follow the rows
#: held, 4096 and 128 at a time) is paid once for up to 8192 rows, four
#: times what a balanced router sends to 8 of 256 experts from 8192 tokens,
#: and a pass's rows and products stay under 0.2 GiB (rows of the hidden
#: size; where the experts work on a latent the rows are that wide: 1024
#: for Nemotron-H's, 16 MiB a pass in bfloat16 beside products of 2688).
#: With 14% of the
#: assignments held (9218 rows in the fullest layer: two passes) the Kimi
#: Linear step is 5.6 ms longer than with 4.4%, 4.2 ms of it the experts'
#: products on their rows (PERF.md section 6, PR 30).
HELD_ROW_TILE = 8192


def _held_row_tile(assignments, held, experts):
    """Rows a pass for a layer that holds ``held`` of ``experts`` and sees
    ``assignments`` (token, expert) pairs a step: ``HELD_ROW_TILE``, or as
    many of them as take twice what a balanced router sends here. A router
    at par must not sit on a pass's edge, nor within reach of one: 32 of 256
    experts and 8 x 16 384 assignments are 16 384 rows at par, two tiles to
    the row, and the batch's own noise (120 rows) then decides between two
    passes and three; a layer's share of the assignments also differs by
    two or three points from batch to batch and by seed around its 12.5%,
    and a second pass of 24 576 rows was 14 ms of a 705 ms step in the Laguna
    cell whatever it holds (PERF.md section 6, PR 33; a nearly empty second
    pass of 32 768 rows is 13 ms a layer since PR 41, section 6). With
    32 768 rows a pass it is one pass up to a share of 25%. 8 of 256 and
    8 x 8192 are 2048 rows at par: one tile, as ever."""
    par = assignments * held // experts
    tiles = max(1, -(-2 * par // HELD_ROW_TILE))
    return min(HELD_ROW_TILE * tiles, assignments)


def held_passes(rows, tile):
    """Passes of ``tile`` rows that ``rows`` held assignments take,
    ``ceil(rows / tile)``: the trip count of ``_held_experts``' loop, forward
    and backward, and what a reader of a step's counts (``step_fn.aux``)
    calls to say how many passes that step ran. ``rows`` a jax or a NumPy
    value, a scalar or one number an expert layer."""
    return -(-rows // tile)


def _held_pass(i, order, top_p, sizes, tile):
    """What pass ``i`` works on: the places [tile] its assignments have in
    (token, choice) order, their weights (0 past the rows held), the rows
    each held expert has inside this pass [n], and how many that is."""
    lo = i * tile
    at = jax.lax.dynamic_slice(order, (lo,), (tile,))
    ends = jnp.cumsum(sizes)
    weight = jnp.where(lo + jnp.arange(tile) < ends[-1],
                       jnp.take(top_p.reshape(-1), at), 0.0)
    part = jnp.clip(jnp.minimum(ends, lo + tile)
                    - jnp.maximum(ends - sizes, lo), 0, None)
    return at, weight, part, jnp.clip(ends[-1] - lo, 0, tile)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _held_experts(xt, top_p, weights, order, sizes, top_k, mesh, tile,
                  activation):
    """The experts' part for a layer that holds a share of them, [T, D]
    float32. ``order`` [a multiple of ``tile``] lists the assignments with
    the held ones first, sorted by expert; ``sizes`` [n] how many each held
    expert took. The rows are worked ``tile`` at a time in a loop whose trip
    count is ``ceil(sum(sizes) / tile)``, forward and backward, so the time
    follows the rows held. The backward pass keeps the tokens, the order and
    the scores and makes each pass's rows and products again. A pass's rows
    come back into their tokens' rows through ``_sum_back``, forward (the
    experts' outputs times their scores into ``y``) and backward (the rows'
    gradients into ``dx``): on one chip no XLA scatter of rows is left in
    either direction, only the scores' gradient's scatter of a tile of
    scalars (0.13 to 0.32 ms a pass: PERF.md section 6, PR 41)."""
    from paddle_tpu.ops.pallas.moe_combine import rows_held

    passes = held_passes(jnp.sum(sizes), tile)

    def one(i, y):
        with jax.named_scope("moe_dispatch"):
            with jax.named_scope("dispatch_order"):
                at, weight, part, held = _held_pass(i, order, top_p, sizes,
                                                    tile)
            with jax.named_scope("dispatch_gather"):
                token = at // top_k
                rows = rows_held(xt, token, held)
        out = _experts(rows, weights, part, mesh, activation)
        return _sum_back(y, out, token, weight, mesh)

    return jax.lax.fori_loop(0, passes, one,
                             jnp.zeros(xt.shape, jnp.float32))


def _held_fwd(xt, top_p, weights, order, sizes, top_k, mesh, tile,
              activation):
    return (_held_experts(xt, top_p, weights, order, sizes, top_k, mesh,
                          tile, activation),
            (xt, top_p, weights, order, sizes))


def _held_bwd(top_k, mesh, tile, activation, kept, dy):
    from paddle_tpu.ops.pallas.moe_combine import rows_held

    xt, top_p, weights, order, sizes = kept
    passes = held_passes(jnp.sum(sizes), tile)

    def one(i, grads):
        dx, dp, dw = grads
        with jax.named_scope("moe_dispatch"):
            with jax.named_scope("dispatch_order"):
                at, weight, part, held = _held_pass(i, order, top_p, sizes,
                                                    tile)
            with jax.named_scope("dispatch_gather"):
                token = at // top_k
                rows = rows_held(xt, token, held)
                dy_rows = rows_held(dy, token, held).astype(jnp.float32)
        out, back = jax.vjp(
            lambda r, w: _experts(r, w, part, mesh, activation), rows,
            weights)
        d_rows, dw_pass = back((dy_rows * weight[:, None]).astype(out.dtype))
        with jax.named_scope("moe_dispatch"), \
                jax.named_scope("dispatch_combine"):
            # a row past the rows held came out zero: its score gets nothing
            dp = dp.at[at].add(jnp.sum(out.astype(jnp.float32) * dy_rows,
                                       axis=-1))
        # and a zero cotangent went back through it: its gradient is zero
        dx = _sum_back(dx, d_rows, token, (weight != 0).astype(jnp.float32),
                       mesh)
        return dx, dp, jax.tree.map(jnp.add, dw, dw_pass)

    dx, dp, dw = jax.lax.fori_loop(0, passes, one, (
        jnp.zeros(xt.shape, jnp.float32), jnp.zeros(top_p.size, jnp.float32),
        jax.tree.map(jnp.zeros_like, weights)))
    return (dx.astype(xt.dtype), dp.reshape(top_p.shape).astype(top_p.dtype),
            dw, None, None)


_held_experts.defvjp(_held_fwd, _held_bwd)


def dropless_moe_ffn(params, x, top_k, mesh=None, scoring=Scoring(),
                     held=None, activation="silu"):
    """Top-k experts with every assignment computed.

    x: [..., d_model], leading dims flattened as T tokens. ``params``:
    ``router_w`` [D, E], and the experts' stacks, no biases: ``w_gate`` and
    ``w_up`` [n, D, F] with ``w_down`` [n, F, D], SiLU-gated experts; or,
    without ``w_gate``, plain ones whose non-linearity ``activation`` names
    (``ACTIVATIONS``). Optionally ``router_bias`` [E], the selection bias of
    ``route``; a shared expert every token takes, of the same two forms
    (``shared_gate`` / ``shared_up`` / ``shared_down``, [D, F], [D, F],
    [F, D], or ``shared_up`` and ``shared_down`` alone): with weight 1, or,
    where the parameters have ``shared_scale_w`` [D], with the weight
    ``sigmoid(x . shared_scale_w)``, a scalar a token (float32; no relation
    of ``shared_gate``, which is the shared expert's SiLU branch); and
    ``latent_down`` [D, L] with ``latent_up`` [L, D] (LatentMoE): the routed
    experts then work on ``l = x latent_down``, their stacks are [n, L, F]
    and [n, F, L], and the weighted sum of their outputs goes back through
    ``latent_up``; the router and the shared expert stay on x. Per token
    ``sum_e w_e * w_down_e (silu(w_gate_e x) * w_up_e x)``, or ``sum_e w_e *
    w_down_e act(w_up_e x)``, over its ``top_k`` experts, chosen and
    weighted as ``scoring`` says (default: the largest softmax
    probabilities, NOT renormalised). Router in float32, experts in
    ``x.dtype``.

    ``held`` = (first, n): this layer holds the experts ``first`` to
    ``first + n - 1`` of the router's E, and the stacks have n matrices. It
    routes over all E, computes the assignments that fall on its own and
    leaves the others out of ``y`` (another chip's part). ``None``: all E.
    The assignments held come first in expert order and are worked
    ``_held_row_tile`` rows a pass, as many passes as they need
    (``_held_experts``): one path, whose time follows the rows held, with
    nothing sized for a router's worst case and nothing dropped. The
    backward pass makes each pass's rows and products again (the tokens,
    the order and the scores are kept, not the rows).

    Returns (y, aux): ``aux["balance"]`` as ``balance_loss``, ``aux["z"]``
    the router z-loss ``mean(logsumexp(logits)^2)``, ``aux["counts"]`` [E]
    the assignments each expert took, held or not (they sum to ``top_k *
    T``), ``aux["choice"]`` [T, top_k] the experts of each token.

    Under a mesh the grouped matmul takes the body GSPMD can partition
    (``mesh_scope``). Named scopes, inside the caller's ``ffn``:
    ``moe_router``, ``moe_dispatch``, ``moe_experts``, ``moe_shared``, and
    ``moe_latent`` around the latent's two projections. Every operation
    under ``moe_router`` or ``moe_dispatch`` is under one stage scope
    besides, forward and backward. In ``moe_router``: ``router_logits`` (the
    float32 product at ``HIGHEST``; its two gradient products),
    ``router_scores`` (the softmax or sigmoid, the renormalisation and the
    scale of the chosen scores), ``router_select`` (``top_k``, or with a
    bias the one sort that hands out the chosen experts and their scores;
    backward their gradient spread over [T, E] by a compare: no gather, no
    scatter), ``router_stats`` (the counts, a compare and a sum, the balance
    term, the z term). In
    ``moe_dispatch``: ``dispatch_order`` (the keys and their sorts, and in
    every pass over held rows what the pass works on, ``_held_pass``),
    ``dispatch_gather`` (the rows, and their gradients, in expert order),
    ``dispatch_combine`` (the rows back in token order and summed by their
    scores, ``_sum_back`` both ways, the scores' gradient). The passes a
    step ran over held rows are ``held_passes`` of its counts."""
    shape = x.shape
    xt = x.reshape(-1, shape[-1])
    e = params["router_w"].shape[-1]
    with jax.named_scope("moe_router"):
        with jax.named_scope("router_logits"):
            x32 = xt.astype(jnp.float32)
        logits, probs, top_p, top_e = route(
            x32, params["router_w"], top_k, scoring,
            params.get("router_bias"))
        with jax.named_scope("router_stats"):
            counts = _counts(top_e, e)
            aux = {"balance": balance_loss(probs, counts),
                   "z": jnp.mean(jnp.square(
                       jax.nn.logsumexp(logits, axis=-1))),
                   "counts": counts, "choice": top_e}
    weights = tuple(params[name] for name in ("w_gate", "w_up", "w_down")
                    if name in params)
    rows_of = xt                      # what the routed experts work on
    if "latent_down" in params:
        with jax.named_scope("moe_latent"):
            rows_of = xt @ params["latent_down"].astype(xt.dtype)
    if held is None:
        with jax.named_scope("moe_dispatch"):
            with jax.named_scope("dispatch_order"):
                order = jnp.argsort(top_e.reshape(-1), stable=True)  # [k T]
                inverse = jnp.argsort(order)
            with jax.named_scope("dispatch_gather"):
                rows = _rows_in_expert_order(rows_of, order, inverse, top_k)
        out = _experts(rows, weights, counts, mesh, activation)
        with jax.named_scope("moe_dispatch"), \
                jax.named_scope("dispatch_combine"):
            out = _rows_in_token_order(out, order, inverse)
            y = jnp.sum(out.reshape(-1, top_k, rows_of.shape[-1])
                        .astype(jnp.float32) * top_p[..., None], axis=1)
    else:
        first, n = held
        with jax.named_scope("moe_dispatch"), \
                jax.named_scope("dispatch_order"):
            here = (top_e >= first) & (top_e < first + n)
            key = jnp.where(here, top_e - first, n)     # the others: last
            order = jnp.argsort(key.reshape(-1), stable=True)
            tile = _held_row_tile(order.shape[0], n, e)
            order = jnp.pad(order, (0, -order.shape[0] % tile))
        y = _held_experts(rows_of, top_p, weights, order,
                          counts[first:first + n], top_k, mesh, tile,
                          activation)
    if "latent_up" in params:
        with jax.named_scope("moe_latent"):
            y = jnp.dot(y.astype(xt.dtype),
                        params["latent_up"].astype(xt.dtype),
                        preferred_element_type=jnp.float32)
    if "shared_up" in params:
        with jax.named_scope("moe_shared"):
            shared = _feed(xt, tuple(
                params[name] for name in ("shared_gate", "shared_up",
                                          "shared_down") if name in params),
                activation).astype(jnp.float32)
            if "shared_scale_w" in params:
                shared = shared * jax.nn.sigmoid(jnp.dot(
                    xt, params["shared_scale_w"].astype(xt.dtype),
                    preferred_element_type=jnp.float32))[:, None]
            y = y + shared
    return y.reshape(shape).astype(x.dtype), aux
