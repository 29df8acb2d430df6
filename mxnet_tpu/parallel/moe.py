"""Mixture-of-experts layers: two routers, one file.

**Token-choice top-k on one chip's share** (``topk_choice``, ``topk_route``,
``held_expert_ffn``, ``token_choice_moe``) is the layer a Gluon model
reaches: ``gluon.nn.TokenChoiceMoE`` calls it through the
``moe_token_choice`` op, and ``model_zoo.glm_moe_lite`` builds its expert
blocks from that, ``model_zoo.nemotron_h`` its latent expert layers.  The
layer is told which experts it HOLDS, scores every token over ALL experts
(sigmoid scores, selection by score + bias, weights renormalised over the
chosen k and scaled), and computes the part of the result its own experts
give: assignments sorted by expert, one grouped product a projection
(``ops/grouped.py``: Pallas kernels that stop at the load on a TPU,
``lax.ragged_dot`` elsewhere), no capacity and no dropped token whatever
the imbalance.  An
expert is what its weights' shapes say - a gated SwiGLU or an ungated
squared-ReLU MLP - of whatever width its input has (the model width, or
a latent the layer projects to), which need not be the width the router
reads.  On one chip it runs without an exchange; what the absent
experts would add is left out, here and in the reference alike.

**Top-1 with capacity over an ``ep`` mesh axis** (``top1_dispatch``,
``moe_apply``, ``moe_parallel``) is the older, cross-chip half: experts
live sharded across ``ep`` (``e_local`` per device); tokens are top-1
routed, packed to a fixed per-expert capacity into a dense ``(T, E, C)``
dispatch, exchanged with TWO ``all_to_all`` collectives (dispatch, return)
and combined scaled by the gate probability.  Over-capacity tokens
contribute zeros (GShard/Switch).  No Gluon block reaches it; it is called
directly (``__graft_entry__.dryrun_multichip``).  Its exchange is to be
re-based onto the token-choice router (ROADMAP Queue 3).

Everything is jittable and differentiable; correctness is pinned against
per-token dense references (tests/test_parallel.py, tests/test_glm_moe_lite.py).
"""
from __future__ import annotations

import functools
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..base import recompute_keep
from ..ops import grouped

__all__ = ["moe_apply", "moe_parallel", "top1_dispatch", "topk_choice",
           "topk_route", "held_expert_ffn", "token_choice_moe"]


def top1_dispatch(gate_logits, n_experts: int, capacity: int):
    """Build dispatch/combine tensors for top-1 routing.

    gate_logits: (T, E).  Returns (dispatch (T,E,C) one-hot placement,
    combine (T,E,C) = dispatch * gate_prob, aux_loss scalar — the Switch
    load-balancing loss).
    """
    probs = jax.nn.softmax(gate_logits, axis=-1)
    expert = jnp.argmax(probs, axis=-1)                  # (T,)
    onehot = jax.nn.one_hot(expert, n_experts, dtype=probs.dtype)
    gate = jnp.sum(probs * onehot, axis=-1)              # (T,)
    # position of each token within its expert's queue (1-based at the
    # selected expert, 0 elsewhere; summing over E extracts it)
    pos = jnp.cumsum(onehot, axis=0) * onehot
    keep = (pos <= capacity) & (onehot > 0)
    position = pos.sum(axis=-1).astype(jnp.int32) - 1    # (T,), 0-based
    loc = jax.nn.one_hot(position, capacity, dtype=probs.dtype)  # (T, C)
    dispatch = loc[:, None, :] * keep.astype(probs.dtype)[:, :, None]
    combine = dispatch * gate[:, None, None]
    # Switch aux loss: E * sum_e (fraction tokens to e) * (mean prob to e)
    frac = onehot.mean(axis=0)
    mean_prob = probs.mean(axis=0)
    aux = n_experts * jnp.sum(frac * mean_prob)
    return dispatch, combine, aux


def moe_apply(x, gate_w, expert_params, *, expert_fn: Callable,
              axis_name: str = "ep", capacity_factor: float = 2.0):
    """Call INSIDE shard_map.  x: (T_local, d) tokens on this device;
    gate_w: (d, E) replicated; expert_params: this device's experts with
    leading axis e_local.  Returns (y (T_local, d), aux_loss)."""
    n = lax.psum(1, axis_name)
    e_local = jax.tree_util.tree_leaves(expert_params)[0].shape[0]
    n_experts = n * e_local
    if gate_w.shape[-1] != n_experts:
        raise ValueError(
            "moe: gate_w routes to %d experts but %d are stacked "
            "(%d devices x %d local)" % (gate_w.shape[-1], n_experts, n,
                                         e_local))
    t_local = x.shape[0]
    capacity = max(1, int(capacity_factor * t_local / n_experts))

    logits = x @ gate_w                                  # (T, E)
    dispatch, combine, aux = top1_dispatch(logits, n_experts, capacity)
    # pack: (E, C, d) expert-major token buffers
    xin = jnp.einsum("tec,td->ecd", dispatch, x)
    # dispatch all_to_all: every device keeps its e_local experts' buffers
    # from ALL devices -> (e_local, n*C, d)
    xin = lax.all_to_all(xin, axis_name, split_axis=0, concat_axis=1,
                         tiled=True)
    yout = jax.vmap(expert_fn)(expert_params, xin)       # (e_local, n*C, d)
    # return all_to_all: back to (E, C, d) token-origin layout
    yout = lax.all_to_all(yout, axis_name, split_axis=1, concat_axis=0,
                          tiled=True)
    y = jnp.einsum("tec,ecd->td", combine, yout)
    return y, lax.pmean(aux, axis_name)


def moe_parallel(expert_fn: Callable, mesh: Mesh, *, ep_axis: str = "ep",
                 capacity_factor: float = 2.0):
    """User-facing wrapper: apply(x, gate_w, stacked_expert_params) with
    x (tokens, d) sharded over ``ep_axis``, experts stacked on a leading
    axis of size n_devices*e_local and sharded over ``ep_axis``.
    Returns (y, aux_loss)."""

    def inner(x, gate_w, expert_params):
        return moe_apply(x, gate_w, expert_params, expert_fn=expert_fn,
                         axis_name=ep_axis,
                         capacity_factor=capacity_factor)

    def apply(x, gate_w, stacked_expert_params):
        espec = jax.tree_util.tree_map(lambda _: P(ep_axis),
                                       stacked_expert_params)
        mapped = shard_map(inner, mesh=mesh,
                           in_specs=(P(ep_axis), P(), espec),
                           out_specs=(P(ep_axis), P()))
        return mapped(x, gate_w, stacked_expert_params)

    return apply


# ---------------------------------------------------------------------------
# Token-choice top-k routing over ALL experts, computed for the experts HELD
# here.  No capacity: an assignment is never dropped.
# ---------------------------------------------------------------------------


def _chose(idx, n_experts):
    """(N, k, E) bool: choice j of token n is expert e.  Never held in
    memory: what reads it reduces it inside its own fusion."""
    return idx[:, :, None] == lax.broadcasted_iota(
        idx.dtype, (1, 1, n_experts), 2)


def _pick(table, idx):
    """``table[n, idx[n, j]]`` (N, k) out of (N, E) as a compare of `idx`
    against the expert axis and a sum over it: one term of each sum is not
    zero, so the result is a gather's bit for bit."""
    return jnp.where(_chose(idx, table.shape[-1]), table[:, None, :],
                     jnp.zeros((), table.dtype)).sum(-1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _chosen_scores(logits, idx, n_experts):
    """``sigmoid(logits)[n, idx[n, j]]`` (N, k), without a gather: a TPU
    walks a gather of N * k scalars one index at a time (7-10 ns each;
    PERF.md section 6, PR 36), where `_pick`'s compare and sum run at the
    vector units' width.  The cotangent of `logits` is built from the
    chosen scores alone - ``g s (1 - s)`` spread by the same compare,
    summed over the choices: a token's k experts are distinct, so each (n,
    e) receives at most one term, which is what the gather's transpose, a
    serialised scatter-add into the table, would have put there.  A
    recomputed block keeps the chosen scores beside the choice (4 N k
    bytes): its second run then holds no top-k, no selection and - the
    backward pass reads nothing of the (N, E) table - no router product."""
    return recompute_keep(_pick(jax.nn.sigmoid(logits), idx))


def _chosen_scores_fwd(logits, idx, n_experts):
    # tagged here too, and handed on as output AND residual (as the flash
    # kernels' results are: ops/attention.py:_kept)
    chosen = recompute_keep(_pick(jax.nn.sigmoid(logits), idx), count=False)
    return chosen, (idx, chosen)


def _chosen_scores_bwd(n_experts, res, g):
    idx, chosen = res
    return jnp.where(_chose(idx, n_experts),
                     (g * chosen * (1 - chosen))[:, :, None],
                     jnp.zeros((), g.dtype)).sum(1), None


_chosen_scores.defvjp(_chosen_scores_fwd, _chosen_scores_bwd)


def topk_choice(x, router_w, bias, top_k: int):
    """Every token's logits over ALL experts and the k it chooses by their
    sigmoid.

    x: (N, d) tokens; router_w: (E, d); bias: (E,) selection-only
    correction (``noaux_tc``: it picks, it does not weigh, and it gets no
    gradient).  Float32 at the highest matmul precision whatever the
    inputs' dtype: a near-tie between the k-th and the next score must not
    be decided by a rounded product.  Returns (idx (N, k) int32, logits
    (N, E) float32)."""
    with jax.default_matmul_precision("highest"):
        logits = jnp.dot(x.astype(jnp.float32),
                         router_w.astype(jnp.float32).T)
    _, idx = lax.top_k(
        jax.nn.sigmoid(logits)
        + lax.stop_gradient(bias.astype(jnp.float32)), top_k)
    # a recomputed block keeps the choice of its forward pass: run again,
    # a near-tie can fall the other way (base.RECOMPUTE_KEEP)
    return recompute_keep(idx.astype(jnp.int32)), logits


def topk_route(x, router_w, bias, top_k: int, scale: float = 1.0,
               norm_topk_prob: bool = True):
    """`topk_choice` and the weights of the chosen: (idx (N, k) int32,
    weights (N, k) float32 = scale * s / sum of the chosen s)."""
    idx, logits = topk_choice(x, router_w, bias, top_k)
    chosen = _chosen_scores(logits, idx, logits.shape[-1])
    if norm_topk_prob:
        chosen = chosen / (chosen.sum(-1, keepdims=True) + 1e-20)
    return idx, chosen * scale


@jax.custom_vjp
def _take_rows(x, take, back, n_valid):
    """out[r] = x[take[r]] for r < n_valid, else 0.  `back` (rows of x, j)
    lists for every row of x the positions r that took it (any value
    >= n_valid: none): the transpose is then a gather too, where a plain
    ``x[take]`` would transpose to a scatter-add, which a TPU serialises."""
    del back
    rows = jnp.arange(take.shape[0]) < n_valid
    return jnp.where(rows[:, None], x[take], jnp.zeros((), x.dtype))


def _take_rows_fwd(x, take, back, n_valid):
    return _take_rows(x, take, back, n_valid), (take, back, n_valid)


def _take_rows_bwd(res, g):
    take, back, n_valid = res
    return _sum_rows(g, back, take, n_valid), None, None, None


@jax.custom_vjp
def _sum_rows(rows, back, take, n_valid):
    """out[i] = sum over j of rows[back[i, j]] where back[i, j] < n_valid:
    the transpose of `_take_rows`, and a gather as well."""
    ok = back < n_valid
    got = rows[jnp.where(ok, back, 0)]                  # (I, j, d)
    return jnp.where(ok[..., None], got, jnp.zeros((), rows.dtype)) \
        .astype(jnp.float32).sum(1).astype(rows.dtype)


def _sum_rows_fwd(rows, back, take, n_valid):
    return _sum_rows(rows, back, take, n_valid), (back, take, n_valid)


def _sum_rows_bwd(res, g):
    back, take, n_valid = res
    return _take_rows(g, take, back, n_valid), None, None, None


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)
_sum_rows.defvjp(_sum_rows_fwd, _sum_rows_bwd)


EXPERT_ACTIVATIONS = ("swiglu", "relu2")

# The rows after the sort come in two sizes, chosen on the device.  The
# short one is this many times an even router's share of the assignments
# (rounded up to _SHORT_UNIT rows): balanced routers read 1.04-1.15 x the
# even share in both benchmark cells (PERF.md section 5), so 2 leaves the
# usual step well inside it, and `moe_exact_buffer_calls` counts the steps
# that were not.
_SHORT_OVER_EVEN = 2
_SHORT_UNIT = 512

def short_rows(n: int, k: int, h: int, n_experts: int):
    """The short buffer's rows for `n` tokens choosing `k` of `n_experts`
    with `h` held - twice an even router's share, a multiple of 512 - or
    None where it would not halve the exact bound ``n * min(k, h)``: the
    layer then has one path."""
    units = -(-_SHORT_OVER_EVEN * n * k * h // (n_experts * _SHORT_UNIT))
    short = units * _SHORT_UNIT
    return short if 0 < 2 * short <= n * min(k, h) else None


def _slot_of(experts, held):
    """(M,) int32: where each of `experts` (M,) stands in `held` (static
    global ids), ``len(held)`` where it is not among them."""
    h = len(held)
    hit = experts[None, :] == jnp.asarray(held, experts.dtype)[:, None]
    return jnp.where(hit, jnp.arange(h, dtype=jnp.int32)[:, None], h).min(0)


def _activate(up, activation):
    if activation == "swiglu":
        f = up.shape[-1] // 2
        return (jax.nn.silu(up[:, :f].astype(jnp.float32))
                * up[:, f:].astype(jnp.float32)).astype(up.dtype)
    return jnp.square(jax.nn.relu(up))


def _exact_stages(k, activation, order, position, places, counts):
    """The exact buffer's path - the first ``N * min(k, H)`` sorted rows -
    as its three stages, each a function of what it differentiates:
    dispatch (x, flat_w) -> (xs, ws), experts (xs, w_in, w_down) -> out,
    combine (out, ws) -> y."""
    here = counts.sum()
    taken = order[:places.size]

    def dispatch(x, flat_w):
        return (_take_rows(x, taken // k, places, here),
                _take_rows(flat_w, taken, position[:, None], here))

    def experts(xs, w_in, w_down):
        return grouped.grouped_product(
            _activate(grouped.grouped_product(xs, w_in, counts), activation),
            w_down, counts)

    def combine(out, ws):
        return _sum_rows((out.astype(jnp.float32) * ws).astype(out.dtype),
                         places, taken // k, here)

    return dispatch, experts, combine


def _exact_path(k, activation, x, flat_w, w_in, w_down, *indices):
    """The held experts' result over the exact no-drop buffer."""
    dispatch, experts, combine = _exact_stages(k, activation, *indices)
    with jax.named_scope("dispatch"):
        xs, ws = dispatch(x, flat_w)
    with jax.named_scope("experts"):
        out = experts(xs, w_in, w_down)
    with jax.named_scope("combine"):
        return combine(out, ws)


def _exact_backward(k, activation, x, flat_w, w_in, w_down, indices, g):
    """The cotangents of x, flat_w, w_in, w_down over the exact buffer,
    its forward run again.  Stage by stage, each stage's derivative taken
    INSIDE its scope: a ``jax.vjp`` of the whole path would name what it
    runs ``transpose(jvp(experts))``, which is no scope a reader of the
    `op_name` paths finds (`moe/experts` would lose the branch's
    kernels)."""
    dispatch, experts, combine = _exact_stages(k, activation, *indices)
    with jax.named_scope("dispatch"):
        (xs, ws), d_dispatch = jax.vjp(dispatch, x, flat_w)
    with jax.named_scope("experts"):
        out, d_experts = jax.vjp(experts, xs, w_in, w_down)
    with jax.named_scope("combine"):
        d_out, d_ws = jax.vjp(combine, out, ws)[1](g)
    with jax.named_scope("experts"):
        d_xs, d_w_in, d_w_down = d_experts(d_out)
    with jax.named_scope("dispatch"):
        d_x, d_flat_w = d_dispatch((d_xs, d_ws))
    return d_x, d_flat_w, d_w_in, d_w_down


class _Short:
    """The same result over the first `short` sorted rows, for a load
    `here` <= `short`; forward and backward written apart, so that what
    the backward reads of the forward is `short` rows long.  Tokens ->
    rows is a gather of `short` rows; rows -> tokens stays the gather of
    min(k, H) places a token, now out of a `short`-row buffer: on the v5e
    that costs a sixth of the same gather out of the exact buffer, and a
    one-hot product ``(N, short) @ (short, d)`` in its place was no
    faster at either cell's shape (PERF.md section 6, PR 34).  The
    products are one grouped product a projection
    (`grouped.grouped_product`), forward and in both cotangents: its
    kernels visit the row tiles the load reaches and write zeros past it,
    whatever the rows there hold - `rows_of` makes them zeros anyway, as
    ``lax.ragged_dot``, which multiplies the whole buffer where the
    kernels' rule does not hold (off a TPU; a width that is no multiple
    of 128 lanes), needs them."""

    def __init__(self, k, short, activation, order, position, places,
                 counts):
        self.activation, self.counts = activation, counts
        self.position, self.places = position, places
        self.here = counts.sum()
        self.taken = order[:short]
        self.token = self.taken // k
        self.valid = (jnp.arange(short) < self.here)[:, None]

    def rows_of(self, tokens):
        """(short, d): the token's row for every sorted row in the load."""
        return jnp.where(self.valid, tokens[self.token],
                         jnp.zeros((), tokens.dtype))

    def tokens_of(self, rows):
        """(N, d): every token's sum of its rows in the load, in float32."""
        ok = self.places < self.here
        got = rows[jnp.where(ok, self.places, 0)]      # (N, min(k, H), d)
        return jnp.where(ok[..., None], got, jnp.zeros((), rows.dtype)) \
            .astype(jnp.float32).sum(1).astype(rows.dtype)

    def choices_of(self, rows):
        """(N k, 1): every assignment's row (short, 1) in the load, 0 for
        one outside it.  `taken` names each assignment once, so `short`
        values are put in place, where a gather by `position` would walk
        all N k to find them."""
        return jnp.zeros((self.position.size, 1), rows.dtype) \
            .at[self.taken].set(jnp.where(self.valid, rows, 0.0),
                                unique_indices=True)

    def product_of(self, rows, w):
        return grouped.grouped_product(rows, w, self.counts)

    def forward(self, x, flat_w, w_in, w_down):
        """(y, what the backward reads again: `short` rows each)."""
        with jax.named_scope("dispatch"):
            xs = self.rows_of(x)
            ws = jnp.where(self.valid, flat_w[self.taken], 0.0)
        with jax.named_scope("experts"):
            up = self.product_of(xs, w_in)
            out = self.product_of(_activate(up, self.activation), w_down)
        with jax.named_scope("combine"):
            y = self.tokens_of((out.astype(jnp.float32) * ws)
                               .astype(x.dtype))
        return y, (xs, ws, up, out)

    def backward(self, kept, w_in, w_down, g):
        """The cotangents of x, flat_w, w_in, w_down."""
        xs, ws, up, out = kept
        with jax.named_scope("combine"):
            g_rows = self.rows_of(g).astype(jnp.float32)
            d_ws = (g_rows * out.astype(jnp.float32)).sum(-1, keepdims=True)
            d_out = (g_rows * ws).astype(out.dtype)
        with jax.named_scope("experts"):
            act, d_activate = jax.vjp(
                lambda u: _activate(u, self.activation), up)
            d_act, d_w_down = jax.vjp(self.product_of, act, w_down)[1](d_out)
            d_up, = d_activate(d_act)
            d_xs, d_w_in = jax.vjp(self.product_of, xs, w_in)[1](d_up)
        with jax.named_scope("dispatch"):
            d_x = self.tokens_of(d_xs)
            d_flat_w = self.choices_of(d_ws)
        return d_x, d_flat_w, d_w_in, d_w_down


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _sized_to_the_load(k, short, activation, x, flat_w, w_in, w_down, order,
                       position, places, counts):
    """The held experts' result by the path the load `counts.sum()` asks
    for: `_Short` where it fits `short` rows, `_exact_path` otherwise.
    The choice is NOT differentiated through.  jax's derivative of a
    ``lax.cond`` hands the backward pass the union of both branches'
    residuals and fills the branch not taken with zeros, so the short
    branch would write, and hold, every worst-case-sized intermediate of
    the exact one (PR 28's second branch: 2.2 GB of temporaries for 0.7
    ms).  Here the backward pass is a ``cond`` of its own on the same
    load; between the two cross the inputs and `short`-sized values (the
    exact branch leaves them zero and runs its forward again in its
    backward: it is the rare one)."""
    return _sized_fwd(k, short, activation, x, flat_w, w_in, w_down, order,
                      position, places, counts)[0]


def _sized_fwd(k, short, activation, x, flat_w, w_in, w_down, *indices):
    def short_path(x, flat_w, w_in, w_down):
        return _Short(k, short, activation, *indices) \
            .forward(x, flat_w, w_in, w_down)

    def exact_path(x, flat_w, w_in, w_down):
        y = _exact_path(k, activation, x, flat_w, w_in, w_down, *indices)
        kept = jax.eval_shape(short_path, x, flat_w, w_in, w_down)[1]
        return y, jax.tree_util.tree_map(
            lambda v: jnp.zeros(v.shape, v.dtype), kept)

    y, kept = lax.cond(indices[-1].sum() <= short, short_path, exact_path,
                       x, flat_w, w_in, w_down)
    return y, (x, flat_w, w_in, w_down, indices, kept)


def _sized_bwd(k, short, activation, res, g):
    x, flat_w, w_in, w_down, indices, kept = res

    def short_path(g):
        return _Short(k, short, activation, *indices) \
            .backward(kept, w_in, w_down, g)

    def exact_path(g):
        return _exact_backward(k, activation, x, flat_w, w_in, w_down,
                               indices, g)

    # the barrier keeps what follows a gradient (an optimizer's cast to
    # float32) out of the branches: XLA would move it in and hand on
    # every expert matrix's gradient twice as large
    return lax.optimization_barrier(
        lax.cond(indices[-1].sum() <= short, short_path, exact_path, g)) \
        + (None,) * len(indices)


_sized_to_the_load.defvjp(_sized_fwd, _sized_bwd)


def held_expert_ffn(x, idx, weights, w_in, w_down, held: Sequence[int],
                    n_experts: int, activation: str = "swiglu"):
    """The held experts' part of a token-choice layer's result.

    x: (N, d), what the experts read; idx, weights: (N, k) from
    `topk_route` (over ALL `n_experts`); w_in and w_down (H, f, d), the H
    = len(held) experts stacked in the order of `held` (their global
    ids).  `activation` says what an expert is: ``"swiglu"``, w_in (H, d,
    2f) the gate's columns first, ``(silu(x Wg) * (x Wu)) Wd``;
    ``"relu2"``, w_in (H, d, f), no gate, ``relu(x W1)^2 Wd``.

    Assignments (token, choice) are sorted by held expert - those routed
    to experts held elsewhere sort last - and go through ONE grouped
    product a projection (`ops.grouped.grouped_product`, groups = held
    experts) over a buffer of the first sorted rows: on a TPU, for widths
    that are multiples of 128 lanes, two Pallas kernels
    (`grouped_product_rows`, forward and by the rows; `grouped_product_weights`,
    by the weights) that visit only the row tiles the load reaches and
    write zeros past it; anywhere else ``lax.ragged_dot``, which XLA:TPU
    would expand into `ragged-dot-*` kernels of its own over the whole
    buffer.  ``N * min(k, H)`` rows are the
    exact no-drop bound: a token's k choices are k DIFFERENT experts, so
    at most min(k, H) of them are held here, whatever the router does -
    every assignment that lands here has its row and no token is ever
    dropped.  (Top-4 with 8 held: all N*k rows; top-22 with 8 held of
    512: 8 N rows where N*k would be 22 N, of which 0.34 N are used at an
    even load.)  Every gather, elementwise pass and product over that
    buffer pays for the worst case, 8 to 23 times the load, so the rows
    come in TWO SIZES and the device chooses by the load it counted
    (`here`, the held experts' assignments): `short_rows` - twice an even
    router's share, from the shapes alone - where the load fits it, the
    exact bound otherwise; where the short size would not halve the
    bound (small shapes, a layer that holds all its experts) there is one
    path, the exact one.  The mathematics, the dtypes and the no-drop
    guarantee are the same in both (`_sized_to_the_load` says why the
    choice is not differentiated through, and what PR 28's second branch
    - 0.7 ms of a layer's 11.4 for 2.1 GB - got wrong).  Where k > H the
    exact path's sum back to tokens reads, for each token, only the
    min(k, H) places its held assignments can have (its places sorted,
    the held ones first).  Gather, products and the weighted sum back to
    tokens are scoped `dispatch`, `experts`, `combine`, in either path,
    forward and backward.

    Returns (y (N, d), counts (H,) assignments a held expert, elsewhere
    () assignments routed away, exact () 1 where the load passed the
    short size and the exact buffer ran - 0 where the layer has one
    path), the last three as float32."""
    if activation not in EXPERT_ACTIVATIONS:
        raise ValueError("held_expert_ffn: activation %r is none of %r"
                         % (activation, EXPERT_ACTIVATIONS))
    n, k = idx.shape
    h = len(held)
    m = n * k
    if not all(0 <= e < n_experts for e in held):
        raise ValueError("held_expert_ffn: held experts %r of %d"
                         % (tuple(held), n_experts))
    with jax.named_scope("dispatch"):
        # sorts and compares only: a TPU serialises a scatter, and a
        # gather of N * k scalars as well.  An assignment's place among
        # the held experts, h = held elsewhere: H compares
        local = _slot_of(idx.reshape(m), held)
        order = jnp.argsort(local, stable=True).astype(jnp.int32)
        position = jnp.argsort(order).astype(jnp.int32)  # order's inverse
        sizes = (local[:, None] == jnp.arange(h + 1)).sum(0, dtype=jnp.int32)
        # a recomputed block sorts once: its second run and its backward
        # pass read the forward's order (0.26 MB against two sorts) - and
        # so take the path the forward took
        order, position, sizes = map(recompute_keep,
                                     (order, position, sizes))
        counts, elsewhere = sizes[:h], sizes[h]
        flat_w = weights.reshape(m, 1).astype(jnp.float32)
        places = position.reshape(n, k)
        if h < k:
            # a token's places in the sorted order, the held ones (those
            # before the load) first: min(k, H) columns hold them all
            places = jnp.sort(places, axis=-1)[:, :h]
    short = short_rows(n, k, h, n_experts)
    if short is None:
        y = _exact_path(k, activation, x, flat_w, w_in, w_down, order,
                        position, places, counts)
        exact = jnp.zeros((), jnp.float32)
    else:
        y = _sized_to_the_load(k, short, activation, x, flat_w, w_in,
                               w_down, order, position, places, counts)
        exact = (counts.sum() > short).astype(jnp.float32)
    return y, counts.astype(jnp.float32), elsewhere.astype(jnp.float32), \
        exact


def token_choice_moe(x, router_w, bias, w_in, w_down, *,
                     held: Sequence[int], top_k: int, scale: float = 1.0,
                     norm_topk_prob: bool = True,
                     activation: str = "swiglu", expert_input=None):
    """`topk_route` (scope `route`) of the (..., d) tokens `x` +
    `held_expert_ffn` on `expert_input` (..., d'), default `x` itself: a
    latent expert layer routes on the model width and computes in its
    latent.  Returns (y like `expert_input`, counts (H,), elsewhere (),
    exact ())."""
    read = x if expert_input is None else expert_input
    lead = read.shape[:-1]
    with jax.named_scope("route"):
        idx, weights = topk_route(x.reshape(-1, x.shape[-1]), router_w,
                                  bias, top_k, scale, norm_topk_prob)
    y, counts, elsewhere, exact = held_expert_ffn(
        read.reshape(-1, read.shape[-1]), idx, weights, w_in, w_down, held,
        router_w.shape[0], activation)
    return y.reshape(lead + (y.shape[-1],)), counts, elsewhere, exact
