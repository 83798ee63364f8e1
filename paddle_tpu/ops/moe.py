"""Sparse-expert layer, array level: token-choice routing without
capacity and without dropped tokens, and the experts' SwiGLU over the
routed (token, expert) pairs only.

    scores s = sigmoid(float32(m . Wr))                   [T, E]
    chosen S = top_k(s + b)       b: the balancing bias, in the
                                  CHOICE only, never in the weights
    weights  w_e = s_e / (sum_S s + 1e-20) * route_scale  (route_norm)
    out      = sum_{e in S} w_e * SwiGLU_e(m)

Group-limited choice (`n_group` > 1; DeepSeek-V3, arXiv:2412.19437
section 2.1.2, the sigmoid-scored form): the E experts lie in `n_group`
groups of E / n_group neighbours, a group's score is the sum of its two
largest s + b, and the top-k is taken inside the `topk_group` best
groups alone — a token's experts then lie on at most that many nodes.
`n_group` 1 is the choice above, bit for bit.

The layer is told which experts it holds (`experts_held = (first,
count)`, the chip's share under expert parallelism): it routes over ALL
experts, computes the part of the sum its own experts give and leaves
the rest out. On one chip that holds every expert this is the whole
layer; no code stands in for absent chips.

The products go through ops/pallas/grouped_matmul.py: pairs sorted by
expert into padded row tiles, one gated call (silu(x . W1) * (x . W3))
and one plain call (. W2), each reading an expert's weights once and
only if a pair chose it.
"""
import jax
import jax.numpy as jnp

from .pallas import grouped_matmul as gmm


def route(m, router_w, bias, top_k, route_scale=1.0, route_norm=True,
          n_group=1, topk_group=1):
    """m [T, H], router_w [H, E], bias [E] or None (no balancing bias)
    -> (experts int32 [T, k], weights float32 [T, k]). The product
    accumulates in float32 and the scores stay float32: the top-k's
    eighth choice hangs on the fourth digit. `n_group` > 1: only experts
    of the `topk_group` best groups can be chosen."""
    logits = jnp.dot(m, router_w.astype(m.dtype),
                     preferred_element_type=jnp.float32)
    scores = jax.nn.sigmoid(logits)
    choice = scores if bias is None else scores + bias.astype(jnp.float32)
    if n_group > 1:
        T, E = choice.shape
        grouped = choice.reshape(T, n_group, E // n_group)
        best_two, _ = jax.lax.top_k(grouped, 2)
        _, kept = jax.lax.top_k(jnp.sum(best_two, -1), topk_group)
        keep = jnp.any(kept[:, :, None] == jnp.arange(n_group), axis=1)
        choice = jnp.where(keep[:, :, None], grouped, -jnp.inf) \
            .reshape(T, E)
    _, experts = jax.lax.top_k(choice, top_k)
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    if route_norm:
        weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-20)
    return experts.astype(jnp.int32), weights * route_scale


def experts_swiglu(m, experts, weights, w1, w3, w2, experts_held=None,
                   live=None, counted=None):
    """The routed experts' part of the layer's output.

    m [T, H]; experts / weights [T, k] from `route`; w1, w3 [C, H, F]
    and w2 [C, F, H]: the C experts held, `experts_held = (first, C)`
    of the router's range (default: all of it, from 0). `live` (bool
    [T], default all): a row that is padding sends its pairs to no
    expert, so they are neither computed nor counted and its output is
    0. Returns (out [T, H] in m's dtype, rows int32 [C]: pairs each
    held expert took) — or, with `counted` (ids int32 [T], count): the
    group each row's pairs are counted with, rows int32 [count, C]."""
    T, k = experts.shape
    C = w1.shape[0]
    first = 0 if experts_held is None else int(experts_held[0])
    if experts_held is not None and int(experts_held[1]) != C:
        raise ValueError(f'experts_held {experts_held} but {C} experts\' '
                         f'weights')
    local = experts.reshape(-1) - first
    held = (local >= 0) & (local < C)
    if live is not None:
        held = held & jnp.repeat(live, k)
    local = jnp.where(held, local, C)
    p = gmm.plan(local, C, gmm.tile_rows_for(T * k, C))
    tiles = (p['tile_expert'], p['tile_block'], p['n_live'])
    x = m[p['src'] // k]                                # [M_pad, H]
    h = gmm.grouped_matmul(x, w3, *tiles, w_gate=w1)    # [M_pad, F]
    y = gmm.grouped_matmul(h, w2, *tiles)               # [M_pad, H]
    held = held.reshape(T, k)
    rows = jnp.minimum(p['dest'], y.shape[0] - 1).reshape(T, k)
    # a pair left out reads some row and is weighted out: `where`, not
    # a product with 0, because a dead tile's rows are never written
    part = jnp.where(held[..., None],
                     y[rows].astype(jnp.float32) * weights[..., None], 0)
    out = jnp.sum(part, axis=1).astype(m.dtype)
    if counted is None:
        return out, p['counts']
    # pairs by (group, expert): each token's pairs per held expert (a
    # pair left out sits at C: 0 or 1 each), summed over the tokens of a
    # group as a product with the groups' 0/1 membership — exact in any
    # matmul precision, the sums far below 2**24
    ids, count = counted
    per_token = jnp.sum(
        local.reshape(T, k, 1) == jnp.arange(C, dtype=jnp.int32),
        axis=1)                                             # [T, C]
    groups = ids[None, :] == jnp.arange(count, dtype=jnp.int32)[:, None]
    rows = jnp.dot(groups.astype(jnp.float32),
                   per_token.astype(jnp.float32))
    return out, rows.astype(jnp.int32)


def swiglu(m, w1, w3, w2):
    """(silu(m . w1) * (m . w3)) . w2 — the dense MLP and the shared
    expert; fp32 accumulation, m's dtype between the products."""
    g = jnp.dot(m, w1, preferred_element_type=jnp.float32)
    u = jnp.dot(m, w3, preferred_element_type=jnp.float32)
    h = (g * jax.nn.sigmoid(g) * u).astype(m.dtype)
    return jnp.dot(h, w2, preferred_element_type=jnp.float32) \
        .astype(m.dtype)
