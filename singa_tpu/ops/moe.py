"""Mixture-of-Experts FFN with top-k routing — the expert-parallel op.

New capability (no MoE in the reference).  Sort-based dispatch: the T·k
(token, expert) assignments are sorted by expert id, ranked within each
expert's run, capacity-clipped, and scattered into a dense
(n_exp, capacity, E) expert batch; expert FFNs run batched over the
leading expert dim and results scatter-add back per token.  Memory is
O(T·k·E + n_exp·capacity·E) — linear in tokens, never the
O(T·n_exp·capacity) one-hot dispatch tensor of naive GShard.

Under expert parallelism the expert-stacked weights (and the expert
batch) shard over the mesh's "expert" axis; XLA lowers the scatter/
gather across that axis to all-to-alls over ICI.

Router aux loss follows Switch Transformer (mean fraction × mean prob
per expert, scaled by n_experts).

Below `moe_ffn`, the serving form of a sparse layer that is told which
experts it holds (`route_sigmoid`, `held_experts_ffn`; the `kRoutedMoE`
layer of core/hybrid_layers.py): it routes over ALL experts, computes
the held ones' part, and has no capacity and no dropped token.  A
long run's rows go through their experts sorted, by the grouped matmul
of ops/grouped_matmul.py (`_grouped`).
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from .grouped_matmul import grouped_matmul


def moe_ffn(x: jnp.ndarray, params: Dict[str, jnp.ndarray], k: int = 2,
            capacity_factor: float = 1.25,
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, E).  params: router (E, n_exp); w1 (n_exp, E, F),
    b1 (n_exp, F); w2 (n_exp, F, E), b2 (n_exp, E).

    Returns (out (B, S, E), router aux loss).
    """
    b, s, e = x.shape
    n_exp = params["router"].shape[1]
    t = b * s
    tokens = x.reshape(t, e)
    capacity = max(int(capacity_factor * (t * k) / n_exp), 1)

    logits = jnp.dot(tokens.astype(jnp.float32),
                     params["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                    # (T, n_exp)
    gate_vals, gate_idx = jax.lax.top_k(probs, k)              # (T, k)

    # flatten assignments; row-major keeps rank-0 choices first per token
    flat_exp = gate_idx.reshape(t * k)
    flat_gate = gate_vals.reshape(t * k)
    flat_tok = jnp.arange(t * k, dtype=jnp.int32) // k

    # sort by expert (stable → earlier tokens keep queue priority)
    order = jnp.argsort(flat_exp, stable=True)
    sorted_exp = flat_exp[order]
    # rank within each expert's contiguous run
    onehot = (sorted_exp[:, None] ==
              jnp.arange(n_exp, dtype=sorted_exp.dtype)[None, :])
    rank = jnp.take_along_axis(
        jnp.cumsum(onehot.astype(jnp.int32), axis=0) - 1,
        sorted_exp[:, None].astype(jnp.int32), axis=1)[:, 0]
    keep = rank < capacity
    # dropped assignments write to a trash slot past the expert batch
    slot = jnp.where(keep, sorted_exp * capacity + rank, n_exp * capacity)

    tok_sorted = tokens[flat_tok[order]]                       # (T*k, E)
    buf = jnp.zeros((n_exp * capacity + 1, e), x.dtype)
    buf = buf.at[slot].set(tok_sorted)
    exp_in = buf[:-1].reshape(n_exp, capacity, e)

    h = jnp.einsum("ecd,edf->ecf", exp_in, params["w1"],
                   preferred_element_type=jnp.float32)
    h = jax.nn.silu(h + params["b1"][:, None, :])
    out = jnp.einsum("ecf,efd->ecd", h.astype(x.dtype), params["w2"],
                     preferred_element_type=jnp.float32)
    out = out + params["b2"][:, None, :]

    out_flat = jnp.concatenate(
        [out.reshape(n_exp * capacity, e), jnp.zeros((1, e), out.dtype)])
    out_sorted = out_flat[slot] * flat_gate[order][:, None]
    y = jnp.zeros((t, e), jnp.float32).at[flat_tok[order]].add(
        out_sorted.astype(jnp.float32))

    # Switch aux loss over rank-0 assignments
    frac_tokens = jnp.mean(
        jax.nn.one_hot(gate_idx[:, 0], n_exp, dtype=jnp.float32), axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    aux = n_exp * jnp.sum(frac_tokens * frac_probs)
    return y.reshape(b, s, e).astype(x.dtype), aux


# -- routing over all experts, computing the held ones ------------------------

# Rows of a longer run that go through the held experts at once.
ROW_BLOCK = 256


def route_sigmoid(x, router, bias, k: int, renormalize: bool,
                  scale: float):
    """Sigmoid scores over every routed expert, the k with the largest
    score + bias chosen, weighted by their scores alone (over the
    chosen ones' sum when `renormalize`), times `scale`.  x (T, E);
    router (E, N); bias (N,).  Returns (idx (T, k) int32, weights (T, k)
    float32).  Scores are float32 whatever x is."""
    logits = jnp.dot(x, router, preferred_element_type=jnp.float32)
    scores = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(scores + bias.astype(jnp.float32), k)
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    if renormalize:
        chosen = chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    return idx.astype(jnp.int32), chosen * scale


def route_mlp_softmax(state, norm_scale, eps: float, layers, bias, k: int):
    """A softmax MLP router over every routed expert, read from the
    router's own state: p = softmax(W3 gelu(W2 gelu(W1 RMSNorm(state) +
    b1) + b2)), the k with the largest p + bias chosen, weighted by
    their p alone.  state (T, R) float32; `layers` the (weight, bias or
    None) pairs of the MLP, stored (in, out); bias (N,) moves the
    choice only.  All of it float32 at full precision whatever the
    weights' dtype: a row's whole expert hangs on an argmax.  Returns
    (idx (T, k) int32, weights (T, k) float32)."""
    f32 = jnp.float32
    var = jnp.mean(jnp.square(state), axis=-1, keepdims=True)
    h = state * jax.lax.rsqrt(var + eps) * norm_scale.astype(f32)
    for i, (w, b) in enumerate(layers):
        if i:
            h = jax.nn.gelu(h, approximate=False)
        h = jnp.dot(h, w.astype(f32), precision=jax.lax.Precision.HIGHEST)
        if b is not None:
            h = h + b.astype(f32)
    p = jax.nn.softmax(h, axis=-1)
    _, idx = jax.lax.top_k(p + bias.astype(f32), k)
    return idx.astype(jnp.int32), jnp.take_along_axis(p, idx, axis=-1)


def grouped_run(rows: int, num_held: int, experts_per_token: int) -> bool:
    """Whether `held_experts_ffn` takes a run of `rows` rows in its
    grouped form: a run past ROW_BLOCK where the dense walk would cost
    more expert products a row than the routing asks for."""
    return rows > ROW_BLOCK and num_held > experts_per_token


def _grouped(x, local, held, weights, group_sizes, w_gate, w_up, w_down):
    """`held_experts_ffn`'s grouped form.  local (T, k) the chosen
    experts' places among the held ones, held (T, k) bool which of them
    are held (and their row real), group_sizes (X,) assignments on each
    held expert.  Returns (y (T, E) float32, the rows of the tiles the
    products' schedules visited, int32).

    The three products are `ops/grouped_matmul.py`'s: a Pallas grouped
    matmul whose 128-row tiles span group boundaries, one visit a
    (tile, group) pair that shares a row.  It visits the tiles of real
    groups only, whatever the rows handed, but everything around it
    follows the rows HANDED: the gather of the rows, the SiLU and the
    product on (m, F), the (m, E) float32 result and its gather back
    (PERF.md 6, PR 43).
    So the sorted rows go through HALF of the T k assignments at a
    time, and the second half only where the groups do not fit the
    first: a router that spreads its choices puts held / routed of them
    here, an eighth in the configurations that hold a share of their
    experts.  ONE size whatever the routing, because a size that
    followed it made a run's cost follow the weights' seed.  A layer
    that holds EVERY expert a token can choose (ZAYA's 16 of 16, top 1)
    takes both halves wherever more than half its rows are real; an
    expert's weights are still read once, by the half that holds its
    rows (twice where its rows lie across the middle).

    The first half's products stand OUTSIDE the `cond`, which it needs
    either way: a Mosaic call in a `cond` branch carries no name of
    ours in a device trace (`tpu_custom_call`), one in the main
    computation its jitted function's (PERF.md 6, PR 45)."""
    t, k = local.shape
    n_held = w_gate.shape[0]
    # an assignment's expert, `n_held` where it is on none held here:
    # those sort behind every group, and no group's product reads them
    order = jnp.argsort(jnp.where(held, local, n_held).reshape(t * k),
                        stable=True)
    back = jnp.zeros((t * k,), jnp.int32).at[order].set(
        jnp.arange(t * k, dtype=jnp.int32)).reshape(t, k)
    ends = jnp.cumsum(group_sizes)

    def run(lo, m):
        """Sorted assignments [lo, lo + m) through their experts."""
        sizes = jnp.diff(jnp.clip(ends, lo, lo + m), prepend=lo)
        rows = x[order[lo:lo + m] // k]                      # (m, E)
        gate, visited = grouped_matmul(rows, w_gate, sizes)
        up, visited_up = grouped_matmul(rows, w_up, sizes)
        hid = (jax.nn.silu(gate) * up).astype(x.dtype)
        out, visited_down = grouped_matmul(hid, w_down, sizes)
        # back to the tokens' order; what lies behind the last group is
        # whatever the product left there (it writes no row there), and
        # is not read
        mine = held & (back >= lo) & (back < lo + m)
        out = out[jnp.clip(back - lo, 0, m - 1)]             # (T, k, E)
        return (jnp.sum(jnp.where(mine[:, :, None],
                                  out * weights[:, :, None], 0.0), axis=1),
                visited + visited_up + visited_down)

    half = -(-t * k // 2)
    y, visited = run(0, half)

    def rest():
        more, visited_more = run(half, t * k - half)
        return y + more, visited + visited_more

    return jax.lax.cond(ends[-1] > half, rest, lambda: (y, visited))


def held_experts_ffn(x, idx, weights, w_gate, w_up, w_down, first: int,
                     valid=None, max_load: bool = False,
                     tile_rows: bool = False):
    """What the experts held here add for each token: expert j of the
    stacked weights is routed expert `first + j`.  x (T, E); idx,
    weights (T, k) from the router over ALL experts; w_gate, w_up
    (X, E, F); w_down (X, F, E); valid (T,) bool or None (a pad is
    routed nowhere).  Returns (y (T, E) float32, counts int32 (2,):
    assignments that fell on held experts, held experts some token
    chose; with `max_load` a third, the busiest held expert's
    assignments; with `tile_rows` the grouped form adds one more, LAST:
    the rows of the tiles its three products visited, of which the
    first count x 3 were some expert's own).  A token none of whose
    experts is held gets zeros; no token is dropped and no row depends
    on another.

    Which of two forms computes it follows from the layer's own sizes
    and the run's length, and is no setting (`grouped_run`):

    * the DENSE WALK, for a run of at most ROW_BLOCK rows (a decode
      step) and wherever this process holds no more experts than a
      token chooses: every row goes through every held expert, the
      pairs the router did not choose weighted 0.  With the experts'
      weights as the traffic and a few rows an expert nothing is saved
      by sorting, and the step costs the same whatever the routing.
      A longer run is walked ROW_BLOCK rows at a time, and no block
      after the last one that holds a real row is walked.
    * the GROUPED form, for a longer run (a prefill, a chunk of one)
      where more experts are held than a token chooses: the dense walk
      costs `num_held` expert products a row, this one the row's own
      assignments on held experts, at most `experts_per_token`.  The
      (token, expert) assignments are sorted by expert, those on no
      held expert (and every pad's) last, and each held expert's run of
      rows is multiplied by that expert's weights
      (`ops/grouped_matmul.py`: a Pallas kernel, compiled by Mosaic on
      the TPU and interpreted elsewhere, whose 128-row tiles span the
      experts' boundaries).  No capacity, no dropped token, float32
      sums, the same `counts` and the tiles' rows behind them."""
    t, e = x.shape
    n_held = w_gate.shape[0]
    local = idx - first                                      # (T, k)
    held = (local >= 0) & (local < n_held)
    if valid is not None:
        held = held & valid[:, None]
    onehot = (local[:, :, None] == jnp.arange(n_held)) & held[:, :, None]
    combine = jnp.sum(jnp.where(onehot, weights[:, :, None], 0.0), axis=1)
    per_expert = jnp.sum(jnp.any(onehot, axis=1), axis=0)    # (X,)
    counts = [jnp.sum(per_expert), jnp.sum(per_expert > 0)]
    if max_load:
        counts.append(jnp.max(per_expert))
    counts = jnp.stack(counts).astype(jnp.int32)
    if grouped_run(t, n_held, idx.shape[1]):
        y, visited = _grouped(x, local, held, weights,
                              per_expert.astype(jnp.int32),
                              w_gate, w_up, w_down)
        if tile_rows:
            counts = jnp.concatenate([counts, visited[None]])
        return y, counts

    def through(rows, row_combine):
        gate = jnp.einsum("te,xef->txf", rows, w_gate,
                          preferred_element_type=jnp.float32)
        up = jnp.einsum("te,xef->txf", rows, w_up,
                        preferred_element_type=jnp.float32)
        # weighted before the way down, so that the sum over experts is
        # the contraction of one matmul: (T, X*F) by (X*F, E)
        hid = (jax.nn.silu(gate) * up
               * row_combine[:, :, None]).astype(rows.dtype)
        return jnp.einsum("txf,xfe->te", hid, w_down,
                          preferred_element_type=jnp.float32)

    block = ROW_BLOCK
    if t <= block:
        return through(x, combine), counts
    pad = -t % block
    xp = jnp.pad(x, ((0, pad), (0, 0)))
    cp = jnp.pad(combine, ((0, pad), (0, 0)))
    n = (t + pad) // block
    live = n if valid is None else -(-jnp.max(
        jnp.where(valid, jnp.arange(t) + 1, 0)) // block)

    def walk(i, out):
        rows = jax.lax.dynamic_slice_in_dim(xp, i * block, block)
        row_combine = jax.lax.dynamic_slice_in_dim(cp, i * block, block)
        return jax.lax.dynamic_update_slice_in_dim(
            out, through(rows, row_combine), i * block, 0)

    out = jax.lax.fori_loop(0, live, walk,
                            jnp.zeros((t + pad, e), jnp.float32))
    return out[:t], counts
