"""Pipeline parallelism with microbatching over the "pipe" mesh axis.

The reference's closest mechanism is cross-process activation exchange
through BridgeSrc/BridgeDst layers over ZMQ PUSH/PULL (SURVEY.md §2.2-4)
— point-to-point dataflow with no microbatch schedule.  This module is
the first-class successor: a GPipe-style schedule where every device
runs one stage and activations hop stage→stage via
`jax.lax.ppermute` (XLA collective-permute over ICI), with n_micro
microbatches in flight to fill the pipeline bubble.

Constraints (SPMD): every stage must map activations of one shared
shape/dtype to the same shape/dtype (true for transformer blocks).  The
backward pass is autodiff through the scan — GPipe semantics (all
forward, then all backward), with activation memory O(n_micro) per
stage; combine with jax.checkpoint on stage_fn for O(1).

The reference's `locationid` layer field (model.proto:128) maps onto
stage ids here: net configs partition into stages by locationid.
"""

from __future__ import annotations

from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def stack_stage_params(per_stage_params: Sequence[Any]) -> Any:
    """Stack a list of per-stage param pytrees along a new leading stage
    dim (leaves must match shapes across stages)."""
    return jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves), *per_stage_params)


def pipeline_apply(mesh: Mesh, stage_fn: Callable[..., jnp.ndarray],
                   stage_params: Any, x: jnp.ndarray,
                   axis: str = "pipe",
                   batch_axis: str | None = None,
                   rng: jax.Array | None = None,
                   virtual: int = 1) -> jnp.ndarray:
    """Run microbatches through the pipeline.

    stage_params: pytree with leaves (n_stages, ...) — sharded over
    `axis` so each device keeps only its stage's slice.
    x: (n_micro, micro_batch, ...) microbatched input.  With
    `batch_axis` set (e.g. "data"), the micro_batch dim (dim 1) shards
    over that axis so dp groups pipeline DIFFERENT slices of the batch
    instead of replicating the work.
    With `rng` set, stage_fn is called as stage_fn(params, mb, key)
    where key = fold_in(fold_in(rng, stage), microbatch) — every
    (stage, microbatch) cell draws independent randomness, so
    rng-bearing layers (dropout) work inside stages; without it the
    two-arg form is called.
    `virtual` > 1 selects the CIRCULAR (interleaved) schedule — the
    1F1B-family form that is natural in SPMD/XLA: n_stages = P·virtual
    virtual stages, P = pipe axis size, each device holding `virtual`
    round-robin slices (device d runs stages d, d+P, d+2P, …) and
    microbatches looping the ring `virtual` times.  Bubble shrinks from
    (P·v−1)/(m+P·v−1) ticks to (P−1)/(m·v+P−1) — ~v× smaller — at the
    same per-tick work; no waiting stash is needed because every
    (microbatch, virtual stage) output feeds the next tick directly.
    Requires n_micro % P == 0 (microbatches travel in rounds of P).
    Returns (n_micro, micro_batch, ...) outputs of the final stage,
    sharded the same way.
    """
    nstages = mesh.shape[axis]
    x_spec = P(None, batch_axis) if batch_axis else P()
    if nstages == 1:
        # degenerate mesh: run every stacked stage sequentially on the
        # one device, with the same per-(stage, microbatch) key fold
        n_total = jax.tree_util.tree_leaves(stage_params)[0].shape[0]

        def all_stages(mb, m_idx):
            h = mb
            for s in range(n_total):
                ps = jax.tree_util.tree_map(lambda p, s=s: p[s],
                                            stage_params)
                if rng is None:
                    h = stage_fn(ps, h)
                else:
                    h = stage_fn(ps, h, jax.random.fold_in(
                        jax.random.fold_in(rng, s), m_idx))
            return h

        return jax.vmap(all_stages)(x, jnp.arange(x.shape[0]))

    n_micro = x.shape[0]
    p_spec = jax.tree_util.tree_map(lambda _: P(axis), stage_params)

    if virtual > 1:
        if n_micro % nstages:
            raise ValueError(
                f"circular schedule needs n_micro ({n_micro}) % pipe "
                f"axis ({nstages}) == 0 (microbatches travel in rounds)")
        return _schedule_circular(mesh, stage_fn, stage_params, x, axis,
                                  x_spec, p_spec, rng, nstages, virtual,
                                  n_micro)

    if n_micro < nstages:
        raise ValueError(f"n_micro ({n_micro}) must be >= pipeline stages "
                         f"({nstages}) to fill the pipeline")

    def call(stage, params, inp, key):
        params = jax.tree_util.tree_map(lambda p: p[0], params)
        if key is None:
            return stage_fn(params, inp)
        return stage_fn(params, inp, key)

    return _schedule(mesh, call, stage_params, x, axis, x_spec, p_spec,
                     rng, nstages, n_micro)


def _schedule(mesh, call, stage_params, x, axis, x_spec, p_spec, rng,
              nstages, n_micro):
    """The GPipe fill-drain schedule shared by the uniform (stacked
    SPMD stages) and heterogeneous (lax.switch branches) pipelines.
    `call(stage, params, inp, key)` runs one stage tick."""

    def local(params, xm):
        stage = jax.lax.axis_index(axis)
        total = n_micro + nstages - 1
        fwd_perm = [(i, i + 1) for i in range(nstages - 1)]
        stage_rng = (None if rng is None
                     else jax.random.fold_in(rng, stage))

        def tick(carry, t):
            state, outputs = carry
            # this stage processes microbatch m = t - stage at tick t
            # (clipped during fill/drain, where the result is discarded)
            m_idx = jnp.clip(t - stage, 0, n_micro - 1)
            x_t = jax.lax.dynamic_index_in_dim(
                xm, jnp.clip(t, 0, n_micro - 1), 0, keepdims=False)
            inp = jnp.where(stage == 0, x_t.astype(state.dtype), state)
            key = (None if stage_rng is None
                   else jax.random.fold_in(stage_rng, m_idx))
            out = call(stage, params, inp, key)
            oidx = jnp.clip(t - (nstages - 1), 0, n_micro - 1)
            updated = jax.lax.dynamic_update_index_in_dim(
                outputs, out, oidx, 0)
            collect = jnp.logical_and(stage == nstages - 1,
                                      t >= nstages - 1)
            outputs = jnp.where(collect, updated, outputs)
            state = jax.lax.ppermute(out, axis, fwd_perm)
            return (state, outputs), None

        state0 = jnp.zeros(xm.shape[1:], xm.dtype)
        out0 = jnp.zeros_like(xm)
        (_, outputs), _ = jax.lax.scan(tick, (state0, out0),
                                       jnp.arange(total))
        # broadcast final-stage outputs to all stages
        mask = (stage == nstages - 1).astype(outputs.dtype)
        return jax.lax.psum(outputs * mask, axis)

    return shard_map(local, mesh=mesh, in_specs=(p_spec, x_spec),
                     out_specs=x_spec, check_vma=False)(stage_params, x)


def _schedule_circular(mesh, stage_fn, stage_params, x, axis, x_spec,
                       p_spec, rng, P_, v, n_micro):
    """Interleaved/circular fill-drain schedule (the 1F1B-family form).

    Device d holds virtual stages {d, d+P, …, d+(v−1)·P} (round-robin),
    microbatches hop the ring with wraparound and loop it v times.
    Work mapping: device d at tick t runs work item j = t − d (idle
    outside [0, v·n_micro)), decomposed j = round·(v·P) + w·P + m_in →
    microbatch m = round·P + m_in at virtual stage σ = w·P + d.  The
    mapping is conflict-free by construction (unique (w, m) per (d, t))
    and every output feeds the next tick's consumer directly, so no
    waiting stash exists.  Total ticks v·n_micro + P − 1: the bubble is
    (P−1) ticks instead of GPipe's (v·P−1) for the same v·P stages.

    `stage_params` leaves are (v·P, …) in virtual-stage order σ; they
    are permuted here so contiguous sharding over `axis` lands stage
    σ = w·P + d at device d row w.  Autodiff through the tick scan
    yields the reverse circular schedule (ppermute transposes to the
    reverse ring)."""
    S = v * P_

    def reorder(p):
        idx = jnp.asarray([(pos % v) * P_ + pos // v
                           for pos in range(S)])
        return p[idx]

    stage_params = jax.tree_util.tree_map(reorder, stage_params)

    def local(params, xm):
        d = jax.lax.axis_index(axis)
        total = v * n_micro + P_ - 1
        perm = [(i, (i + 1) % P_) for i in range(P_)]

        def tick(carry, t):
            state, outputs = carry
            j = t - d                       # this device's work index
            valid = jnp.logical_and(j >= 0, j < v * n_micro)
            jc = jnp.clip(j, 0, v * n_micro - 1)
            rnd, rem = jnp.divmod(jc, v * P_)
            w, m_in = jnp.divmod(rem, P_)
            m = rnd * P_ + m_in             # microbatch index
            sigma = w * P_ + d              # virtual stage id
            pw = jax.tree_util.tree_map(
                lambda p: jax.lax.dynamic_index_in_dim(
                    p, w, 0, keepdims=False), params)
            x_t = jax.lax.dynamic_index_in_dim(xm, m, 0, keepdims=False)
            # stage 0 of the ring at wrap 0 consumes fresh input;
            # everything else consumes the hopped state
            fresh = jnp.logical_and(d == 0, w == 0)
            inp = jnp.where(fresh, x_t.astype(state.dtype), state)
            if rng is None:
                out = stage_fn(pw, inp)
            else:
                key = jax.random.fold_in(jax.random.fold_in(rng, sigma), m)
                out = stage_fn(pw, inp, key)
            collect = jnp.logical_and(
                valid, jnp.logical_and(d == P_ - 1, w == v - 1))
            updated = jax.lax.dynamic_update_index_in_dim(
                outputs, out, m, 0)
            outputs = jnp.where(collect, updated, outputs)
            state = jax.lax.ppermute(out, axis, perm)
            return (state, outputs), None

        state0 = jnp.zeros(xm.shape[1:], xm.dtype)
        out0 = jnp.zeros_like(xm)
        (_, outputs), _ = jax.lax.scan(tick, (state0, out0),
                                       jnp.arange(total))
        mask = (d == P_ - 1).astype(outputs.dtype)
        return jax.lax.psum(outputs * mask, axis)

    return shard_map(local, mesh=mesh, in_specs=(p_spec, x_spec),
                     out_specs=x_spec, check_vma=False)(stage_params, x)


def pipeline_apply_hetero(mesh, branch_fn, params, x,
                          axis: str = "pipe",
                          batch_axis: str | None = None,
                          rng: jax.Array | None = None) -> jnp.ndarray:
    """GPipe schedule for NON-uniform stages: every boundary tensor is
    flattened and zero-padded to one (micro_batch, max_flat) buffer so
    the ppermute hop has a single SPMD shape, and each device runs its
    own structure via `branch_fn(stage, params, flat_mb, key)`
    (lax.switch inside).  `params` is the full resolved param dict,
    REPLICATED on every device (heterogeneous stages cannot stack) —
    the memory tradeoff that buys arbitrary per-stage structure, the
    reference's bridge-layer generality (neuralnet.cc:198-323).
    """
    x_spec = P(None, batch_axis) if batch_axis else P()
    p_spec = jax.tree_util.tree_map(lambda _: P(), params)
    nstages = mesh.shape[axis]
    n_micro = x.shape[0]
    # nstages == 1 is unreachable from HeteroPipelineNet (the trainer
    # only pipelines a pipe axis > 1) and the schedule handles it
    # degenerately anyway (empty ppermute), so no fast path exists.
    if n_micro < nstages:
        raise ValueError(f"n_micro ({n_micro}) must be >= pipeline "
                         f"stages ({nstages}) to fill the pipeline")
    return _schedule(mesh, branch_fn, params, x, axis, x_spec, p_spec,
                     rng, nstages, n_micro)
