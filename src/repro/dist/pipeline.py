"""Microbatch pipeline parallelism over a mesh axis (GPipe schedule).

Stage ``s`` of the network lives on rank ``s`` of the pipeline axis (stage
parameters are sharded on their leading dimension).  Microbatches are fed
into stage 0 one per tick; activations hop to the next rank with a single
neighbour ``ppermute`` per tick, so after the ``S - 1``-tick fill phase the
pipe is full and every rank computes every tick.  Total ticks:
``n_micro + S - 1``.

**Backward pass.**  ``pipelined_apply`` carries a ``jax.custom_vjp`` so
pipeline-parallel training works end to end: the forward stashes each
stage's *inputs*, one activation per tick per rank — the GPipe stash,
``O(n_micro + S)`` activations per rank (everything *inside* a stage is
rematerialized; the interleaved 1F1B schedule that would bound the stash
at ``O(S)`` is a ROADMAP follow-up) — and the backward runs the reverse
schedule: output cotangents enter the last stage one per tick and hop
*backwards* along the ring (the forward neighbour push transposed), each
rank replaying its stage VJP against the stashed input and accumulating
its parameter gradient locally.  Backward ticks mirror forward ticks
one-for-one, so the wire volume is exactly doubled and stays
neighbour-only.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax

from jax.sharding import PartitionSpec as P

from jax import shard_map
from repro.dist.collectives import ppermute, psum


def _pipe_fwd_local(stage_fn, axis, n_stages, n_micro, with_stash,
                    p_local, x_all):
    s = lax.axis_index(axis)
    p_here = jax.tree.map(lambda a: a[0], p_local)  # drop stage dim
    fwd = [(i, i + 1) for i in range(n_stages - 1)]
    is_first = (s == 0)
    is_last = (s == n_stages - 1)
    recv = jnp.zeros_like(x_all[0])
    acc = jnp.zeros_like(x_all)
    stash = []
    for t in range(n_micro + n_stages - 1):
        feed = x_all[t] if t < n_micro else jnp.zeros_like(x_all[0])
        h_in = jnp.where(is_first, feed, recv)
        if with_stash:
            stash.append(h_in)
        h_out = stage_fn(p_here, h_in)
        m = t - (n_stages - 1)  # microbatch index leaving the pipe
        if 0 <= m < n_micro:
            acc = acc.at[m].set(jnp.where(is_last, h_out, 0.0))
        if fwd and t < n_micro + n_stages - 2:
            recv = ppermute(h_out, axis, fwd, tag="pipe_fwd")
    # only the last stage holds real outputs; psum replicates them
    out = psum(acc, axis, tag="pipe_out")
    if not with_stash:
        return out
    return out, jnp.stack(stash)[None]  # leading stage dim for P(axis)


def _pipe_bwd_local(stage_fn, axis, n_stages, n_micro,
                    p_local, stash_local, g_all):
    """Reverse schedule: cotangents enter the last stage and hop backwards;
    each rank replays its stage VJP at the stashed input."""
    s = lax.axis_index(axis)
    p_here = jax.tree.map(lambda a: a[0], p_local)
    stash = stash_local[0]                      # [T, mb, ...]
    bwd_perm = [(i + 1, i) for i in range(n_stages - 1)]
    is_first = (s == 0)
    is_last = (s == n_stages - 1)
    recv = jnp.zeros_like(g_all[0])
    dx = jnp.zeros_like(g_all)
    dp = jax.tree.map(lambda a: jnp.zeros_like(a[0]), p_local)
    T = n_micro + n_stages - 1
    for t in reversed(range(T)):
        m = t - (n_stages - 1)
        gseed = g_all[m] if 0 <= m < n_micro else jnp.zeros_like(g_all[0])
        dh_out = jnp.where(is_last, gseed, recv)
        _, vjp_f = jax.vjp(stage_fn, p_here, stash[t])
        dpt, dh_in = vjp_f(dh_out)
        dp = jax.tree.map(jnp.add, dp, dpt)
        if bwd_perm and t > 0:
            recv = ppermute(dh_in, axis, bwd_perm, tag="pipe_bwd")
        if t < n_micro:  # rank 0 consumed x[t] at tick t
            dx = dx.at[t].set(jnp.where(is_first, dh_in, 0.0))
    dx = psum(dx, axis, tag="pipe_dx")  # only rank 0 holds real
    # input cotangents
    dp = jax.tree.map(lambda a: a[None], dp)  # restore the stage dim
    return dp, dx


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _pipelined(stage_fn, mesh, axis, params, x):
    n_stages, n_micro = mesh.shape[axis], x.shape[0]
    spec_tree = jax.tree.map(lambda _: P(axis), params)
    fn = shard_map(
        functools.partial(_pipe_fwd_local, stage_fn, axis, n_stages,
                          n_micro, False),
        mesh=mesh, in_specs=(spec_tree, P()), out_specs=P(),
        check_vma=False)
    return fn(params, x)


def _pipelined_fwd(stage_fn, mesh, axis, params, x):
    n_stages, n_micro = mesh.shape[axis], x.shape[0]
    spec_tree = jax.tree.map(lambda _: P(axis), params)
    fn = shard_map(
        functools.partial(_pipe_fwd_local, stage_fn, axis, n_stages,
                          n_micro, True),
        mesh=mesh, in_specs=(spec_tree, P()),
        out_specs=(P(), P(axis)), check_vma=False)
    out, stash = fn(params, x)
    return out, (params, stash)


def _pipelined_bwd(stage_fn, mesh, axis, res, g):
    params, stash = res
    n_stages, n_micro = mesh.shape[axis], g.shape[0]
    spec_tree = jax.tree.map(lambda _: P(axis), params)
    fn = shard_map(
        functools.partial(_pipe_bwd_local, stage_fn, axis, n_stages,
                          n_micro),
        mesh=mesh, in_specs=(spec_tree, P(axis), P()),
        out_specs=(spec_tree, P()), check_vma=False)
    return fn(params, stash, g)


_pipelined.defvjp(_pipelined_fwd, _pipelined_bwd)


def pipelined_apply(stage_fn: Callable[[Any, Any], Any], params, x, mesh,
                    *, axis: str = "pod"):
    """Run ``x`` through ``S = mesh.shape[axis]`` stages of ``stage_fn``.

    ``params``: pytree whose leaves have a leading stage dimension ``S``
    (rank ``s`` consumes slice ``s``).  ``x``: ``[n_micro, mb, ...]``
    microbatched input, replicated.  Returns the final-stage output
    ``[n_micro, mb, ...]`` replicated across the axis.

    ``stage_fn(stage_params, h) -> h`` must map activations to activations
    of the same shape (each stage's output feeds the next stage).

    Differentiable: the custom VJP runs the reverse pipeline schedule
    (see module docstring), returning per-stage parameter gradients with
    the same leading stage dimension.
    """
    n_stages = mesh.shape[axis]
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        if leaf.shape[:1] != (n_stages,):
            raise ValueError(
                f"param leaf {jax.tree_util.keystr(path)} has leading dim "
                f"{leaf.shape[:1]}, expected ({n_stages},) = mesh.shape"
                f"[{axis!r}] (one slice per pipeline stage)")
    return _pipelined(stage_fn, mesh, axis, params, x)
