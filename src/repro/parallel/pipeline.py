"""GPipe-style pipeline parallelism via shard_map + ppermute.

Opt-in: the launcher can re-purpose the multi-pod "pod" axis (or a dedicated
"pipe" axis) as pipeline stages — inter-pod links carry only the (micro)batch
activations once per tick, which suits the low inter-pod bandwidth regime.

Schedule: plain GPipe fill-drain over T = M + S - 1 ticks (M microbatches,
S stages).  Bubble fraction = (S-1)/(M+S-1), reported by
:func:`bubble_fraction` and used in the DSE model when the pod axis is a
pipeline axis.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P



def bubble_fraction(n_micro: int, n_stages: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)


def pipeline_apply(fn: Callable, stage_params, x, *, mesh: Mesh,
                   axis: str = "pipe", n_micro: int | None = None):
    """Run ``y = fn(params_s, x)`` through S stages over microbatches.

    stage_params: pytree with leading stage axis S (sharded over ``axis``).
    x: (M, mb, ...) microbatched input (replicated).  fn must preserve the
    activation shape (residual-block stacks do).  Returns (M, mb, ...).
    """
    S = mesh.shape[axis]
    M = x.shape[0] if n_micro is None else n_micro
    T = M + S - 1

    pspec = jax.tree_util.tree_map(lambda _: P(axis), stage_params)

    def run(params, xs):
        # params: leading stage dim of size 1 (this stage's slice)
        params = jax.tree_util.tree_map(lambda a: a[0], params)
        sid = jax.lax.axis_index(axis)
        perm = [(i, i + 1) for i in range(S - 1)]

        def tick(carry, t):
            buf, outs = carry                       # buf: (mb, ...) in transit
            mb_idx = jnp.clip(t, 0, M - 1)
            first_in = jax.lax.dynamic_index_in_dim(xs, mb_idx, 0,
                                                    keepdims=False)
            inp = jnp.where(sid == 0, first_in, buf)
            out = fn(params, inp)
            # stage s processes microbatch t-s at tick t; valid window check
            valid = (t - sid >= 0) & (t - sid < M)
            out = jnp.where(valid, out, jnp.zeros_like(out))
            # last stage records its finished microbatch
            out_idx = jnp.clip(t - (S - 1), 0, M - 1)
            record = (sid == S - 1) & (t - (S - 1) >= 0)
            outs = jax.lax.dynamic_update_index_in_dim(
                outs, jnp.where(record,
                                out, jax.lax.dynamic_index_in_dim(
                                    outs, out_idx, 0, keepdims=False)),
                out_idx, 0)
            nxt = jax.lax.ppermute(out, axis, perm) if S > 1 else out
            return (nxt, outs), None

        outs0 = jnp.zeros_like(xs)
        buf0 = jnp.zeros_like(xs[0])
        (_, outs), _ = jax.lax.scan(tick, (buf0, outs0), jnp.arange(T))
        # only the last stage holds real outputs; broadcast them to all
        outs = jax.lax.psum(
            jnp.where(sid == S - 1, outs, jnp.zeros_like(outs)), axis)
        return outs

    other_axes = [a for a in mesh.axis_names if a != axis]
    in_x_spec = P()      # replicated microbatches (data axis handled outside)
    return jax.shard_map(run, mesh=mesh, in_specs=(pspec, in_x_spec),
                         out_specs=P(), check_vma=False)(stage_params, x)
