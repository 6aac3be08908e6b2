"""GPipe-style pipeline parallelism over a mesh axis via shard_map +
collective_permute.

For pod-scale training the ``pod`` axis can carry pipeline stages instead
of data parallelism: each stage owns a contiguous slice of layers;
microbatches stream through the pipeline with ``ppermute`` handoffs.  The
schedule is the classic GPipe loop of ``M + S - 1`` ticks (M microbatches,
S stages): stage s computes microbatch m at tick m + s, bubbles padded
with zero work.

This module implements the *forward* pipeline as a composable transform
over any per-stage function; it is exercised by a dry-run lowering test
(compile on the production mesh) and a numerical equivalence test on host
devices (pipeline output == sequential output).
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = ["pipeline_forward"]


def pipeline_forward(mesh, stage_fn: Callable, n_stages: int,
                     axis: str = "pod"):
    """Build a pipelined forward: x [M, B, ...] -> y [M, B, ...].

    ``stage_fn(stage_params, x) -> x`` applies one stage's layers.
    ``stage_params`` must be sharded over ``axis`` on dim 0 (one slice per
    stage).  Microbatch m enters stage 0 at tick m; results exit stage
    S-1 at tick m + S - 1.
    """
    S = n_stages
    perm_fwd = [(i, (i + 1) % S) for i in range(S)]

    def run(stage_params, xs):
        # inside shard_map: stage_params [1, ...] (this stage's slice),
        # xs [M, B, ...] full microbatch stream (replicated over stages)
        my = jax.lax.axis_index(axis)
        params = jax.tree.map(lambda p: p[0], stage_params)
        M = xs.shape[0]
        ticks = M + S - 1

        def tick(carry, t):
            buf = carry                     # [B, ...] in-flight activation
            # stage 0 injects microbatch t from the stream
            inject = jnp.where(t < M, t, 0)
            x_in = jnp.where(my == 0, xs[inject], buf)
            y = stage_fn(params, x_in)
            # pass to the next stage; last stage's output is collected
            buf_next = jax.lax.ppermute(y, axis, perm_fwd)
            out = jnp.where(my == S - 1, y, jnp.zeros_like(y))
            return buf_next, out

        buf0 = jnp.zeros_like(xs[0])
        _, outs = jax.lax.scan(tick, buf0, jnp.arange(ticks))
        # microbatch m exits at tick m + S - 1
        idx = jnp.arange(M) + (S - 1)
        ys = outs[idx]
        # only the last stage holds real outputs; broadcast them
        ys = jax.lax.psum(
            jnp.where(my == S - 1, ys, jnp.zeros_like(ys)), axis)
        return ys

    n_extra = None  # stage params pspec built from caller's tree

    def call(stage_params, xs):
        pspec_params = jax.tree.map(
            lambda _: P(axis), stage_params)
        fn = jax.shard_map(
            run, mesh=mesh,
            in_specs=(pspec_params, P()),
            out_specs=P(), check_vma=False)
        return fn(stage_params, xs)

    return call
