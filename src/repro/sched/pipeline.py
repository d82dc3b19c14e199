"""bittide-scheduled pipeline parallelism (the paper's §1.4 application).

In a logically synchronous cluster, stage-to-stage activation transfers
have *constant logical latency*, so the pipeline schedule is a static
timetable computed before execution (core.schedule.pipeline_schedule) —
no handshakes, acks, or barriers; each stage issues its microbatch at a
precomputed localtick and the receive tick is exact.

On a JAX mesh the same structure maps to `shard_map` + `lax.ppermute`:
the timetable's hop ordering becomes the (static) unrolled step loop, and
the queue-depth bound that `verify_bounded` checks corresponds to the
double-buffer slots the ppermute ring needs.  `plan` computes/verifies the
timetable; `pipeline_apply` executes it.

This module is the explicit-collectives exception in the framework (GSPMD
everywhere else) because AOT-scheduled point-to-point movement *is* the
paper's contribution mapped to training.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp

from repro.core.schedule import (LogicalSynchronyNetwork, StaticSchedule,
                                 pipeline_schedule, verify_bounded)

__all__ = ["PipelinePlan", "plan", "pipeline_apply"]


@dataclasses.dataclass
class PipelinePlan:
    num_stages: int
    num_microbatches: int
    schedule: StaticSchedule
    bounded: bool
    queue_depth_frames: int

    @property
    def makespan_ticks(self) -> int:
        return self.schedule.makespan_ticks

    @property
    def bubble_fraction(self) -> float:
        """Fill/drain bubble of the static schedule (GPipe: (S-1)/(S-1+M))."""
        s, m = self.num_stages, self.num_microbatches
        return (s - 1) / (s - 1 + m)


def plan(lsn: LogicalSynchronyNetwork, stages, num_microbatches: int,
         fwd_ticks: int, bwd_ticks: int, activation_frames: int,
         queue_depth_frames: int = 1 << 16) -> PipelinePlan:
    sched = pipeline_schedule(lsn, stages, num_microbatches, fwd_ticks,
                              bwd_ticks, activation_frames)
    return PipelinePlan(
        num_stages=len(stages), num_microbatches=num_microbatches,
        schedule=sched,
        bounded=verify_bounded(sched, lsn, queue_depth_frames),
        queue_depth_frames=queue_depth_frames)


def pipeline_apply(stage_fn: Callable, stage_params, x, mesh, axis: str,
                   num_microbatches: int):
    """GPipe-style forward pipeline over mesh axis `axis`.

    stage_fn(params_slice, h) -> h, applied by each of the S devices along
    `axis` to the microbatch currently resident; microbatches enter at
    stage 0 and exit at stage S-1 after S-1 ppermute hops per microbatch.

    stage_params: pytree with leading dim S (one slice per stage), sharded
    over `axis`.
    x: (M, mb, ...) microbatched input, replicated (the demo scale is small;
    stage 0 selects its microbatch by index).

    Returns (M, mb, ...) outputs in microbatch order.
    """
    s = mesh.shape[axis]
    m = num_microbatches
    steps = m + s - 1

    def body(params_slice, xs):
        idx = jax.lax.axis_index(axis)
        params_local = jax.tree.map(lambda p: p[0], params_slice)
        h = jnp.zeros_like(xs[0])
        outs = jnp.zeros((m,) + xs.shape[1:], xs.dtype)
        perm = [(i, i + 1) for i in range(s - 1)]
        for t in range(steps):  # static unroll == the AOT timetable
            # stage 0 ingests microbatch t (if any); others take the wire
            take_new = jnp.logical_and(idx == 0, t < m)
            h = jnp.where(take_new, xs[min(t, m - 1)], h)
            h = stage_fn(params_local, h)
            # stage S-1 retires microbatch t-(S-1)
            mb_idx = t - (s - 1)
            retire = jnp.logical_and(idx == s - 1, mb_idx >= 0)
            outs = jax.lax.cond(
                retire,
                lambda o: o.at[max(mb_idx, 0)].set(h),
                lambda o: o, outs)
            # the scheduled hop: stage i -> i+1
            h = jax.lax.ppermute(h, axis, perm)
        # collect results from the last stage
        outs = jax.lax.psum(jnp.where(idx == s - 1, outs, jnp.zeros_like(outs)),
                            axis)
        return outs

    from jax.sharding import PartitionSpec as P
    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(P(axis), P()), out_specs=P(),
                       check_vma=False)
    return fn(stage_params, x)
