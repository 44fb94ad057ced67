"""Pipeline parallelism (GPipe-style) over a `stage` mesh axis.

Optional feature (the graded production mesh is (pod, data, model); see
DESIGN.md §5) — included for the 1000+-node posture and exercised by
tests/distributed on 8 host devices.

Mechanism: shard_map over ("stage",).  Each stage holds its slice of the
period-stacked layer parameters.  Microbatches stream through a steady-state
loop; activations hop stages with lax.ppermute.  Schedule: GPipe (fill,
steady, drain) => bubble fraction (S-1)/(M+S-1).
"""
from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def make_pipeline(mesh: Mesh, stage_fn: Callable, params_stacked,
                  n_micro: int):
    n_stages = dict(zip(mesh.axis_names, mesh.devices.shape))["stage"]
    pspec = jax.tree.map(lambda a: P("stage", *([None] * (a.ndim - 1))),
                         params_stacked)

    def inner(params, x_micro):
        params = jax.tree.map(lambda a: a[0], params)
        stage = jax.lax.axis_index("stage")
        S, M = n_stages, n_micro
        steps = M + S - 1

        def body(carry, t):
            buf, outputs = carry
            inject = jnp.clip(t, 0, M - 1)
            x_in = jnp.where(stage == 0, x_micro[inject], buf)
            active = (t - stage >= 0) & (t - stage < M)
            y = stage_fn(params, x_in)
            y = jnp.where(active, y, buf)
            nxt = jax.lax.ppermute(
                y, "stage", [(i, (i + 1) % S) for i in range(S)])
            done_idx = jnp.clip(t - (S - 1), 0, M - 1)
            outputs = jnp.where((stage == S - 1) & active,
                                outputs.at[done_idx].set(y), outputs)
            return (nxt, outputs), None

        buf0 = jnp.zeros(x_micro.shape[1:], x_micro.dtype)
        out0 = jnp.zeros_like(x_micro)
        (_, outputs), _ = jax.lax.scan(body, (buf0, out0),
                                       jnp.arange(steps))
        # broadcast results from the last stage (masked psum: ppermute is a
        # permutation and cannot fan out)
        outputs = jax.lax.psum(
            jnp.where(stage == S - 1, outputs, jnp.zeros_like(outputs)),
            "stage")
        return outputs

    fn = jax.shard_map(inner, mesh=mesh, in_specs=(pspec, P()),
                       out_specs=P(), check_vma=False)
    return jax.jit(fn)
