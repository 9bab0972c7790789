"""Pipeline parallelism (GPipe schedule) over the `pod` axis.

For multi-pod meshes the inter-pod links are the scarcest resource; pipeline
parallelism sends only layer activations across pods — one
(microbatch, seq, d_model) tensor per stage boundary per tick — instead of
gradient/param traffic over the slow axis. This module provides the schedule
as a reusable combinator:

  y = gpipe(stage_fn, stage_params, x, n_micro, axis="pod", mesh=mesh)

  - `stage_params` leaves carry a leading stage axis sharded over `axis`
    (each pod holds ONLY its stage's parameters);
  - activations hop stage->stage+1 with `jax.lax.ppermute` (the canonical
    pipeline collective);
  - the bubble is the standard (S-1)/(M+S-1) GPipe bubble; microbatches keep
    it small.

Used by tests/test_pipeline.py (2-stage compile + exactness vs the
unpipelined reference) and available as a `pp` building block for pod-scale
depth sharding.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def gpipe(stage_fn, stage_params, x, n_micro: int, *, axis: str, mesh):
    """stage_fn(params_slice, x_micro) -> y_micro, applied as S pipeline
    stages over mesh axis `axis`. x: (B, ...) with B % n_micro == 0.
    Returns the same-shaped output after all S stages."""
    n_stages = mesh.shape[axis]
    b = x.shape[0]
    assert b % n_micro == 0

    def body(params_local, x_rep):
        """Runs on every pod; params_local: this pod's stage params
        (leading stage axis stripped to size 1)."""
        sid = jax.lax.axis_index(axis)
        p_stage = jax.tree.map(lambda a: a[0], params_local)
        micro = x_rep.reshape(n_micro, b // n_micro, *x_rep.shape[1:])
        n_ticks = n_micro + n_stages - 1
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def tick(carry, t):
            buf, out = carry
            # stage sid works on microbatch (t - sid) when in range
            mb_id = t - sid
            active = (mb_id >= 0) & (mb_id < n_micro)
            # stage 0 reads fresh input; others read the handed-over buf
            x_in = jnp.where(sid == 0,
                             micro[jnp.clip(mb_id, 0, n_micro - 1)], buf)
            y = stage_fn(p_stage, x_in)
            y = jnp.where(active, y, buf)
            # last stage deposits finished microbatches
            done_id = t - (n_stages - 1)
            deposit = (sid == n_stages - 1) & (done_id >= 0) & (done_id < n_micro)
            out = jax.lax.cond(
                deposit,
                lambda o: jax.lax.dynamic_update_slice(
                    o, y[None], (jnp.clip(done_id, 0, n_micro - 1),)
                    + (0,) * y.ndim),
                lambda o: o, out)
            # hand activations to the next stage
            buf_next = jax.lax.ppermute(y, axis, perm)
            return (buf_next, out), None

        buf0 = jnp.zeros_like(micro[0])
        out0 = jnp.zeros_like(micro)
        (b_, out), _ = jax.lax.scan(tick, (buf0, out0),
                                    jnp.arange(n_ticks))
        # every pod computed `out`; only the last stage's is real — share it
        out = jax.lax.psum(
            jnp.where(sid == n_stages - 1, out, jnp.zeros_like(out)), axis)
        return out.reshape(b, *x_rep.shape[1:])

    in_specs = (jax.tree.map(lambda _: P(axis), stage_params), P())
    smap = jax.shard_map(body, mesh=mesh, in_specs=in_specs, out_specs=P(),
                         check_vma=False)
    return smap(stage_params, x)
