"""GPipe-style pipeline parallelism over the 'pod' mesh axis.

At multi-pod scale the cross-pod (DCN) link is the slowest in the system;
FSDP/TP traffic must stay inside a pod.  Two strategies compose in this
framework:

  * default: the 'pod' axis extends **data parallelism** — only gradient
    all-reduces cross pods (optionally int8-compressed,
    `parallel/compression.py`);
  * optional: the layer stack is split into one **pipeline stage per pod**
    (this module).  Only (microbatch, seq, d_model) activations cross the
    pod boundary once per microbatch per direction — orders of magnitude
    less DCN traffic than FSDP weight gathers would need.

Implementation: `shard_map` over the 'pod' axis; each pod holds
`n_layers / n_stages` layers' params (sharded inside the pod by the usual
TP/FSDP rules, which see only the remaining mesh axes).  The classic
GPipe schedule runs `n_micro + n_stages - 1` ticks; each tick every stage
processes one microbatch slot and hands its output to the next stage with
`jax.lax.ppermute`.  Bubble fraction = (S-1)/(M+S-1).

The schedule is expressed with `jax.lax.scan` over ticks so it lowers to
a single fused loop (no Python unrolling at trace time).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pipeline_stages(n_layers: int, n_stages: int):
    """Evenly partition layers into contiguous stages."""
    assert n_layers % n_stages == 0, (n_layers, n_stages)
    per = n_layers // n_stages
    return [(s * per, (s + 1) * per) for s in range(n_stages)]


def gpipe(stage_fn, n_stages: int, *, axis: str = "pod"):
    """Build the per-shard GPipe schedule body.

    ``stage_fn(stage_params, x) -> x`` applies this stage's layer block to
    one microbatch of activations (B_micro, S, d).  Returns a function
    ``run(stage_params, micro_x) -> micro_y`` to be used under
    ``shard_map`` where ``axis`` indexes the stage:

        micro_x: (n_micro, B_micro, S, d)  on stage 0 (others ignore it)
        micro_y: (n_micro, B_micro, S, d)  from the last stage
    """

    def run(stage_params, micro_x):
        sid = jax.lax.axis_index(axis)
        n_micro = micro_x.shape[0]
        ticks = n_micro + n_stages - 1
        buf = jnp.zeros_like(micro_x)  # output slots (valid on last stage)

        def tick(carry, t):
            buf, inflight = carry
            # stage 0 injects microbatch t (if any); others take the
            # activation handed over by the previous stage
            x_in = jnp.where(
                sid == 0,
                micro_x[jnp.clip(t, 0, n_micro - 1)],
                inflight,
            )
            y = stage_fn(stage_params, x_in)
            # hand to next stage; the last stage's output goes to buf
            nxt = jax.lax.ppermute(
                y, axis, [(i, i + 1) for i in range(n_stages - 1)]
            )
            out_slot = t - (n_stages - 1)
            land = (sid == n_stages - 1) & (out_slot >= 0)
            buf = jnp.where(
                land,
                buf.at[jnp.clip(out_slot, 0, n_micro - 1)].set(y),
                buf,
            )
            return (buf, nxt), None

        (buf, _), _ = jax.lax.scan(
            tick, (buf, jnp.zeros_like(micro_x[0])), jnp.arange(ticks)
        )
        # only the last stage holds outputs; psum replicates them to all
        # pods (zeros elsewhere), satisfying the replicated out_spec
        return jax.lax.psum(buf, axis)

    return run


def pipeline_forward(layer_fn, params_stacked, x, mesh, *, n_micro: int,
                     axis: str = "pod"):
    """Full pipeline forward: split batch into microbatches, run GPipe.

    ``layer_fn(layer_params, x) -> x``; ``params_stacked``: pytree with a
    leading (n_layers, ...) dim; layers are split into one stage per pod.
    ``x``: (B, S, d) with B % n_micro == 0.
    """
    n_stages = mesh.shape[axis]
    b, s, d = x.shape
    assert b % n_micro == 0
    micro = x.reshape(n_micro, b // n_micro, s, d)

    def stage_fn(stage_params, xm):
        # under shard_map the local view keeps a leading stage dim of 1
        stage_params = jax.tree.map(lambda p: p[0], stage_params)

        def body(c, lp):
            return layer_fn(lp, c), None
        out, _ = jax.lax.scan(body, xm, stage_params)
        return out

    run = gpipe(stage_fn, n_stages, axis=axis)

    n_layers = jax.tree.leaves(params_stacked)[0].shape[0]
    per = n_layers // n_stages
    # reshape layers to (n_stages, per, ...) so shard_map splits stages
    staged = jax.tree.map(
        lambda p: p.reshape(n_stages * per, *p.shape[1:]).reshape(
            n_stages, per, *p.shape[1:]
        ),
        params_stacked,
    )

    shmap = jax.shard_map(
        run,
        mesh=mesh,
        in_specs=(
            jax.tree.map(lambda _: P(axis), staged),
            P(),  # microbatches replicated in; stage 0 reads them
        ),
        out_specs=P(),
        check_vma=False,
    )
    out = shmap(jax.tree.map(lambda p: p, staged), micro)
    return out.reshape(b, s, d)
