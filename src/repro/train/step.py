"""Train-step builders: jit+GSPMD (default) and shard_map compressed-DP.

The default step relies on in_shardings (params per the TP/EP rules, batch
over DP axes) and GSPMD propagation; gradient all-reduce, TP collectives and
EP dispatch come out of the partitioner.  Microbatch gradient accumulation is
a ``lax.scan`` over a leading accum dim.  ``remat`` applies
``jax.checkpoint`` to the scanned layer body (see models/transformer).

``make_compressed_dp_step`` is the explicit-collective variant: pure DP under
``shard_map`` with int8 error-feedback compressed gradient all-reduce
(optim/grad_compress.py) — the distributed-optimization path for bandwidth-
constrained inter-pod links.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp

from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.optim.adamw import adamw_update
from repro.optim.grad_compress import compressed_psum
from repro.optim.schedule import cosine_schedule
from repro.train.state import TrainState


def make_train_step(
    loss_fn: Callable,
    cfg,
    *,
    accum: int = 1,
    peak_lr: float = 3e-4,
    warmup_steps: int = 100,
    total_steps: int = 10_000,
    weight_decay: float = 0.1,
    donate: bool = True,
    jit: bool = True,
    **loss_kwargs,
):
    """Returns ``step(state, batch) → (state, metrics)``.

    With ``accum > 1`` the batch must carry a leading accum dim; gradients
    are averaged across microbatches inside a scan (memory-flat).
    ``jit=False`` returns the raw function (the dry-run re-jits it with
    explicit in_shardings).
    """

    def grads_of(params, batch):
        return jax.value_and_grad(lambda p: loss_fn(p, batch, cfg, **loss_kwargs))(
            params
        )

    def step(state: TrainState, batch):
        if accum > 1:
            def micro(carry, mb):
                acc, loss_acc = carry
                loss, g = grads_of(state.params, mb)
                acc = jax.tree.map(jnp.add, acc, g)
                return (acc, loss_acc + loss), None

            zero = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state.params
            )
            (gsum, loss_sum), _ = jax.lax.scan(micro, (zero, jnp.float32(0)), batch)
            grads = jax.tree.map(lambda g: g / accum, gsum)
            loss = loss_sum / accum
        else:
            loss, grads = grads_of(state.params, batch)

        lr = cosine_schedule(
            state.step,
            peak_lr=peak_lr,
            warmup_steps=warmup_steps,
            total_steps=total_steps,
        )
        params, opt, gnorm = adamw_update(
            state.params, grads, state.opt, lr, weight_decay=weight_decay
        )
        new_state = TrainState(params=params, opt=opt, step=state.step + 1)
        return new_state, {"loss": loss, "gnorm": gnorm, "lr": lr}

    if not jit:
        return step
    return jax.jit(step, donate_argnums=(0,) if donate else ())


def make_compressed_dp_step(
    loss_fn: Callable,
    cfg,
    mesh: Mesh,
    dp_axis: str = "data",
    *,
    peak_lr: float = 3e-4,
    warmup_steps: int = 100,
    total_steps: int = 10_000,
):
    """Pure-DP shard_map step with int8 error-feedback gradient compression.

    Params replicated, batch sharded over ``dp_axis``; the gradient
    all-reduce carries int8 payloads; the quantization residual lives in a
    per-shard error buffer threaded through the state.
    """

    def inner(params, opt, step, err, batch):
        loss, grads = jax.value_and_grad(lambda p: loss_fn(p, batch, cfg))(params)
        grads, err = compressed_psum(grads, err, dp_axis)
        loss = jax.lax.pmean(loss, dp_axis)
        lr = cosine_schedule(
            step, peak_lr=peak_lr, warmup_steps=warmup_steps, total_steps=total_steps
        )
        params, opt, gnorm = adamw_update(params, grads, opt, lr)
        return params, opt, step + 1, err, {"loss": loss, "gnorm": gnorm}

    def specs_like(tree, spec):
        return jax.tree.map(lambda _: spec, tree)

    def step_fn(state: TrainState, err, batch):
        batch_specs = jax.tree.map(
            lambda x: P(dp_axis, *([None] * (x.ndim - 1))), batch
        )
        f = shard_map(
            inner,
            mesh=mesh,
            in_specs=(
                specs_like(state.params, P()),
                specs_like(state.opt, P()),
                P(),
                specs_like(err, P()),
                batch_specs,
            ),
            out_specs=(
                specs_like(state.params, P()),
                specs_like(state.opt, P()),
                P(),
                specs_like(err, P()),
                {"loss": P(), "gnorm": P()},
            ),
            check_vma=False,
        )
        params, opt, step, err, metrics = f(
            state.params, state.opt, state.step, err, batch
        )
        return TrainState(params, opt, step), err, metrics

    return jax.jit(step_fn)
