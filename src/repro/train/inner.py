"""Inner (worker-local) training loop: H AdamW steps from a look-ahead
initialization, producing a pseudo-gradient (paper Eq. 2-3)."""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import InnerOptConfig, ModelConfig
from repro.models import Model
from repro.obs.spans import NULL_TRACER
from repro.optim.adamw import AdamState, adamw_update, init_adam

PyTree = Any


class InnerResult(NamedTuple):
    params: PyTree
    opt: AdamState
    losses: jnp.ndarray       # (H,)


@functools.lru_cache(maxsize=32)
def _jitted_step(model: Model, inner_cfg: InnerOptConfig) -> Callable:
    def step(params, opt, batch):
        (loss, _aux), grads = jax.value_and_grad(
            lambda p: model.loss(p, batch), has_aux=True)(params)
        params, opt = adamw_update(params, grads, opt, inner_cfg)
        return params, opt, loss
    return jax.jit(step, donate_argnums=(0, 1))


def run_inner(model: Model, inner_cfg: InnerOptConfig, params: PyTree,
              opt: AdamState, sampler, h_steps: int,
              step_offset: int = 0, tracer=NULL_TRACER) -> InnerResult:
    """H local steps; data drawn from `sampler.sample(step)` per step.
    ``tracer`` times the copy, and each step's sampling, host-to-device
    transfer and dispatch (host clock, no device sync)."""
    step_fn = _jitted_step(model, inner_cfg)
    # the caller keeps theta_bar for the pseudo-gradient; the jitted step
    # donates its params buffer, so work on a copy.
    with tracer.span("round_copy", cat="compute"):
        params = jax.tree.map(jnp.copy, params)
        opt = jax.tree.map(jnp.copy, opt)
    losses = []
    for h in range(h_steps):
        with tracer.span("batch_sample", cat="input"):
            batch = sampler.sample(step_offset + h)
        with tracer.span("batch_to_device", cat="input"):
            batch = jax.tree.map(jnp.asarray, batch)
        with tracer.span("inner_dispatch", cat="compute"):
            params, opt, loss = step_fn(params, opt, batch)
        losses.append(loss)
    return InnerResult(params=params, opt=opt, losses=jnp.stack(losses))


@jax.jit
def pseudo_gradient(theta_init: PyTree, theta_final: PyTree) -> PyTree:
    """Delta = theta_bar - theta_H  (descent displacement, Eq. 3), in fp32.

    One program over both trees, traced once per tree structure and
    dispatched once a round, not one op per leaf. Neither tree is
    donated: the caller keeps theta_bar."""
    return jax.tree.map(
        lambda a, b: (a.astype(jnp.float32) - b.astype(jnp.float32)),
        theta_init, theta_final)


def eval_loss(model: Model, params: PyTree, batch: Dict) -> float:
    loss, _ = jax.jit(lambda p, b: model.loss(p, b))(
        params, jax.tree.map(jnp.asarray,
                             {k: v for k, v in batch.items() if k != "lang"}))
    return float(loss)
