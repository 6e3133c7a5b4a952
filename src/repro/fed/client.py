"""Client-side computation (paper Alg. 1 ClientUpdate / Alg. 2 Step 2).

All client functions are pure and jitted once per model; the Python-level
federated loop (server.py) feeds them per-client data.  The same functions
are vmapped by simulator.py for the mesh-parallel cohort path.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import baselines, fim


def make_grad_fim_fn(loss_fn: Callable, per_example_loss: Callable | None,
                     fim_mode: str = "per_example", kernels: str = "off"):
    """Client update for Algorithm 1: returns (grad, Γ_k, loss).

    loss_fn(params, batch) -> scalar; per_example_loss(params, x, y) ->
    scalar (needed for the exact Eq. 9 diagonal).  ``kernels``
    (FedConfig.kernels) routes the Fisher square+mean through the fused
    Pallas op (repro.kernels.ops.fim_diag_update)."""

    @jax.jit
    def client_grad_fim(params, batch):
        loss, grad = jax.value_and_grad(loss_fn)(params, batch)
        if fim_mode == "per_example" and per_example_loss is not None:
            diag = fim.per_example_diag(per_example_loss, params,
                                        batch["x"], batch["y"],
                                        kernels=kernels)
        else:
            diag = fim.microbatch_diag(grad, kernels=kernels)
        return grad, diag, loss

    return client_grad_fim


def make_local_sgd_fn(loss_fn: Callable):
    """FedAvg client: E epochs of minibatch SGD over stacked local batches.

    batches: pytree with leading (n_batches, ...) dim; scanned."""

    @functools.partial(jax.jit, static_argnames=("lr",))
    def local_sgd(params, batches, lr: float):
        def step(p, batch):
            loss, grad = jax.value_and_grad(loss_fn)(p, batch)
            p = jax.tree.map(lambda w, g: w - lr * g.astype(w.dtype), p, grad)
            return p, loss

        params, losses = jax.lax.scan(step, params, batches)
        return params, jnp.mean(losses)

    return local_sgd


def make_local_adam_fn(loss_fn: Callable):
    """FedAvg-based Adam client: E epochs of minibatch Adam locally
    (the paper's 'FedAvg-based Adam' baseline, Table II)."""

    @functools.partial(jax.jit, static_argnames=("lr",))
    def local_adam(params, batches, lr: float):
        state = baselines.adam_init(params)

        def step(carry, batch):
            p, st = carry
            loss, grad = jax.value_and_grad(loss_fn)(p, batch)
            p, st, _ = baselines.adam_update(st, p, grad, lr)
            return (p, st), loss

        (params, _), losses = jax.lax.scan(step, (params, state), batches)
        return params, jnp.mean(losses)

    return local_adam


def make_feddane_fn(loss_fn: Callable):
    """FedDANE client: inner SGD on the DANE-corrected local objective."""

    @functools.partial(jax.jit, static_argnames=("lr", "mu"))
    def local_dane(params, batches, global_grad, local_grad_at_start,
                   lr: float, mu: float):
        start = params

        def step(p, batch):
            loss, g = jax.value_and_grad(loss_fn)(p, batch)
            g = baselines.feddane_inner_grad(g, local_grad_at_start, global_grad,
                                             p, start, mu)
            p = jax.tree.map(lambda w, gi: w - lr * gi.astype(w.dtype), p, g)
            return p, loss

        params, losses = jax.lax.scan(step, params, batches)
        return params, jnp.mean(losses)

    return local_dane


def make_fedprox_fn(loss_fn: Callable):
    """FedProx client [Li et al., MLSys 2020]: inner SGD on the proximal
    objective  F_k(w) + (mu/2)||w - w_t||²  — bounds local drift under
    non-IID data."""

    @functools.partial(jax.jit, static_argnames=("lr", "mu"))
    def local_prox(params, batches, lr: float, mu: float):
        start = params

        def step(p, batch):
            loss, g = jax.value_and_grad(loss_fn)(p, batch)
            g = jax.tree.map(
                lambda gi, w, w0: gi + mu * (w - w0).astype(gi.dtype),
                g, p, start)
            p = jax.tree.map(lambda w, gi: w - lr * gi.astype(w.dtype), p, g)
            return p, loss

        params, losses = jax.lax.scan(step, params, batches)
        return params, jnp.mean(losses)

    return local_prox


def stack_batches(xs, ys, batch_size: int, epochs: int, rng):
    """Materialize E epochs of shuffled minibatches as stacked host
    arrays for lax.scan (static shapes: drops ragged tails); the strategy
    copies them to the device (``FedStrategy._put``)."""
    n = len(xs)
    bs = min(batch_size, n)
    nb = max(1, n // bs)
    bx, by = [], []
    for _ in range(epochs):
        order = rng.permutation(n)
        for i in range(nb):
            idx = order[i * bs:(i + 1) * bs]
            bx.append(xs[idx])
            by.append(ys[idx])
    return {"x": np.stack(bx), "y": np.stack(by)}
