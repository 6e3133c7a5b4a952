"""FedDANE [Li et al., Asilomar 2019] as a two-phase FedStrategy.

Phase 1 (``round_context``): broadcast w_t, every client uploads its full
local gradient; the aggregate ∇f(w_t) is summable (tree-aggregatable).
Phase 2: broadcast the global gradient, clients run inner SGD on the
DANE-corrected objective and upload their local models — k distinct
iterates, NOT aggregatable, which is FedDANE's O(2·k·d) in Theorem 3's
terms and why the plan is not ``summable`` (no async until a summable
surrogate strategy is registered).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import aggregation
from repro.edge import device as edge_device
from repro.fed import client as fed_client
from repro.fed.strategies.base import FedStrategy, PhasePlan, RoundPlan, register
from repro.models import cnn


@register("feddane")
class FedDaneStrategy(FedStrategy):
    def _build(self, key) -> None:
        self.params, _ = cnn.init(self.mcfg, key)
        def _loss(p, b):
            return cnn.softmax_loss(p, self.mcfg, b)
        self._loss = _loss
        self._grad_fim = fed_client.make_grad_fim_fn(
            self._loss, cnn.per_example_loss_fn(self.mcfg), self.fcfg.fim_mode,
            kernels=getattr(self.fcfg, "kernels", "auto"))
        self._dane = fed_client.make_feddane_fn(self._loss)
        self._eval = jax.jit(lambda p, x, y: cnn.accuracy(p, self.mcfg, x, y))
        # the context phase's gradient uploads route through the codec too
        # (stateless — no error-feedback accumulator for the pre-phase)
        self._ckey = jax.random.PRNGKey(self.fcfg.seed + 29)

    def _make_plan(self) -> RoundPlan:
        d = self.n_params()
        e = self.fcfg.local_epochs
        return RoundPlan(
            phases=(
                PhasePlan("gradient", down_floats=d, up_floats=d,
                          codec=self.codec, aggregatable=True),
                PhasePlan("inner_solve", down_floats=d, up_floats=d,
                          codec=self.codec, aggregatable=False),
            ),
            flops=lambda n: (edge_device.flops_grad_fim(self.n_params(), n)
                             + edge_device.flops_local_sgd(self.n_params(), n, e)),
            summable=False,
        )

    def round_context(self, datas, rng):
        """Phase 1: full local gradients -> the cohort's global gradient;
        each client's context is (global_grad, its own ∇F_k(w_t))."""
        if not datas:
            return []
        local_grads, sent_grads, weights = [], [], []
        for xs, ys in datas:
            batch = self._put({"x": xs, "y": ys})
            g, _, _ = self._dispatch(self._grad_fim, self.params, batch)
            local_grads.append(g)  # the client keeps its exact gradient
            if not self.codec.identity:
                self._ckey, sub = jax.random.split(self._ckey)
                g, _ = self.codec.roundtrip(g, sub)
            sent_grads.append(g)   # the server only sees the wire version
            weights.append(len(xs))
        w = jnp.asarray(weights, jnp.float32)
        global_grad = aggregation.weighted_mean(
            jax.tree.map(lambda *t: jnp.stack(t), *sent_grads), w)
        return list(zip([global_grad] * len(datas), local_grads, strict=True))

    def client_step(self, data, rng, context=None):
        xs, ys = data
        global_grad, g0 = context
        batches = self._put(fed_client.stack_batches(
            xs, ys, self.fcfg.batch_size, self.fcfg.local_epochs, rng))
        p, loss = self._dispatch(self._dane, self.params, batches,
                                 global_grad, g0,
                                 lr=float(self.fcfg.learning_rate), mu=0.1)
        return p, self._wait(loss)

    def server_step(self, aggregate) -> None:
        self.params = aggregate
