"""Algorithm 1 (FIM-driven distributed L-BFGS) as a FedStrategy.

Clients upload (∇F_k, Γ_k) — summable, so the plan is fully
tree-aggregatable (Theorem 3's O(d log τ)) and async-eligible; the server
runs the FIM-L-BFGS quasi-Newton step on the aggregated pair, exchanging
only the (2m+1)² Gram scalars on top.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import aggregation, fim_lbfgs
from repro.edge import device as edge_device
from repro.fed import client as fed_client
from repro.fed.strategies.base import FedStrategy, PhasePlan, RoundPlan, register
from repro.models import cnn


@register("fim_lbfgs")
class FimLbfgsStrategy(FedStrategy):
    def _build(self, key) -> None:
        self.params, _ = cnn.init(self.mcfg, key)
        def _loss(p, b):
            return cnn.softmax_loss(p, self.mcfg, b)
        self._loss = _loss
        kernels = getattr(self.fcfg, "kernels", "auto")
        self._grad_fim = fed_client.make_grad_fim_fn(
            self._loss, cnn.per_example_loss_fn(self.mcfg), self.fcfg.fim_mode,
            kernels=kernels)
        self.ocfg = fim_lbfgs.FimLbfgsConfig(
            learning_rate=self.fcfg.second_order_lr, m=self.fcfg.lbfgs_m,
            damping=self.fcfg.fim_damping, fim_ema=self.fcfg.fim_ema,
            max_step_norm=self.fcfg.max_step_norm, kernels=kernels)
        self.opt_state = fim_lbfgs.init(self.params, self.ocfg)
        self._eval = jax.jit(lambda p, x, y: cnn.accuracy(p, self.mcfg, x, y))

    def _make_plan(self) -> RoundPlan:
        d = self.n_params()
        return RoundPlan(
            phases=(PhasePlan("grad_fim", down_floats=d, up_floats=2.0 * d,
                              codec=self.codec, aggregatable=True),),
            flops=lambda n: edge_device.flops_grad_fim(self.n_params(), n),
            summable=True,
            round_scalars=(2 * self.fcfg.lbfgs_m + 1) ** 2,  # Gram exchange
        )

    def client_step(self, data, rng, context=None):
        xs, ys = data
        # Full local gradient/Fisher (the ERM F_k over D_k, as in
        # DANE/GIANT); stochastic batches are exercised by the LLM-scale
        # path where full data is impossible.
        batch = self._put({"x": xs, "y": ys})
        g, f, loss = self._dispatch(self._grad_fim, self.params, batch)
        return (g, f), self._wait(loss)

    def compress_payload(self, payload, key, residual=None, codec=None):
        out, residual = (codec or self.codec).roundtrip(payload, key, residual)
        g, f = out
        # the Fisher diagonal must stay nonnegative through the roundtrip
        return (g, jax.tree.map(jnp.abs, f)), residual

    def aggregate(self, payloads, weights):
        w = jnp.asarray(weights, jnp.float32)
        grad = aggregation.weighted_mean(
            jax.tree.map(lambda *t: jnp.stack(t), *[p[0] for p in payloads]), w)
        fimd = aggregation.weighted_mean(
            jax.tree.map(lambda *t: jnp.stack(t), *[p[1] for p in payloads]), w)
        return grad, fimd

    def server_step(self, aggregate) -> None:
        grad, fimd = aggregate
        self.params, self.opt_state, _ = fim_lbfgs.update(
            self.opt_state, self.params, grad, fimd, self.ocfg)

    # -- vmapped cohort path (fed/simulator.py) --------------------------
    @property
    def cohort_client_fn(self):
        """Pure (params, batch) -> (grad, Γ, loss), vmappable over a
        stacked cohort batch."""
        return self._grad_fim

    def cohort_server_update(self, opt_state, params, grad, fim_diag):
        """Pure server update for the jitted cohort round_step."""
        return fim_lbfgs.update(opt_state, params, grad, fim_diag, self.ocfg)
