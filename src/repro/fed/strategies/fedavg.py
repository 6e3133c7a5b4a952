"""FedAvg-family strategies: local solves, model-delta payloads.

Clients run E local epochs and upload their model *delta* w_k − w_t.  The
delta form makes one aggregation path serve both modes: synchronously,
w_t + Σ (n_k/n)(w_k − w_t) equals FedAvg's weighted model mean; under
buffered-async aggregation a stale delta is a (staleness-discounted)
correction to the *current* params rather than a pull back toward the
stale starting point — so the plan is ``summable`` and async support
falls out.  The paper's Theorem 3 accounting is unchanged: the server
still learns k distinct iterates, so the uploads are NOT in-network
tree-aggregatable (O(k·d) at the root).
"""
from __future__ import annotations

import jax

from repro.edge import device as edge_device
from repro.fed import client as fed_client
from repro.fed.strategies.base import FedStrategy, PhasePlan, RoundPlan, register
from repro.models import cnn


class LocalSolveStrategy(FedStrategy):
    """Shared scaffolding: softmax model, delta payloads, FedAvg plan.
    Subclasses provide ``_local_solve(params, batches, rng)``."""

    def _build(self, key) -> None:
        self.params, _ = cnn.init(self.mcfg, key)
        def _loss(p, b):
            return cnn.softmax_loss(p, self.mcfg, b)
        self._loss = _loss
        self._eval = jax.jit(lambda p, x, y: cnn.accuracy(p, self.mcfg, x, y))
        self._build_solver()

    def _build_solver(self) -> None:
        raise NotImplementedError

    def _local_solve(self, params, batches):
        raise NotImplementedError

    def _make_plan(self) -> RoundPlan:
        d = self.n_params()
        e = self.fcfg.local_epochs
        return RoundPlan(
            # the paper's accounting: k distinct local models reach the
            # server — O(k·d), no in-network aggregation gain (Thm 3)
            phases=(PhasePlan("local_model", down_floats=d, up_floats=d,
                              codec=self.codec, aggregatable=False),),
            flops=lambda n: edge_device.flops_local_sgd(self.n_params(), n, e),
            summable=True,  # delta payloads sum — async- and sparsify-eligible
        )

    def client_step(self, data, rng, context=None):
        xs, ys = data
        batches = self._put(fed_client.stack_batches(
            xs, ys, self.fcfg.batch_size, self.fcfg.local_epochs, rng))
        p, loss = self._dispatch(self._local_solve, self.params, batches)
        delta = jax.tree.map(lambda a, b: a - b, p, self.params)
        return delta, self._wait(loss)

    def server_step(self, aggregate) -> None:
        self.params = jax.tree.map(lambda p, dl: p + dl,
                                   self.params, aggregate)


@register("fedavg_sgd")
class FedAvgSgdStrategy(LocalSolveStrategy):
    """FedAvg with local SGD [McMahan et al.]."""

    def _build_solver(self) -> None:
        self._sgd = fed_client.make_local_sgd_fn(self._loss)

    def _local_solve(self, params, batches):
        return self._sgd(params, batches, lr=float(self.fcfg.learning_rate))


@register("fedavg_adam")
class FedAvgAdamStrategy(LocalSolveStrategy):
    """Table II's "FedAvg-based Adam": clients run local Adam, the server
    averages (Adam lr convention: ~10x smaller than the SGD lr)."""

    def _build_solver(self) -> None:
        self._adam = fed_client.make_local_adam_fn(self._loss)

    def _local_solve(self, params, batches):
        return self._adam(params, batches,
                          lr=float(self.fcfg.learning_rate) * 0.1)
