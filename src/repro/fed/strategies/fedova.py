"""FedOVA (paper Sec. IV-B, Algorithm 2) as a FedStrategy — optionally
driven by the FIM-L-BFGS server step ("fedova_lbfgs", the paper's claim
that the two contributions compose).

Each client trains only the binary OVA components whose class appears in
its local data and uploads (trained component stack, class-presence
mask); the grouped aggregation (Eq. 11) is a per-class weighted mean, so
the uploads ARE tree-aggregatable in Theorem 3's accounting.  The payload
is *not* summable (the mask-grouped mean needs each client's mask), so
async stays off until a summable surrogate is registered.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import fedova, fim_lbfgs
from repro.edge import device as edge_device
from repro.fed import client as fed_client
from repro.fed import comm
from repro.fed.strategies.base import FedStrategy, PhasePlan, RoundPlan, register
from repro.models import cnn


class FedOvaStrategy(FedStrategy):
    server_opt = "sgd"  # "sgd" (Alg. 2 as written) | "fim_lbfgs"

    def _build(self, key) -> None:
        bcfg = self.mcfg.binary()
        self.bcfg = bcfg
        self.model = fedova.OvaModel(
            components=jax.vmap(lambda k: cnn.init(bcfg, k)[0])(
                jax.random.split(key, self.n_classes)),
            n_classes=self.n_classes,
        )
        def _binary_loss(p, b):
            return cnn.binary_loss(p, bcfg, b)
        self._binary_loss = _binary_loss
        self._local_sgd = fed_client.make_local_sgd_fn(self._binary_loss)
        self._apply = jax.jit(lambda p, x: cnn.apply(p, bcfg, x))
        if self.server_opt == "fim_lbfgs":
            kernels = getattr(self.fcfg, "kernels", "auto")
            self.ocfg = fim_lbfgs.FimLbfgsConfig(
                learning_rate=self.fcfg.second_order_lr, m=self.fcfg.lbfgs_m,
                damping=self.fcfg.fim_damping, fim_ema=self.fcfg.fim_ema,
                max_step_norm=self.fcfg.max_step_norm, kernels=kernels)
            one = jax.tree.map(lambda leaf: leaf[0], self.model.components)
            self.opt_state = jax.vmap(
                lambda _: fim_lbfgs.init(one, self.ocfg))(
                    jnp.arange(self.n_classes))
            self._grad_fim = fed_client.make_grad_fim_fn(
                self._binary_loss, cnn.per_example_loss_fn(bcfg, binary=True),
                self.fcfg.fim_mode, kernels=kernels)

    def n_params(self) -> int:
        """One binary component (the broadcast/upload unit)."""
        if self._n_params_cache is None:
            one = jax.tree.map(lambda leaf: leaf[0], self.model.components)
            self._n_params_cache = comm.tree_n_floats(one)
        return self._n_params_cache

    def _classes_per_client(self) -> int:
        return min(self.fcfg.noniid_l or self.n_classes, self.n_classes)

    def _make_plan(self) -> RoundPlan:
        d = self.n_params()
        n = self.n_classes
        e = self.fcfg.local_epochs
        c = self._classes_per_client()
        return RoundPlan(
            # server broadcasts the full component stack; each client
            # uploads only the components it trained (its local label
            # set), and Eq. 11's grouped mean sums them in-network.
            # up_floats is the plan's *prediction* (and what the ledger
            # meters): exact under non-IID-l partitions (each client
            # holds exactly l labels); for IID shards smaller than the
            # class count it is an upper bound on the data-dependent
            # truth
            phases=(PhasePlan("ova_components", down_floats=float(d * n),
                              up_floats=float(d * c), codec=self.codec,
                              aggregatable=True),),
            flops=lambda nk: edge_device.flops_local_sgd(
                self.n_params(), nk, e) * self._classes_per_client(),
            summable=False,  # the grouped mean needs per-client masks
            scalars_per_client=n,  # class-presence masks
        )

    def client_step(self, data, rng, context=None):
        xs, ys = data
        n = self.model.n_classes
        mask = np.zeros(n, np.float32)
        client_comp = self.model.components  # start from server components
        losses = []
        for c in np.unique(ys):
            c = int(c)
            mask[c] = 1.0
            yb = (ys == c).astype(np.int64)
            batches = self._put(fed_client.stack_batches(
                xs, yb, self.fcfg.batch_size, self.fcfg.local_epochs, rng))
            comp_c = jax.tree.map(lambda leaf, cc=c: leaf[cc],
                                  self.model.components)
            comp_new, loss = self._train_component(c, comp_c, batches)
            client_comp = jax.tree.map(
                lambda full, new, cc=c: full.at[cc].set(new),
                client_comp, comp_new)
            losses.append(self._wait(loss))
        return (client_comp, mask), float(np.mean(losses)) if losses else float("nan")

    def _train_component(self, c, comp_c, batches):
        if self.server_opt == "fim_lbfgs":
            big = {"x": batches["x"].reshape((-1,) + batches["x"].shape[2:]),
                   "y": batches["y"].reshape(-1)}
            g, f, loss = self._dispatch(self._grad_fim, comp_c, big)
            ost = jax.tree.map(lambda s: s[c], self.opt_state)
            comp_new, ost, _ = fim_lbfgs.update(ost, comp_c, g, f, self.ocfg)
            self.opt_state = jax.tree.map(
                lambda s, o: s.at[c].set(o), self.opt_state, ost)
            return comp_new, loss
        return self._dispatch(self._local_sgd, comp_c, batches,
                              lr=float(self.fcfg.learning_rate))

    def compress_payload(self, payload, key, residual=None, codec=None):
        # codec the component stack only: the class-presence mask is
        # metered as scalars and must survive the wire exactly
        comp, mask = payload
        comp, residual = (codec or self.codec).roundtrip(comp, key, residual)
        return (comp, mask), residual

    def aggregate(self, payloads, weights):
        comps = [p[0] for p in payloads]
        masks = [p[1] for p in payloads]
        stacked = jax.tree.map(lambda *t: jnp.stack(t), *comps)
        return stacked, jnp.asarray(np.stack(masks))

    def server_step(self, aggregate) -> None:
        stacked, masks = aggregate
        self.model = fedova.aggregate(self.model, stacked, masks)

    def evaluate(self, x, y) -> float:
        return self._wait(self._dispatch(fedova.accuracy, self._apply,
                                         self.model, x, y))


register("fedova", FedOvaStrategy)


@register("fedova_lbfgs")
class FedOvaLbfgsStrategy(FedOvaStrategy):
    server_opt = "fim_lbfgs"
