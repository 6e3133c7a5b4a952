"""The ``FedStrategy`` protocol + registry.

Every federated algorithm in this repo is a self-describing strategy
object; ``FederatedRun`` (fed/server.py) is a *generic* round driver that
never branches on the algorithm name.  A strategy declares:

  * ``round_plan()`` — a :class:`RoundPlan`: per-phase upload/download
    floats, the upload's wire codec (repro.fed.codecs), and
    ``aggregatable`` flags, plus client FLOPs.  The plan is the single
    source of truth consumed by CommLedger metering, edge time/energy
    estimation, and scheduler planning — the ledger records exactly what
    the plan predicts, by construction, under every codec.
  * ``client_step(data, rng, context)`` — one client's local work,
    returning ``(payload, loss)``.  Payloads whose plan is ``summable``
    may be summed in-network and buffered asynchronously (FedBuff-style),
    so async edge support falls out of the declaration.
  * ``aggregate(payloads, weights)`` — combine client payloads (the same
    code path serves synchronous n_k-weighted and asynchronous
    staleness-weighted aggregation).
  * ``server_step(aggregate)`` — apply the aggregate to the server model.

A strategy moves data and waits on the device through three helpers,
``_put`` (host→device copy of a batch), ``_dispatch`` (a jitted call)
and ``_wait`` (a host read of a device value), which put each of those
host steps on the run's tracer as ``fed.put`` / ``fed.dispatch`` /
``fed.wait`` spans and count ``h2d_bytes_total`` and
``device_syncs_total`` (see :mod:`repro.obs.trace`).

Multi-phase algorithms (FedDANE's gradient round before the inner solves)
implement ``round_context``, which sees the whole cohort once and hands
each client its per-client context; the extra phase's bytes live in the
same plan.

Registering a strategy makes it constructible by name through
``FederatedRun(model_cfg, fed_cfg, train, test, algorithm="<name>")``::

    @register("my_alg")
    class MyStrategy(FedStrategy):
        ...
"""
from __future__ import annotations

import abc
from dataclasses import dataclass
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import aggregation
from repro.fed import codecs, comm
from repro.obs import trace as obs


# ---------------------------------------------------------------------------
# RoundPlan: the strategy's declared per-round resource footprint
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class PhasePlan:
    """One communication phase of a round (per *selected client*).

    ``codec`` declares the upload's wire format (repro.fed.codecs): its
    ``wire_bytes(up_floats)`` is the single number CommLedger metering,
    edge uplink time/energy, and scheduler estimates all consume — the
    built-in strategies attach the run codec (``FedConfig.compress``) to
    every payload-carrying phase, so compressed wire sizes reach all
    three by construction.

    ``aggregatable`` carries the Theorem 3 semantics: summable payloads
    (gradients, Fisher diagonals, per-class OVA components) admit
    in-network tree aggregation — any node forwards O(log τ) payloads —
    while distinct local models must each reach the root (O(k·d))."""
    name: str
    down_floats: float = 0.0          # broadcast floats (server -> client)
    up_floats: float = 0.0            # upload floats (client -> server)
    codec: codecs.PayloadCodec = codecs.NONE   # upload wire format
    aggregatable: bool = True

    def wire_up_bytes(self) -> float:
        """Per-client upload bytes of this phase under its codec."""
        return self.codec.wire_bytes(self.up_floats)


@dataclass(frozen=True)
class RoundPlan:
    """Everything the generic driver needs to meter, estimate, and
    schedule one round of a strategy — consumed once, never branched on
    by algorithm name.

    flops(n_k) predicts one client's round FLOPs given its local sample
    count (partition sizes are run-constant, so the driver caches it).
    ``summable`` gates buffered-async aggregation: a stale summable
    payload is still a valid (staleness-discounted) additive update —
    and it also gates *sparsifying* codecs (top-k / rand-k), which zero
    coordinates and are only meaningful for such additive payloads.
    """
    phases: tuple[PhasePlan, ...]
    flops: Callable[[int], float]
    summable: bool = False
    round_scalars: int = 0            # per-round scalar floats (Gram m²)
    scalars_per_client: int = 0       # per-client scalar floats (OVA masks)

    def upload_bytes(self) -> float:
        """Per-client upload wire bytes per round (all phases)."""
        return float(sum(p.wire_up_bytes() for p in self.phases))

    def downlink_bytes(self) -> float:
        """Per-client broadcast bytes per round (all phases)."""
        return float(sum(p.down_floats * comm.BYTES_F32 for p in self.phases))

    def nonagg_upload_bytes(self) -> float:
        """The non-aggregatable share of upload_bytes (0 = fully summable
        in-network; FedDANE's model phase makes it a strict subset)."""
        return float(sum(p.wire_up_bytes()
                         for p in self.phases if not p.aggregatable))


# ---------------------------------------------------------------------------
# The strategy protocol
# ---------------------------------------------------------------------------
class FedStrategy(abc.ABC):
    """One federated algorithm as a self-describing object.

    Owns the server-side model/optimizer state and the jitted client
    functions; the driver owns sampling, metering, compression keys, the
    edge runtime, and the client loop."""

    name: str = ""  # filled in by ``register``
    # the run's tracer (FederatedRun.tracer hands it over)
    tracer = obs.NULL_TRACER

    def __init__(self, model_cfg: Any, fed_cfg: Any, n_classes: int):
        self.mcfg = model_cfg
        self.fcfg = fed_cfg
        self.n_classes = n_classes
        # the run's payload codec (FedConfig.compress); _make_plan attaches
        # it to payload-carrying phases so wire bytes flow everywhere.
        # FedConfig.kernels selects the Pallas encode fast path
        self.codec = codecs.make(fed_cfg.compress,
                                 kernels=getattr(fed_cfg, "kernels", None))
        self._n_params_cache: Optional[int] = None
        self._plan_cache: Optional[RoundPlan] = None
        self._build(jax.random.PRNGKey(fed_cfg.seed))

    # -- construction ----------------------------------------------------
    @abc.abstractmethod
    def _build(self, key: jax.Array) -> None:
        """Initialize model params, optimizer state, and jitted fns."""

    # -- declaration -----------------------------------------------------
    @abc.abstractmethod
    def _make_plan(self) -> RoundPlan:
        """Declare this strategy's per-round resource footprint."""

    def round_plan(self) -> RoundPlan:
        if self._plan_cache is None:
            plan = self._make_plan()
            if self.codec.sparsifying and not plan.summable:
                raise ValueError(
                    f"codec {self.codec.spec()!r} sparsifies payload "
                    "coordinates, which is only meaningful for additive "
                    f"(summable) payloads; strategy {self.name!r} uploads "
                    "distinct models/components (summable=False) — use "
                    "compress='none' or 'int8'")
            self._plan_cache = plan
        return self._plan_cache

    def n_params(self) -> int:
        """Float count of ONE broadcast model.  Default: the ``params``
        pytree built by ``_build``; strategies with a different server
        state (OVA's stacked components) override."""
        if self._n_params_cache is None:
            self._n_params_cache = comm.tree_n_floats(self.params)
        return self._n_params_cache

    # -- checkpoint/resume ----------------------------------------------
    def state_dict(self) -> dict:
        """Every server-side array that mutates across rounds, as a
        pytree ``repro.checkpoint`` round-trips (see
        :mod:`repro.checkpoint.run_state`).  Default: the ``params``
        pytree plus ``opt_state`` when the strategy keeps one; override
        for any extra mutable server state."""
        sd: dict = {"params": self.params}
        if hasattr(self, "opt_state"):
            sd["opt_state"] = self.opt_state
        return sd

    def load_state_dict(self, state: dict) -> None:
        self.params = jax.tree.map(jnp.asarray, state["params"])
        if "opt_state" in state:
            self.opt_state = jax.tree.map(jnp.asarray, state["opt_state"])

    # -- one round -------------------------------------------------------
    def round_context(self, datas: Sequence[tuple], rng: Any
                      ) -> Optional[Sequence[Any]]:
        """Optional cohort-wide pre-phase (FedDANE's gradient round).

        datas: list of (xs, ys) for the selected cohort.  Returns a
        per-client context sequence (or None), threaded into each
        ``client_step``."""
        return None

    @abc.abstractmethod
    def client_step(self, data: tuple, rng: Any,
                    context: Any = None) -> tuple[Any, float]:
        """One client's local update on data=(xs, ys).

        Returns (payload, loss).  The payload is whatever
        ``aggregate`` consumes — for summable plans it must be a pytree
        that remains meaningful under weighted summation."""

    def aggregate(self, payloads: Sequence[Any],
                  weights: Sequence[float]) -> Any:
        """Combine client payloads under (n_k- or staleness-) weights.
        Default: weighted mean over the stacked payload pytrees — right
        for any single-pytree payload (deltas, models, gradients);
        structured payloads (grad+Fisher pairs, masked OVA stacks)
        override."""
        return aggregation.weighted_mean(
            jax.tree.map(lambda *t: jnp.stack(t), *payloads),
            jnp.asarray(weights, jnp.float32))

    @abc.abstractmethod
    def server_step(self, aggregate: Any) -> None:
        """Apply an aggregate to the server model/optimizer state."""

    def compress_payload(self, payload: Any, key: Any, residual: Any = None,
                         codec: Optional[codecs.PayloadCodec] = None
                         ) -> tuple[Any, Any]:
        """Round-trip the payload through ``codec`` (default: the run's
        codec; an allocation policy may hand a client its own wire
        format, e.g. adaptive_codec's channel-scheduled top-k ratios).
        Returns ``(payload, new_residual)`` — the driver owns the
        per-client error-feedback residual and threads it back in next
        round.  Strategies whose payloads need structure-aware handling
        (e.g. a nonnegative Fisher diagonal, an OVA presence mask that
        must not be quantized) override this."""
        return (codec or self.codec).roundtrip(payload, key, residual)

    # -- host steps on the tracer ----------------------------------------
    def _put(self, tree: Any) -> Any:
        """Copy a pytree of host arrays to the device, in ``fed.put``; its
        ``bytes`` (those of the device arrays made) also go to
        ``h2d_bytes_total``, labelled by the step that pays for them."""
        tr = self.tracer
        if not tr.enabled:
            return jax.tree.map(jnp.asarray, tree)
        # host arrays are copied at the dtype JAX gives them (int64
        # labels arrive as int32 unless x64 is on); device arrays stay
        nbytes = sum(a.size * jax.dtypes.canonicalize_dtype(a.dtype).itemsize
                     for a in jax.tree.leaves(tree)
                     if isinstance(a, np.ndarray))
        tr.count("h2d_bytes_total", nbytes)
        with tr.wall_span("fed.put", bytes=nbytes):
            return jax.tree.map(jnp.asarray, tree)

    def _dispatch(self, fn: Callable, *args, **kwargs) -> Any:
        """Call ``fn`` (a jitted step), in ``fed.dispatch``: the span ends
        when the call returns, before its work ends on the device."""
        with self.tracer.wall_span("fed.dispatch"):
            return fn(*args, **kwargs)

    def _wait(self, value: Any) -> float:
        """Read a device scalar on the host, in ``fed.wait``: the host
        blocks until the device has computed it (``device_syncs_total``)."""
        tr = self.tracer
        tr.count("device_syncs_total")
        with tr.wall_span("fed.wait"):
            return float(value)

    # -- evaluation ------------------------------------------------------
    def evaluate(self, x: Any, y: Any) -> float:
        """Test accuracy of the current server model.  Default: the
        jitted ``self._eval`` over ``self.params`` (built in ``_build``);
        strategies with other model state override."""
        return self._wait(self._dispatch(self._eval, self.params, x, y))


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------
_REGISTRY: dict[str, Callable[..., FedStrategy]] = {}


def register(name: str, factory: Optional[Callable[..., FedStrategy]] = None):
    """Register ``factory(model_cfg, fed_cfg, n_classes) -> FedStrategy``
    under ``name``.  Usable as a decorator on a strategy class or called
    directly with a factory (variants of one class register twice)."""

    def _do(f):
        try:
            f.name = name
        except (AttributeError, TypeError):
            pass  # e.g. a functools.partial; the registry key still works
        _REGISTRY[name] = f
        return f

    return _do if factory is None else _do(factory)


def get(name: str) -> Callable[..., FedStrategy]:
    if name not in _REGISTRY:
        raise ValueError(
            f"unknown federated strategy {name!r}; known: {names()}")
    return _REGISTRY[name]


def names() -> list[str]:
    return sorted(_REGISTRY)
