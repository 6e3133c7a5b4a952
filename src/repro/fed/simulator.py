"""Mesh-parallel federated cohort simulation.

server.py loops clients in Python (faithful to the paper's sequential
simulation).  This module is the *production* path: the selected cohort's
batches are stacked on a leading client dim, client gradients + FIM
diagonals are computed with vmap, and the aggregation reduces over that dim
— under pjit with the client dim sharded over the ("pod","data") mesh axes,
that reduction lowers to exactly one all-reduce per round, the paper's
O(d log τ) term (see launch/train.py for the LLM-scale equivalent where
microbatch cohorts play the client role).

The cohort client function is the SAME jitted fn the federated loop uses
(fed/client.py's ``make_grad_fim_fn``) — ``from_strategy`` derives the
whole round step from a registered strategy object, so the Python-loop
and vmapped paths cannot drift apart."""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import aggregation, fim_lbfgs
from repro.edge.device import flops_grad_fim
from repro.edge.runtime import EdgeRuntime
from repro.fed import client as fed_client
from repro.fed import codecs, comm


def _build_round_step(client_fn: Callable, server_update: Callable,
                      compress_fn: Optional[Callable] = None):
    """round_step(params, opt_state, cohort_batch, weights, key=None):
    vmap the per-client fn over the stacked cohort, optionally round-trip
    each client's (grad, Γ) payload through the codec (``key`` supplies
    the per-client randomness; None skips compression), aggregate once,
    apply the pure server update."""

    def round_step(params, opt_state, cohort_batch, weights, key=None):
        grads, diags, losses = jax.vmap(client_fn, in_axes=(None, 0))(
            params, cohort_batch)
        if compress_fn is not None and key is not None:
            keys = jax.random.split(key, losses.shape[0])
            grads, diags = jax.vmap(compress_fn, in_axes=((0, 0), 0))(
                (grads, diags), keys)
        grad = aggregation.weighted_mean(grads, weights)      # Σ_k (n_k/n) ∇F_k
        diag = aggregation.weighted_mean(diags, weights)      # Σ_k (n_k/n) Γ_k
        new_params, new_state, stats = server_update(
            opt_state, params, grad, diag)
        stats["loss"] = jnp.mean(losses)
        return new_params, new_state, stats

    return jax.jit(round_step)


def make_round_step(loss_fn: Callable, per_example_loss: Callable | None,
                    ocfg: fim_lbfgs.FimLbfgsConfig, fim_mode: str = "per_example"):
    """Returns round_step(params, opt_state, cohort_batch, weights).

    cohort_batch: {"x": (K, B, ...), "y": (K, B)} — one stacked batch per
    selected client; weights: (K,) sample counts n_k."""
    client_fn = fed_client.make_grad_fim_fn(loss_fn, per_example_loss, fim_mode)

    def server_update(opt_state, params, grad, diag):
        return fim_lbfgs.update(opt_state, params, grad, diag, ocfg)

    return _build_round_step(client_fn, server_update)


def from_strategy(strategy):
    """Derive the vmapped cohort ``round_step`` from a registered strategy
    (repro.fed.strategies): the strategy's own jitted client fn and pure
    server update, so the sequential and mesh-parallel paths share code.

    The strategy's codec (``FedConfig.compress``) is threaded through as
    well: pass a PRNG ``key`` to the returned step and every client's
    payload is round-tripped through ``strategy.compress_payload`` inside
    the same jitted round (stateless — the vmapped path keeps no per-
    client error-feedback residuals, so sparsifiers here quantify the
    raw, feedback-free compression error)."""
    try:
        client_fn = strategy.cohort_client_fn
        server_update = strategy.cohort_server_update
    except AttributeError as e:
        raise NotImplementedError(
            f"strategy {getattr(strategy, 'name', strategy)!r} does not "
            "expose a vmappable cohort path (needs cohort_client_fn + "
            "cohort_server_update)") from e
    compress_fn = None
    codec = getattr(strategy, "codec", codecs.NONE)
    if not codec.identity:
        def compress_fn(payload, key):
            out, _ = strategy.compress_payload(payload, key)
            return out
    jitted = _build_round_step(client_fn, server_update, compress_fn)

    def round_step(params, opt_state, cohort_batch, weights, key=None):
        return jitted(params, opt_state, cohort_batch, weights, key)

    # advertise the wire format so with_edge bills the same codec the
    # payloads actually round-trip through — one spec, not two
    round_step.codec = codec
    return round_step


def with_edge(round_step: Callable, edge: EdgeRuntime, n_params: int,
              compress=None, tracer=None):
    """Wrap a jitted ``round_step`` with the edge cost model.

    ``tracer`` (a :class:`repro.obs.trace.Tracer`) attaches observability
    to the given ``edge`` runtime — round/client spans on the simulated
    timeline, byte/energy/drop metrics — exactly as passing the tracer to
    ``EdgeRuntime(...)`` directly would; the kwarg exists so callers who
    received an already-built runtime can still trace it.

    The vmapped cohort is the selected client set; after the device-side
    step, the wrapper advances the edge clock by the synchronous-round
    wall time (per-client grad+FIM compute plus the 2d-float uplink under
    the configured topology) and drains batteries.  stats gains
    ``wall_s`` / ``sim_time_s`` / ``energy_j`` host-side entries.

    The wrapped step takes an optional ``clients`` array — the TRUE
    selected client ids — so device heterogeneity and battery drain hit
    the right fleet entries; without it, cohort slot i falls back to
    fleet entry i (mod fleet size).

    The uplink is costed at the codec's wire size, so edge time/energy
    shrink exactly as the ledger bytes do.  The codec is derived from the
    ``round_step`` itself (``from_strategy`` attaches the strategy's
    codec); ``compress`` exists only to state it explicitly and must
    match — billing a wire format the step does not round-trip raises,
    so cost and accuracy cannot be paired apart by accident.

    Each round the edge's AllocationPolicy apportions the shared
    bandwidth budget over the given cohort (``EdgeRuntime.allocate_for``
    — selection already happened upstream, only the ``allocate`` stage
    runs, and it runs BEFORE the device step so deadline enforcement can
    shape the aggregation), so e.g. ``bandwidth_opt`` shrinks the sync
    barrier here too.  Granted deadlines are enforced: a cohort slot
    whose device busts min(its grant, EdgeConfig.enforce_deadline_s) is
    cut off at the barrier — its weight is zeroed so the in-jit
    weighted_mean re-normalizes over the on-time partial cohort, and an
    all-dropped round applies no server step.
    Policies that emit per-client *codecs* are rejected: the vmapped
    path round-trips every client through the one run codec, and billing
    wire formats the payloads never saw is the divergence this layer
    exists to forbid."""
    if tracer is not None:
        edge.tracer = tracer
    step_codec = getattr(round_step, "codec", codecs.NONE)
    codec = step_codec if compress is None else codecs.make(compress)
    if codec.spec() != step_codec.spec():
        raise ValueError(
            f"round_step round-trips payloads through "
            f"{step_codec.spec()!r} but billing was requested at "
            f"{codec.spec()!r}; build the step with the same codec "
            "(simulator.from_strategy attaches FedConfig.compress)")
    down_bytes = float(n_params * comm.BYTES_F32)

    def wire_fn(override=None):
        # grad+FIM payloads are summable: fully aggregatable on the wire
        return float((override or codec).wire_bytes(2.0 * n_params)), 0.0

    def edge_round_step(params, opt_state, cohort_batch, weights,
                        clients: Optional[np.ndarray] = None, key=None):
        if key is None and not codec.identity:
            # billing compressed wire bytes for payloads that never
            # round-trip would pair uncompressed accuracy with compressed
            # cost — the silent divergence this layer exists to forbid
            raise ValueError(
                f"codec {codec.spec()!r} bills compressed uplink bytes: "
                "pass key=... so the payloads actually round-trip through "
                "it (or build the step with compress='none')")
        k, b = cohort_batch["y"].shape[:2]
        if clients is None:
            cohort = np.arange(k) % edge.num_clients
        else:
            cohort = np.asarray(clients, dtype=int)
            if cohort.shape != (k,):
                raise ValueError(
                    f"clients must map each of the {k} cohort slots to a "
                    f"fleet entry, got shape {cohort.shape}")
            if cohort.size and (cohort.min() < 0
                                or cohort.max() >= edge.num_clients):
                raise ValueError(
                    f"client ids must be in [0, {edge.num_clients}), "
                    f"got range [{cohort.min()}, {cohort.max()}]")
        est, decision = edge.allocate_for(
            cohort, wire_fn, flops_grad_fim(n_params, b), codec=codec)
        if decision.heterogeneous_codecs:
            raise ValueError(
                f"allocation policy {edge.cfg.scheduler!r} assigns "
                "per-client upload codecs, but the vmapped cohort path "
                "round-trips every client through the one run codec — "
                "use FederatedRun for adaptive per-client wire formats")
        # deadline enforcement: a cohort slot whose device busted its
        # granted deadline contributes nothing — its weight is zeroed, so
        # weighted_mean re-normalizes over the on-time partial cohort
        # (an all-dropped round applies no server step at all)
        mask = None
        if decision.n_dropped:
            mask = np.asarray([float(int(cc) not in decision.dropped)
                               for cc in cohort], dtype=np.float32)
            weights = jnp.asarray(weights) * mask
        if mask is not None and not mask.any():
            new_params, new_state, stats = (
                params, opt_state, {"loss": float("nan")})
        else:
            # only forward key when given: a bare 4-arg round_step stays
            # valid
            args = (params, opt_state, cohort_batch, weights)
            new_params, new_state, stats = (
                round_step(*args) if key is None else round_step(*args, key))
        # duplicate cohort slots (mod fallback) share one subchannel but
        # carry one payload each — bill every slot
        uniq, counts = np.unique(cohort, return_counts=True)
        mult = {int(u): int(c) for u, c in zip(uniq, counts, strict=True)}
        up_arr = np.asarray([mult[int(i)] * wire_fn()[0]
                             for i in decision.selected])
        rec = edge.finish_round_sync(est, up_arr, down_bytes)
        stats = dict(stats)
        stats.update(wall_s=rec["wall_s"], sim_time_s=rec["clock_s"],
                     energy_j=rec["energy_j"], dropped=rec["dropped"])
        if "barrier_s" in rec:
            stats["barrier_s"] = rec["barrier_s"]
        return new_params, new_state, stats

    return edge_round_step
