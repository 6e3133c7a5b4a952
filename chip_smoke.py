#!/usr/bin/env python3
"""Bring-up check of the federated FIM-L-BFGS round on a TPU.

    python3 chip_smoke.py [--seed N]      # one chip: the paper's federated path
    python3 chip_smoke.py --chips 4       # four chips: the sharded LLM train step

One chip runs full-width CIFAR VGG11 (9 488 266 parameters) through the
entry points a user calls:

  * ``FederatedRun(..., "fim_lbfgs")`` on a non-IID-2 partition with partial
    participation, 3 rounds under each upload codec ``none``, ``int8`` and
    ``topk:0.01``;
  * 2 rounds of ``fedova_lbfgs`` (Algorithm 2 with the FIM-L-BFGS step);
  * 2 rounds of the vmapped cohort step (``simulator.from_strategy``);
  * each Pallas kernel of that path, native, against its ``ref.py`` oracle
    on one real payload of the run.

``--chips 4`` runs only ``launch/train.make_train_step`` for granite-8b's
smoke config on a 2x2 ("data", "model") mesh, the same step on one chip,
and compares the two.

The script needs a TPU and never falls back to the CPU: on any other
backend it exits nonzero before doing any work.  Each phase prints one
line; the last line of standard output is a JSON object naming the device.
Host seconds bracket work that ends in ``block_until_ready``: they include
compilation and dispatch and are not device metrics.  JAX's persistent
compilation cache is kept where ``JAX_COMPILATION_CACHE_DIR`` says, else
in ``.jax_cache/`` next to this file.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

CLIENT_EXAMPLES = 100   # per client: the per-example Fisher of VGG11 at
                        # B=100 needs ~2 GB of temporaries with the native
                        # fim_diag kernel (9.6 GB at B=500)
NUM_CLIENTS = 10
PARTICIPATION = 0.4     # 4 clients per round
COHORT = 4              # clients per vmapped cohort step: ~8 GB of
                        # per-example temporaries on a 16 GB v5e
FEDOVA_LBFGS_M = 2      # 10 per-class L-BFGS histories of 9.5M floats each
VGG11_PARAMS = 9_488_266
LOSS_RTOL_4CHIP = 1e-3  # sharded vs one-chip train-step loss

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling (or fetching a
    compiled program from the persistent cache), from jax.monitoring."""

    def __init__(self):
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event in _COMPILE_EVENTS:
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def _require(ok: bool, msg: str) -> None:
    if not ok:
        raise RuntimeError(msg)


def _report(name: str, **fields) -> None:
    parts = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"[{name}] {parts}", flush=True)


def _peak_gb(dev) -> str:
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "n/a" if peak is None else f"{peak / 1e9:.2f}"


def check_device():
    """The first TPU device; exits nonzero on any other backend, and
    fails unless the Pallas kernels dispatch natively."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r} "
              f"({dev.device_kind})", file=sys.stderr)
        sys.exit(1)
    from repro.kernels import ops
    _require(ops.resolve("auto") == "native",
             f"kernels resolve to {ops.resolve('auto')!r}, not 'native'")
    return dev


# ---------------------------------------------------------------------------
# one chip: the paper's federated path
# ---------------------------------------------------------------------------
def balanced_data(mcfg, per_class: int, seed: int):
    """make_classification's train split cut to ``per_class`` examples of
    every class, so the non-IID-2 partition deals equal client shards
    (one compiled client step instead of one per client size)."""
    from repro.data.synthetic import Dataset, make_classification
    train, test = make_classification(
        mcfg, n_train=per_class * mcfg.num_classes * 3 // 2, n_test=500,
        seed=seed)
    idx = []
    for c in range(mcfg.num_classes):
        where = np.flatnonzero(train.y == c)
        _require(len(where) >= per_class,
                 f"class {c} has {len(where)} < {per_class} examples")
        idx.append(where[:per_class])
    idx = np.sort(np.concatenate(idx))
    return Dataset(train.x[idx], train.y[idx], train.n_classes,
                   train.name), test


def fed_config(seed: int, **kw):
    from repro.configs.base import FedConfig
    return FedConfig(num_clients=NUM_CLIENTS, participation=PARTICIPATION,
                     noniid_l=2, local_epochs=1, batch_size=CLIENT_EXAMPLES,
                     seed=seed, **kw)


def run_rounds(name, mcfg, fcfg, train, test, algorithm, rounds, clock):
    """``rounds`` rounds of FederatedRun; prints and returns the run."""
    from repro.fed.server import FederatedRun
    c0, h0 = clock.seconds, clock.cache_hits
    t0 = time.perf_counter()
    run = FederatedRun(mcfg, fcfg, train, test, algorithm)
    history = run.run(rounds=rounds, eval_every=rounds)
    state = run.params if run.params is not None else run.model
    jax.block_until_ready(state)
    host_s = time.perf_counter() - t0
    losses = [h["loss"] for h in history]
    acc = history[-1]["accuracy"]
    _report(name, rounds=rounds, cohort=[h["cohort"] for h in history],
            host_s=f"{host_s:.2f}", compile_s=f"{clock.seconds - c0:.2f}",
            cache_hits=clock.cache_hits - h0,
            loss=[f"{v:.4f}" for v in losses], accuracy=f"{acc:.3f}",
            peak_hbm_gb=_peak_gb(jax.devices()[0]))
    _require(all(math.isfinite(v) for v in losses),
             f"{name}: non-finite loss {losses}")
    _require(math.isfinite(acc), f"{name}: non-finite accuracy")
    return run


def cohort_rounds(run, rounds, cohort, seed, clock):
    """``rounds`` rounds of the vmapped cohort step built from ``run``'s
    strategy, over the first ``cohort`` clients of its partition."""
    from repro.fed import simulator
    c0, h0 = clock.seconds, clock.cache_hits
    t0 = time.perf_counter()
    step = simulator.from_strategy(run.strategy)
    ids = [i for i in range(run.fcfg.num_clients)
           if len(run.partition[i])][:cohort]
    datas = [run._client_data(i) for i in ids]
    batch = {"x": jnp.asarray(np.stack([d[0] for d in datas])),
             "y": jnp.asarray(np.stack([d[1] for d in datas]))}
    weights = jnp.asarray([len(d[1]) for d in datas], jnp.float32)
    params, opt_state = run.strategy.params, run.strategy.opt_state
    key = jax.random.PRNGKey(seed)
    losses = []
    for _ in range(rounds):
        key, sub = jax.random.split(key)
        params, opt_state, stats = step(params, opt_state, batch, weights,
                                        sub)
        losses.append(float(jax.block_until_ready(stats["loss"])))
    jax.block_until_ready(params)
    host_s = time.perf_counter() - t0
    _report("cohort_vmap", rounds=rounds, cohort=cohort,
            codec=step.codec.spec(), host_s=f"{host_s:.2f}",
            compile_s=f"{clock.seconds - c0:.2f}",
            cache_hits=clock.cache_hits - h0,
            loss=[f"{v:.4f}" for v in losses],
            peak_hbm_gb=_peak_gb(jax.devices()[0]))
    _require(all(math.isfinite(v) for v in losses),
             f"cohort_vmap: non-finite loss {losses}")


def _ulps(a, b) -> int:
    """Largest distance in units in the last place between f32 arrays of
    equal signs (sign-magnitude bit patterns made monotone)."""
    def ordered(x):
        i = np.asarray(x, np.float32).view(np.int32).astype(np.int64)
        return np.where(i < 0, -(i & 0x7FFFFFFF), i)
    return int(np.max(np.abs(ordered(a) - ordered(b))))


def kernel_check(run, seed):
    """Every Pallas kernel of the main path, native on the chip, against
    its ref.py oracle (also on the chip) on one real payload of ``run``."""
    from repro.core import lbfgs
    from repro.kernels import codec_ops, fim_diag, ref, vlbfgs
    from repro.models import cnn

    strat = run.strategy
    cid = next(i for i in range(run.fcfg.num_clients)
               if len(run.partition[i]))
    xs, ys = run._client_data(cid)
    (g, f), _ = strat.client_step((xs, ys), np.random.default_rng(seed))
    flat, _ = ravel_pytree((g, f))

    # int8_roundtrip: bit-identical to the oracle
    u = jax.random.uniform(jax.random.PRNGKey(seed), flat.shape)
    scale = ref.int8_scale(flat)
    nat = codec_ops.int8_roundtrip(flat, u, scale)
    orc = jax.jit(ref.int8_roundtrip_ref)(flat, u, scale)
    nat, orc = np.asarray(nat), np.asarray(orc)
    n_diff = int(np.sum(nat != orc))
    fields = dict(n=flat.size, differing=n_diff)
    if n_diff:
        fields["max_ulp"] = _ulps(nat, orc)
    _report("check_int8", **fields)
    _require(n_diff == 0, f"int8_roundtrip is not bit-identical to the "
                          f"oracle: {fields}")

    # topk_select: exactly k kept, the oracle's set
    k = math.ceil(0.01 * flat.size)
    nat = np.asarray(codec_ops.topk_select(flat, k))
    orc = np.asarray(jax.jit(ref.topk_select_ref, static_argnums=1)(flat, k))
    kept = int(np.sum(nat != 0))
    same = bool(np.array_equal(nat != 0, orc != 0))
    _report("check_topk", n=flat.size, k=k, kept=kept, same_set=same,
            bit_identical=bool(np.array_equal(nat, orc)))
    _require(kept == k and same, "topk_select disagrees with the oracle")

    # fim_diag: per-example gradients of the largest leaf over the client
    leaves = jax.tree.leaves(strat.params)
    big = max(range(len(leaves)), key=lambda i: leaves[i].size)
    per_ex = cnn.per_example_loss_fn(run.mcfg)

    @jax.jit
    def leaf_grads(params, x, y):
        grads = jax.vmap(lambda xi, yi: jax.grad(per_ex)(params, xi, yi))(
            x, y)
        leaf = jax.tree.leaves(grads)[big]
        return leaf.reshape(leaf.shape[0], -1)

    grads = leaf_grads(strat.params, jnp.asarray(xs), jnp.asarray(ys))
    old = jnp.abs(jax.random.normal(jax.random.PRNGKey(seed + 1),
                                    (grads.shape[1],))) * 1e-4
    nat = np.asarray(fim_diag.fim_diag(grads, old, 0.9))
    orc = np.asarray(jax.jit(ref.fim_diag_ref, static_argnums=2)(
        grads, old, 0.9))
    err = float(np.max(np.abs(nat - orc)) / np.max(np.abs(orc)))
    _report("check_fim_diag", shape=tuple(grads.shape),
            max_rel_err=f"{err:.3e}")
    _require(np.allclose(nat, orc, rtol=1e-5, atol=1e-6 * np.max(orc)),
             "fim_diag disagrees with the oracle")
    del grads

    # vlbfgs gram: the run's own L-BFGS basis [s.., y.., g]
    hist = strat.opt_state.history
    basis = lbfgs.gram_basis(hist, g)
    nat = np.asarray(vlbfgs.gram(basis))
    with jax.default_matmul_precision("highest"):
        orc = np.asarray(jax.jit(ref.vlbfgs_gram_ref)(basis))
    err = float(np.max(np.abs(nat - orc)) / np.max(np.abs(orc)))
    _report("check_vlbfgs_gram", shape=tuple(basis.shape),
            pairs=int(hist.count), max_rel_err=f"{err:.3e}")
    _require(np.allclose(nat, orc, rtol=1e-4, atol=1e-5 * np.max(orc)),
             "vlbfgs gram disagrees with the oracle")


def one_chip(seed: int, clock: CompileClock) -> None:
    from repro.configs.paper_models import CIFAR_VGG
    train, test = balanced_data(
        CIFAR_VGG, CLIENT_EXAMPLES * NUM_CLIENTS // CIFAR_VGG.num_classes,
        seed)
    _report("setup", model=CIFAR_VGG.name, clients=NUM_CLIENTS,
            examples_per_client=CLIENT_EXAMPLES, participation=PARTICIPATION,
            noniid_l=2, n_train=len(train.y), n_test=len(test.y))

    runs = {}
    for codec in ("none", "int8", "topk:0.01"):
        runs[codec] = run_rounds(f"fim_lbfgs/{codec}", CIFAR_VGG,
                                 fed_config(seed, compress=codec), train,
                                 test, "fim_lbfgs", 3, clock)
        n = runs[codec].strategy.n_params()
        _require(n == VGG11_PARAMS, f"VGG11 has {n} parameters")
        _require(len({len(p) for p in runs[codec].partition}) == 1,
                 "client shards differ in size")
    del runs["topk:0.01"]
    cohort_rounds(runs.pop("int8"), 2, COHORT, seed, clock)
    kernel_check(runs.pop("none"), seed)

    run_rounds("fedova_lbfgs", CIFAR_VGG,
               fed_config(seed, lbfgs_m=FEDOVA_LBFGS_M), train, test,
               "fedova_lbfgs", 2, clock)


# ---------------------------------------------------------------------------
# four chips: the sharded LLM train step
# ---------------------------------------------------------------------------
def four_chips(seed: int, clock: CompileClock) -> None:
    from repro.configs.base import ShapeConfig
    from repro.configs.granite_8b import smoke_config
    from repro.launch import mesh as meshlib
    from repro.launch import train as trainlib
    from repro.models import model as zoo
    from repro.models.layers import use_mesh
    from repro.utils import sharding as shd

    _require(len(jax.devices()) >= 4,
             f"--chips 4 needs 4 devices, found {len(jax.devices())}")
    cfg = smoke_config()
    shape = ShapeConfig("smoke", 64, 8, "train")
    ocfg = trainlib.opt_config(cfg)
    step = trainlib.make_train_step(cfg, ocfg, n_micro=2)
    batch = zoo.synth_batch(cfg, shape, jax.random.PRNGKey(seed + 1))

    def two_steps(jitted, params, opt, batch):
        losses = []
        for _ in range(2):
            params, opt, stats = jitted(params, opt, batch)
            losses.append(float(jax.block_until_ready(stats["loss"])))
        return params, losses

    def fresh():
        params, axes, opt, opt_axes = trainlib.init_train_state(
            cfg, ocfg, jax.random.PRNGKey(seed))
        return params, axes, opt, opt_axes

    c0, t0 = clock.seconds, time.perf_counter()
    params, _, opt, _ = fresh()
    _, ref_losses = two_steps(jax.jit(step), params, opt, batch)
    _report("train_step/1chip", arch=cfg.name, steps=2,
            host_s=f"{time.perf_counter() - t0:.2f}",
            compile_s=f"{clock.seconds - c0:.2f}",
            loss=[f"{v:.6f}" for v in ref_losses])

    mesh = meshlib.make_debug_mesh(2, 2)
    c0, t0 = clock.seconds, time.perf_counter()
    params, axes, opt, opt_axes = fresh()
    shardings = (shd.shardings_for_tree(params, axes, mesh),
                 shd.shardings_for_tree(opt, opt_axes, mesh, shd.OPT_RULES),
                 shd.shardings_for_tree(batch, zoo.input_axes(cfg, shape),
                                        mesh))
    params = jax.device_put(params, shardings[0])
    opt = jax.device_put(opt, shardings[1])
    batch = jax.device_put(batch, shardings[2])
    with use_mesh(mesh):
        # params and optimizer state keep their layout from step to step
        jitted = jax.jit(step, in_shardings=shardings, out_shardings=(
            shardings[0], shardings[1],
            jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())))
        params, losses = two_steps(jitted, params, opt, batch)
    per_dev: dict = {}
    for leaf in jax.tree.leaves(params):
        for sh in leaf.addressable_shards:
            per_dev[sh.device.id] = per_dev.get(sh.device.id, 0) \
                + sh.data.nbytes
    total = sum(leaf.nbytes for leaf in jax.tree.leaves(params))
    rel = max(abs(a - b) / abs(b) for a, b in zip(losses, ref_losses,
                                                  strict=True))
    _report("train_step/2x2", mesh=dict(mesh.shape), steps=2,
            host_s=f"{time.perf_counter() - t0:.2f}",
            compile_s=f"{clock.seconds - c0:.2f}",
            loss=[f"{v:.6f}" for v in losses], max_rel_diff=f"{rel:.3e}",
            rtol=LOSS_RTOL_4CHIP, param_bytes=total,
            param_bytes_per_device=dict(sorted(per_dev.items())))
    _require(rel <= LOSS_RTOL_4CHIP,
             f"sharded losses {losses} != one-chip {ref_losses}")
    _require(len(per_dev) == 4 and all(v > 0 for v in per_dev.values()),
             f"parameters are not on four devices: {per_dev}")
    _require(max(per_dev.values()) < total,
             "every device holds a full copy: nothing is sharded")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the data, the weights and the codecs")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded LLM train step on a 2x2 "
                         "mesh and compare it with one chip")
    args = ap.parse_args()

    dev = check_device()
    from repro.utils.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    clock = CompileClock()
    _report("device", platform=dev.platform, kind=dev.device_kind,
            count=len(jax.devices()), compile_cache=cache)
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(args.seed, clock)
    else:
        one_chip(args.seed, clock)
    _report("total", host_s=f"{time.perf_counter() - t0:.2f}",
            compile_s=f"{clock.seconds:.2f}", cache_hits=clock.cache_hits)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
