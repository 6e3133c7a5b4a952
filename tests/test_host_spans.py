"""The federated round's host steps as spans and counters on the tracer.

``FederatedRun`` and the strategies' ``_put`` / ``_dispatch`` / ``_wait``
helpers open ``fed.*`` blocks through ``Tracer.wall_span``: each is a
``jax.profiler.TraceAnnotation`` (so it lands in the profiler's
``.xplane.pb`` beside the device ops) and, with ``wall=True``, a
``CAT_WALL`` span in memory with its parent.  Counters follow the work:
``h2d_bytes_total``, ``device_syncs_total`` and JAX's compile events,
labelled by the innermost open span.  Locks:

  * the span tree of a round, per codec (``fed.compress`` only where the
    codec encodes, ``codec_encode_s`` fed from its duration);
  * the counters against what they count (bytes put, JAX's own compile
    event totals);
  * tracing changes no number, and ``NULL_TRACER`` records nothing;
  * the spans and their stats in a CPU profiler trace.
"""
import glob
import warnings

import jax
import numpy as np
import pytest

from repro import obs
from repro.configs.base import FedConfig
from repro.configs.paper_models import FMNIST_CNN, reduced
from repro.data.synthetic import make_classification
from repro.edge import EdgeConfig
from repro.fed.server import FederatedRun
from repro.obs import trace as obs_trace

MCFG = reduced(FMNIST_CNN)
TRAIN, TEST = make_classification(MCFG, n_train=200, n_test=40, seed=3,
                                  noise=0.5)
CLIENTS = 4
ROUNDS = 2
EVAL = 40
CODECS = ("none", "int8", "topk:0.1")
# a landed client's blocks, in order, under each codec: topk's error
# feedback adds a read of the residual's norm (the ef_residual_norm gauge)
CLIENT_CHILDREN = {
    "none": ["fed.put", "fed.dispatch", "fed.wait"],
    "int8": ["fed.put", "fed.dispatch", "fed.wait", "fed.compress"],
    "topk:0.1": ["fed.put", "fed.dispatch", "fed.wait", "fed.compress",
                 "fed.wait"],
}


def _run(compress="none", tracer=None, edge=None) -> FederatedRun:
    fcfg = FedConfig(num_clients=CLIENTS, participation=1.0, local_epochs=1,
                     batch_size=50, noniid_l=2, seed=0, compress=compress,
                     edge=edge)
    return FederatedRun(MCFG, fcfg, TRAIN, TEST, "fim_lbfgs", tracer=tracer)


def _drive(run) -> list:
    losses = [run.round()["loss"] for _ in range(ROUNDS)]
    run.evaluate(EVAL)
    return losses


@pytest.fixture(scope="module")
def traced():
    """codec -> (run, tracer, losses) for ROUNDS traced rounds and one
    evaluation."""
    out = {}
    for codec in CODECS:
        tracer = obs.Tracer(wall=True, sink=lambda line: None)
        run = _run(codec, tracer)
        out[codec] = (run, tracer, _drive(run))
    return out


def _wall(tracer, name=None) -> list:
    return [s for s in tracer.spans if s.cat == obs.CAT_WALL
            and (name is None or s.name == name)]


def _children(tracer, parent) -> list:
    """Wall spans whose recorded parent is ``parent``'s name and that lie
    inside it, in start order."""
    return sorted((s for s in _wall(tracer)
                   if s.args.get("parent") == parent.name
                   and parent.t0 <= s.t0 and s.t1 <= parent.t1),
                  key=lambda s: s.t0)


# ---------------------------------------------------------------------------
# the span tree
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("codec", CODECS)
def test_round_span_tree(traced, codec):
    run, tracer, _ = traced[codec]
    rounds = _wall(tracer, "fed.round")
    assert [s.round_id for s in rounds] == list(range(ROUNDS))
    for rnd in rounds:
        kids = _children(tracer, rnd)
        names = [s.name for s in kids]
        assert names == (["fed.sample", "fed.gather"]
                         + ["fed.client"] * CLIENTS
                         + ["fed.aggregate", "fed.server_step"])
        assert all(s.round_id == rnd.round_id for s in kids)
        clients = [s for s in kids if s.name == "fed.client"]
        assert sorted(s.client for s in clients) == list(range(CLIENTS))
        for cl in clients:
            inner = _children(tracer, cl)
            assert [s.name for s in inner] == CLIENT_CHILDREN[codec]
            # children take the client and round of the enclosing block
            assert {(s.round_id, s.client) for s in inner} == {
                (rnd.round_id, cl.client)}
    ev = _wall(tracer, "fed.evaluate")
    assert len(ev) == 1
    assert [s.name for s in _children(tracer, ev[0])] == [
        "fed.put", "fed.dispatch", "fed.wait"]
    assert not [s for s in _wall(tracer) if s.args.get("parent") == ""
                and s.name not in ("fed.round", "fed.evaluate")]


@pytest.mark.parametrize("codec", [c for c in CODECS if c != "none"])
def test_codec_encode_time_is_the_compress_span(traced, codec):
    run, tracer, _ = traced[codec]
    spans = _wall(tracer, "fed.compress")
    assert len(spans) == ROUNDS * CLIENTS
    assert {s.args["codec"] for s in spans} == {codec}
    enc = tracer.metrics.get("codec_encode_s").stats(codec=codec)
    assert enc["count"] == len(spans)
    assert enc["sum"] == pytest.approx(sum(s.dur for s in spans), rel=1e-9)


def test_ef_residual_norm_is_one_reduction(traced):
    run, tracer, _ = traced["topk:0.1"]
    gauge = tracer.metrics.get("ef_residual_norm")
    assert len(gauge.items()) == CLIENTS
    for cid, res in run._ef_residual.items():
        want = np.sqrt(sum(np.vdot(a, a).real for a in
                           (np.asarray(x, np.float64)
                            for x in jax.tree.leaves(res))))
        # one float32 reduction against float64: sqrt(n) * eps of float32
        # over the residual's 2 x 27,930 elements is about 3e-5
        assert gauge.value(client=int(cid)) == pytest.approx(want, rel=1e-4)


# ---------------------------------------------------------------------------
# counters
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("codec", CODECS)
def test_h2d_bytes_are_the_arrays_put(traced, codec):
    run, tracer, _ = traced[codec]
    h2d = tracer.metrics.get("h2d_bytes_total")
    # float32 images and int32 labels (x64 off), per landed client a
    # round, and one evaluation
    per_round = sum(TRAIN.x[p].astype(np.float32).nbytes + 4 * len(p)
                    for p in run.partition)
    evaluation = TEST.x[:EVAL].astype(np.float32).nbytes + 4 * EVAL
    assert h2d.value(span="fed.client") == ROUNDS * per_round
    assert h2d.value(span="fed.evaluate") == evaluation
    assert h2d.total() == sum(s.args["bytes"]
                              for s in _wall(tracer, "fed.put"))
    syncs = tracer.metrics.get("device_syncs_total")
    assert syncs.total() == len(_wall(tracer, "fed.wait"))


def test_compile_counters_add_up_to_jax_monitoring():
    seen = {"lowered": 0, "compiled": 0}

    def listener(event, secs, **_):
        if event == obs_trace._LOWER_EVENT:
            seen["lowered"] += 1
        elif event == obs_trace._COMPILE_EVENT:
            seen["compiled"] += 1

    run = _run()                       # a fresh client step: it lowers
    tracer = obs.Tracer()
    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        run.tracer = tracer
        _drive(run)
        jax.jit(lambda x: x + 1)(np.float32(1.0))   # outside any span
        run.tracer = None
        jax.jit(lambda x: x + 2)(np.float32(1.0))   # detached: not seen
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
    lowered = tracer.metrics.get("jax_lowered_total")
    compiled = tracer.metrics.get("jax_compiled_total")
    assert seen["lowered"] > 0
    assert lowered.total() == seen["lowered"] - 1
    assert compiled.total() == seen["compiled"] - 1
    spans = {lbl["span"] for lbl in lowered.labelsets()}
    assert "fed.dispatch" in spans and "" in spans
    assert lowered.value(span="fed.dispatch",
                         program="jit(client_grad_fim)") >= 1
    assert tracer not in obs_trace._ATTACHED


def test_wall_span_nesting_and_attach():
    tr = obs.Tracer()
    with tr.wall_span("a", round_id=4) as a:
        with tr.wall_span("b", client=2) as b:
            tr.count("n", 3)
        tr.count("n")
    tr.count("n")
    assert (b.round_id, b.client) == (4, 2)
    assert a.dur >= b.dur >= 0
    assert tr.spans == []          # wall=False keeps nothing in memory
    n = tr.metrics.get("n")
    assert (n.value(span="b"), n.value(span="a"), n.value(span="")) == (
        3, 1, 1)
    tr.attach()
    tr.attach()
    tr.detach()
    assert obs_trace._listening and tr in obs_trace._ATTACHED
    tr.detach()
    assert tr not in obs_trace._ATTACHED


def test_tracer_is_handed_to_strategy_and_edge():
    run = _run(edge=EdgeConfig(mode="async", buffer_size=2))
    tr = obs.Tracer()
    run.tracer = tr
    assert run.strategy.tracer is run.edge.tracer is tr
    assert run.edge.async_agg.tracer is tr
    run.tracer = None
    assert run.tracer is run.strategy.tracer is obs.NULL_TRACER
    assert run.edge.async_agg.tracer is obs.NULL_TRACER
    assert tr._attached == 0


# ---------------------------------------------------------------------------
# tracing changes nothing; NULL_TRACER records nothing
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("codec", CODECS)
def test_traced_run_is_bit_identical(traced, codec):
    run, _, losses = traced[codec]
    plain = _run(codec)
    assert _drive(plain) == losses
    for a, b in zip(jax.tree.leaves(plain.strategy.state_dict()),
                    jax.tree.leaves(run.strategy.state_dict()), strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    null = obs.NULL_TRACER
    assert plain.tracer is null
    assert null.wall_span("fed.round", round_id=0) is null.wall_span("x")
    assert null.metrics.names() == [] and null.audit.rows == []


# ---------------------------------------------------------------------------
# the profiler's trace
# ---------------------------------------------------------------------------
def test_spans_land_in_the_profiler_trace(tmp_path):
    from jax.profiler import ProfileData
    tracer = obs.Tracer(wall=True)
    run = _run(tracer=tracer)
    run.round()                        # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        run.round()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = []
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in ProfileData.from_file(path).planes:
            if plane.name.startswith("/host:"):
                events += [(e.name, dict(e.stats)) for line in plane.lines
                           for e in line.events if e.name.startswith("fed.")]
    names = [n for n, _ in events]
    for name in ("fed.round", "fed.sample", "fed.gather", "fed.client",
                 "fed.put", "fed.dispatch", "fed.wait", "fed.aggregate",
                 "fed.server_step"):
        assert name in names
    assert names.count("fed.client") == CLIENTS
    assert [st for n, st in events if n == "fed.round"] == [{"round_id": 1}]
    clients = sorted(st["client"] for n, st in events if n == "fed.client")
    assert clients == list(range(CLIENTS))
    assert {st["round_id"] for n, st in events if n == "fed.client"} == {1}
    put = [st["bytes"] for n, st in events if n == "fed.put"]
    assert put == [s.args["bytes"] for s in _wall(tracer, "fed.put")
                   if s.round_id == 1]
