"""repro.edge.fleet — the struct-of-arrays mega-scale engine.

The fleet engine's contract has three layers, tested bottom-up:

  * state/sampling — cohorts are drawn without replacement from the
    alive (charged, non-busy) population only, on both backends;
  * backend agreement — ``backend="exact"`` wraps a real EdgeRuntime
    (bit-identical to the dict path by construction, asserted here
    end-to-end at engine level); ``backend="jit"`` reruns the same
    rounds through the fused x64 lax kernels and must agree to float
    tolerance with IDENTICAL discrete decisions (cohorts, drop counts);
  * round contracts — the PR-3/PR-5 edge cases (empty cohort records
    cohort=0 and leaves the clock alone; an all-dropped round records
    cohort=0 while the clock still advances to the barrier and partial
    energy is billed) hold under the fleet path, including through a
    full ``FederatedRun`` with ``EdgeConfig.fleet="on"``.

The two observability satellites ride along: PlanAudit ``max_rows``
(exact totals, shortfall rows always retained) and the Chrome exporter's
``top_k_clients`` (slowest-finishing clients keep their tracks, the
round track stays complete).
"""
import dataclasses

import numpy as np
import pytest

from repro.configs.base import FedConfig
from repro.configs.paper_models import FMNIST_CNN, reduced
from repro.data.synthetic import make_classification
from repro.edge import (ChannelConfig, DeviceConfig, EdgeConfig,
                        EdgeRuntime, FleetEngine)
from repro.edge.fleet import FleetState
from repro.obs.export import to_chrome
from repro.obs.metrics import PlanAudit
from repro.obs.trace import CAT_CLIENT, CAT_ROUND, Tracer

UPLINK = ChannelConfig(bandwidth_hz=2e5, snr_db_mean=10.0, snr_db_std=3.0,
                       fading="rayleigh", server_rate_bps=50e6)
HETERO = DeviceConfig(flops_per_s_mean=2e9, flops_per_s_sigma=1.0)
UP, DOWN, FLOPS = 80_000.0, 40_000.0, 1e9
POLICIES = ["uniform", "bandwidth_opt", "energy_opt"]


def _cfg(policy="uniform", **kw):
    kw.setdefault("deadline_s", 5.0)
    kw.setdefault("min_clients", 1)
    kw.setdefault("enforce_deadline_s", 1.5)
    return EdgeConfig(channel=UPLINK, device=HETERO, scheduler=policy, **kw)


def _engine(policy="uniform", pop=300, backend="exact", seed=0, **kw):
    return FleetEngine(_cfg(policy, **kw), pop, up_bytes=UP, flops=FLOPS,
                       down_bytes=DOWN, seed=seed, backend=backend)


# ------------------------------------------------------------- state layer
def test_fleet_state_draw_and_alive_mask():
    st = FleetState.draw(UPLINK, HETERO, 64, seed=0)
    assert st.population == 64
    assert st.alive_mask().all()          # fresh fleet: charged, not busy
    st.fleet.battery_j[3] = 0.0
    st.busy[5] = True
    mask = st.alive_mask()
    assert not mask[3] and not mask[5] and mask.sum() == 62


@pytest.mark.parametrize("backend", ["exact", "jit"])
def test_cohort_without_replacement_from_alive_only(backend):
    eng = _engine("uniform", pop=100, backend=backend)
    eng.state.fleet.battery_j[:20] = 0.0    # shared with the runtime view
    for _ in range(3):
        eng.run_round(50)
        ids = np.asarray(eng.last_decision.selected)
        assert len(ids) == 50
        assert len(np.unique(ids)) == len(ids)          # no replacement
        assert ids.min() >= 20                          # depleted excluded


def test_busy_mask_respected_on_jit_backend():
    eng = _engine("uniform", pop=40, backend="jit")
    eng.state.busy[:30] = True
    eng.run_round(20)                      # only 10 alive -> short cohort
    ids = np.asarray(eng.last_decision.selected)
    assert set(ids) <= set(range(30, 40)) and len(ids) == 10


# -------------------------------------------------------- backend agreement
@pytest.mark.parametrize("policy", POLICIES)
def test_engine_exact_is_bit_identical_to_dict_runtime(policy):
    """backend='exact' forces the fleet fast path inside its runtime;
    replaying the same rounds on a fleet='off' runtime must land the
    SAME floats — the engine-level version of the determinism lock."""
    eng = _engine(policy, pop=200, backend="exact")
    for _ in range(3):
        eng.run_round(60)

    rt = EdgeRuntime(dataclasses.replace(_cfg(policy), fleet="off"), 200,
                     seed=0)
    for _ in range(3):
        _, est, _ = rt.decide(60, np.arange(200), lambda c=None: (UP, 0.0),
                              FLOPS, summable=True)
        rt.finish_round_sync(est, UP, DOWN, aggregatable=True)
    assert eng.clock_s == rt.clock.now
    assert eng.energy_j == rt.energy_j
    assert eng.deadline_dropped_total == rt.deadline_dropped_total
    assert np.array_equal(eng.state.battery_j, rt.fleet.battery_j)


@pytest.mark.parametrize("policy", POLICIES)
def test_jit_backend_matches_exact(policy):
    """Same seed, same rounds: the jit backend must draw the SAME
    cohorts and drop the SAME count (discrete decisions identical),
    with clock/energy/battery agreeing to float tolerance (XLA
    reassociation only)."""
    ex = _engine(policy, pop=300, backend="exact")
    jt = _engine(policy, pop=300, backend="jit")
    for _ in range(4):
        ra = ex.run_round(80)
        rb = jt.run_round(80)
        assert (np.asarray(ex.last_decision.selected)
                == np.asarray(jt.last_decision.selected)).all()
        assert ra["dropped"] == rb["dropped"]
        assert np.isclose(ra["wall_s"], rb["wall_s"], rtol=1e-9)
    assert np.isclose(ex.clock_s, jt.clock_s, rtol=1e-9)
    assert np.isclose(ex.energy_j, jt.energy_j, rtol=1e-9)
    assert np.allclose(ex.state.battery_j, jt.state.battery_j, rtol=1e-9)


# -------------------------------------------------------- round contracts
@pytest.mark.parametrize("backend", ["exact", "jit"])
def test_empty_cohort_round_records_zero_and_clock_unchanged(backend):
    """All batteries depleted: the round records cohort=0 / dropped=0
    and the clock does not advance (nobody transmitted) — the PR-3
    empty-cohort contract under the fleet path."""
    eng = _engine("uniform", pop=30, backend=backend)
    eng.state.fleet.battery_j[:] = 0.0
    rec = eng.run_round(10)
    assert rec["cohort"] == 0 and rec["dropped"] == 0
    assert eng.clock_s == 0.0 and eng.energy_j == 0.0
    assert eng.last_decision is None or eng.last_decision.n_selected == 0


@pytest.mark.parametrize("backend", ["exact", "jit"])
def test_all_dropped_round_bills_partials_and_advances_clock(backend):
    """An infeasibly tight hard deadline drops the whole cohort: the
    record shows cohort=0 with every selected client dropped, the
    barrier is cut at the deadline, and the partial uploads still cost
    energy + clock — the PR-5 all-dropped contract under the fleet
    path."""
    eng = _engine("uniform", pop=50, backend=backend,
                  enforce_deadline_s=0.01)
    rec = eng.run_round(20)
    assert rec["cohort"] == 0 and rec["dropped"] == 20
    assert rec["barrier_s"] <= 0.01 + 1e-6
    assert eng.clock_s > 0.0 and eng.energy_j > 0.0
    assert eng.deadline_dropped_total == 20


def test_fleet_federated_all_dropped_preserves_pr3_contract():
    """Through a full FederatedRun with the fleet path forced on: the
    all-dropped round records cohort=0 with no loss/server step while
    the partial uploads are still billed (tests/test_deadline_
    enforcement.py's contract, fleet edition)."""
    mcfg = reduced(FMNIST_CNN)
    train, test = make_classification(mcfg, n_train=120, n_test=40, seed=0,
                                      noise=0.5)
    edge = _cfg("uniform", enforce_deadline_s=0.01, fleet="on")
    fcfg = FedConfig(num_clients=8, participation=1.0, local_epochs=1,
                     batch_size=32, rounds=2, noniid_l=2, seed=0, edge=edge)
    from repro.fed.server import FederatedRun
    run = FederatedRun(mcfg, fcfg, train, test, "fedavg_sgd")
    hist = run.run(rounds=2, eval_every=2)
    for h in hist:
        assert h["cohort"] == 0
        assert "loss" not in h
        assert h["dropped"] > 0
    assert run.ledger.up_star_bytes > 0.0


# ------------------------------------------------- observability satellites
def test_plan_audit_max_rows_keeps_totals_and_shortfalls():
    a = PlanAudit(max_rows=4)
    for i in range(10):
        a.add(0, i, "up", 100.0, 100.0)       # clean rows
    a.add(1, 99, "up", 100.0, 40.0)           # shortfall: always retained
    assert len(a.rows) == 5                   # 4 clean + the shortfall
    assert a.dropped_rows == 6
    assert a.planned_total() == 1100.0        # totals cover every add
    assert a.billed_total() == 1040.0
    assert any(r.client == 99 and r.billed_bytes == 40.0 for r in a.rows)

    exhaustive = PlanAudit()                  # default: keep everything
    for i in range(10):
        exhaustive.add(0, i, "up", 100.0, 100.0)
    assert len(exhaustive.rows) == 10 and exhaustive.dropped_rows == 0


def test_plan_audit_max_rows_retains_overbilled_rows_for_verify():
    """Over-billing is a bug verify() must still see — those rows are
    never dropped either, even past the cap."""
    a = PlanAudit(max_rows=1)
    a.add(0, 0, "up", 100.0, 100.0)
    a.add(0, 1, "up", 100.0, 150.0)           # above plan: retained
    assert any(r.billed_bytes > r.planned_bytes for r in a.rows)

    class _Ledger:
        up_star_bytes = 250.0

    with pytest.raises(ValueError, match="ABOVE plan"):
        a.verify(_Ledger())


def test_chrome_export_top_k_clients_keeps_stragglers_and_round_track():
    tr = Tracer()
    tr.span("round", CAT_ROUND, 0.0, 10.0, round_id=0)
    finishes = {0: 2.0, 1: 9.0, 2: 7.0, 3: 4.0}
    for c, t1 in finishes.items():
        tr.span("uplink", CAT_CLIENT, 0.0, t1, round_id=0, client=c)

    full = to_chrome(tr, top_k_clients=None)
    capped = to_chrome(tr, top_k_clients=2)
    clients = {e["tid"] - 1 for e in capped["traceEvents"]
               if e.get("ph") == "X" and e["tid"] > 0}
    assert clients == {1, 2}                  # the two slowest finishers
    # the round-level track survives the cap intact
    rounds_full = [e for e in full["traceEvents"]
                   if e.get("ph") == "X" and e["tid"] == 0]
    rounds_capped = [e for e in capped["traceEvents"]
                     if e.get("ph") == "X" and e["tid"] == 0]
    assert rounds_capped == rounds_full and len(rounds_capped) == 1
    # k=0 leaves only the round track
    none_kept = to_chrome(tr, top_k_clients=0)
    assert all(e["tid"] == 0 for e in none_kept["traceEvents"]
               if e.get("ph") == "X")
