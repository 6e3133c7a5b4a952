"""repro.analysis: the contract linter's own test suite.

Per rule: a fixture snippet that must fire (positive), its corrected
twin that must stay quiet (negative), and the suppression layers
(pragma, baseline) + CLI surface (JSON schema, exit codes).  Everything
runs on in-memory sources — no jax, no file tree needed.
"""
from __future__ import annotations

import json
import subprocess
import sys
import textwrap

import pytest

from repro.analysis import (Baseline, Finding, ModuleSource, Rule, all_rules,
                            check_module, get, names, run_paths)
from repro.analysis.cli import main as cli_main

SIM_PATH = "src/repro/edge/some_module.py"


def lint(src: str, path: str = SIM_PATH, rule: str | None = None):
    mod = ModuleSource(path, textwrap.dedent(src))
    rules = [get(rule)()] if rule else None
    return check_module(mod, rules=rules)


def rule_ids(findings):
    return sorted({f.rule for f in findings})


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------
def test_registry_has_the_six_contract_rules():
    assert names() == ["RPL001", "RPL002", "RPL003", "RPL004", "RPL005",
                       "RPL006"]
    for r in all_rules():
        assert r.id and r.title and r.description


def test_third_party_rule_registers_like_a_strategy():
    from repro.analysis import core

    class XRule(Rule):
        id = "TST900"
        title = "test"
        description = "fixture"

        def check(self, mod):
            return [self.finding(mod, mod.tree.body[0], "always")]

    core.register(XRule)
    try:
        out = check_module(ModuleSource("x.py", "a = 1\n"), rules=[XRule()])
        assert [f.rule for f in out] == ["TST900"]
    finally:
        core._REGISTRY.pop("TST900")


# ---------------------------------------------------------------------------
# RPL001 sim-determinism
# ---------------------------------------------------------------------------
RPL001_BAD = """\
    import time
    import numpy as np

    def sample():
        t = time.time()
        noise = np.random.randn(4)
        return t, noise
"""

RPL001_GOOD = """\
    import numpy as np

    def sample(clock, rng: np.random.Generator):
        t = clock.now
        noise = rng.standard_normal(4)
        gen = np.random.default_rng(17)
        return t, noise, gen
"""


def test_rpl001_fires_on_wall_clock_and_global_rng():
    out = lint(RPL001_BAD, rule="RPL001")
    assert len(out) == 2
    assert "time.time" in out[0].message
    assert "np.random.randn" in out[1].message


def test_rpl001_quiet_on_seeded_generators_and_clock():
    assert lint(RPL001_GOOD, rule="RPL001") == []


def test_rpl001_scoped_to_sim_paths():
    assert lint(RPL001_BAD, path="benchmarks/common.py") == []
    for p in ("src/repro/fed/x.py", "src/repro/obs/x.py"):
        assert rule_ids(lint(RPL001_BAD, path=p)) == ["RPL001"]


def test_rpl001_datetime_and_random_module():
    src = """\
        import random
        from datetime import datetime

        def stamp():
            return datetime.now(), random.random()
    """
    out = lint(src, rule="RPL001")
    assert len(out) == 2
    # seeded generator objects stay legal
    ok = "import random\nr = random.Random(3)\n"
    assert lint(ok, rule="RPL001") == []


# ---------------------------------------------------------------------------
# RPL002 x64-hygiene
# ---------------------------------------------------------------------------
RPL002_BAD = """\
    import jax
    from functools import partial

    jax.config.update("jax_enable_x64", True)

    @partial(jax.jit, static_argnames=("iters",))
    def _widths(x, iters):
        return x * iters

    def widths(x, iters=5):
        return _widths(x, iters)
"""

RPL002_GOOD = """\
    import jax
    from functools import partial

    @partial(jax.jit, static_argnames=("iters",))
    def _widths(x, iters):
        return x * iters

    def widths(x, iters=5):
        with jax.enable_x64(True):
            return _widths(x, iters)
"""

FLEET_PATH = "src/repro/edge/fleet/kernel.py"


def test_rpl002_fires_on_global_flip_and_unscoped_kernel_call():
    out = lint(RPL002_BAD, path=FLEET_PATH, rule="RPL002")
    msgs = [f.message for f in out]
    assert len(out) == 2
    assert any("jax.config.update" in m for m in msgs)
    assert any("enable_x64" in m and "_widths" in m for m in msgs)


def test_rpl002_quiet_when_scoped():
    assert lint(RPL002_GOOD, path=FLEET_PATH, rule="RPL002") == []


def test_rpl002_config_update_inside_function_is_fine():
    src = """\
        import jax

        def enable():
            jax.config.update("jax_enable_x64", True)
    """
    assert lint(src, path=FLEET_PATH, rule="RPL002") == []


def test_rpl002_kernel_scoping_only_in_fleet():
    # outside edge/fleet/ only the module-level config flip fires
    out = lint(RPL002_BAD, path="src/repro/kernels/ops.py", rule="RPL002")
    assert len(out) == 1 and "jax.config.update" in out[0].message


# ---------------------------------------------------------------------------
# RPL003 jit-purity
# ---------------------------------------------------------------------------
RPL003_BAD = """\
    import jax
    import jax.numpy as jnp

    @jax.jit
    def step(w, budget):
        if w.sum() > budget:
            w = w * 0.5
        total = float(jnp.sum(w))
        peak = w.max().item()
        return w, total, peak
"""

RPL003_GOOD = """\
    import jax
    import jax.numpy as jnp
    from functools import partial

    @partial(jax.jit, static_argnames=("iters",))
    def step(w, budget, iters):
        if iters > 3:          # static arg: trace-time branching is fine
            w = w * 0.5
        w = jnp.where(jnp.sum(w) > budget, w * 0.5, w)
        B, D = w.shape
        db = min(64, D)        # shape-derived python ints are static
        return w, db
"""


def test_rpl003_fires_on_branch_and_host_syncs():
    out = lint(RPL003_BAD, path=FLEET_PATH, rule="RPL003")
    msgs = " | ".join(f.message for f in out)
    assert len(out) == 3
    assert "Python if" in msgs
    assert "float()" in msgs
    assert ".item()" in msgs


def test_rpl003_quiet_on_static_branching_and_lax_style():
    assert lint(RPL003_GOOD, path=FLEET_PATH, rule="RPL003") == []


def test_rpl003_only_inside_jit_functions():
    src = """\
        def host_side(w):
            if w.sum() > 0:
                return float(w.sum())
            return w.max().item()
    """
    assert lint(src, path=FLEET_PATH, rule="RPL003") == []
    # and only in the kernel files
    assert lint(RPL003_BAD, path="src/repro/edge/runtime.py",
                rule="RPL003") == []


# ---------------------------------------------------------------------------
# RPL004 registry-contract
# ---------------------------------------------------------------------------
RPL004_STRATEGY_BAD = """\
    from repro.fed.strategies.base import FedStrategy, RoundPlan, register

    @register("broken")
    class Broken(FedStrategy):
        def client_step(self, data, rng, context=None):
            return None, 0.0
"""

RPL004_PLAN_INCOMPLETE = """\
    from repro.fed.strategies.base import (FedStrategy, PhasePlan, RoundPlan,
                                           register)

    @register("half")
    class Half(FedStrategy):
        def _make_plan(self):
            return RoundPlan(phases=(PhasePlan("up", up_floats=10.0),))
"""

RPL004_STRATEGY_GOOD = """\
    from repro.fed.strategies.base import (FedStrategy, PhasePlan, RoundPlan,
                                           register)

    @register("ok")
    class Ok(FedStrategy):
        def _make_plan(self):
            return RoundPlan(phases=(PhasePlan("up", up_floats=10.0),),
                             flops=lambda n_k: 6.0 * n_k, summable=True)
"""


def test_rpl004_strategy_without_plan_fires():
    out = lint(RPL004_STRATEGY_BAD, path="src/repro/fed/x.py", rule="RPL004")
    assert len(out) == 1 and "_make_plan" in out[0].message


def test_rpl004_incomplete_roundplan_fires():
    out = lint(RPL004_PLAN_INCOMPLETE, path="src/repro/fed/x.py",
               rule="RPL004")
    assert len(out) == 1 and "flops" in out[0].message


def test_rpl004_complete_strategy_quiet():
    assert lint(RPL004_STRATEGY_GOOD, path="src/repro/fed/x.py",
                rule="RPL004") == []


def test_rpl004_imported_base_is_trusted():
    src = """\
        from repro.fed.strategies.base import register
        from repro.fed.strategies.fedavg import LocalSolveStrategy

        @register("prox")
        class Prox(LocalSolveStrategy):
            pass
    """
    assert lint(src, path="src/repro/fed/x.py", rule="RPL004") == []


def test_rpl004_codec_and_direct_register_call():
    bad = """\
        from repro.fed.codecs import PayloadCodec, register

        class Fp16(PayloadCodec):
            pass

        register("fp16", Fp16)
    """
    out = lint(bad, path="examples/custom_codec.py", rule="RPL004")
    assert len(out) == 1 and "wire_bytes" in out[0].message
    good = """\
        from repro.fed.codecs import PayloadCodec, register

        class Fp16(PayloadCodec):
            def wire_bytes(self, n_floats):
                return 2.0 * n_floats

        register("fp16", Fp16)
    """
    assert lint(good, path="examples/custom_codec.py", rule="RPL004") == []


def test_rpl004_decide_vectorized_signature():
    bad = """\
        class P:
            def decide_vectorized(self, fstate, extra):
                return None
    """
    out = lint(bad, path="src/repro/edge/policies.py", rule="RPL004")
    assert len(out) == 1 and "decide_vectorized" in out[0].message
    good = """\
        class P:
            def decide_vectorized(self, fstate):
                return None
    """
    assert lint(good, path="src/repro/edge/policies.py", rule="RPL004") == []


# ---------------------------------------------------------------------------
# RPL005 tracer-noop
# ---------------------------------------------------------------------------
RPL005_BAD = """\
    def round_end(tracer, t, cohort):
        tracer.event("alloc", "client", t, detail=f"cohort={cohort}")
        tracer.metrics.counter("drops_total").inc(1.0, **{"reason": "x"})
"""

RPL005_GOOD = """\
    def round_end(tracer, t, cohort):
        if tracer.enabled:
            tracer.event("alloc", "client", t, detail=f"cohort={cohort}")
        tracer.event("alloc", "client", t, cohort=cohort)  # lazy: no work
"""


def test_rpl005_fires_on_unguarded_eager_args():
    out = lint(RPL005_BAD, rule="RPL005")
    assert len(out) == 2
    assert all("NULL_TRACER" in f.message for f in out)


def test_rpl005_quiet_under_enabled_guard_or_lazy_args():
    assert lint(RPL005_GOOD, rule="RPL005") == []


def test_rpl005_early_out_guard_counts():
    src = """\
        def trace_round(tracer, rows):
            if not tracer.enabled:
                return
            tracer.record_round({"rows": len(rows)})
    """
    assert lint(src, rule="RPL005") == []


def test_rpl005_metric_alias_receiver_is_tracked():
    src = """\
        def meter(self, x):
            m = self.tracer.metrics
            m.gauge("battery_j").set(x, labels={"client": 1})
    """
    out = lint(src, rule="RPL005")
    assert len(out) == 1
    # non-tracer receivers with the same method names stay out of scope
    quiet = """\
        def collect(seen, items):
            seen.add({"k": 1})
            items.set(0, {"k": 1})
    """
    assert lint(quiet, rule="RPL005") == []


# ---------------------------------------------------------------------------
# RPL006 ledger-discipline
# ---------------------------------------------------------------------------
RPL006_BAD = """\
    def meter(ledger, plan, k):
        ledger.upload(plan.up_floats, k, aggregatable=True)
"""

RPL006_GOOD = """\
    def meter(ledger, ph, k, billed):
        ledger.upload(ph.up_floats, k, aggregatable=True,
                      wire_bytes=ph.wire_up_bytes())
        ledger.upload_per_client(billed, aggregatable=True)
"""


def test_rpl006_fires_without_wire_bytes():
    out = lint(RPL006_BAD, rule="RPL006")
    assert len(out) == 1 and "wire_bytes" in out[0].message


def test_rpl006_quiet_with_explicit_wire_bytes():
    assert lint(RPL006_GOOD, rule="RPL006") == []


# ---------------------------------------------------------------------------
# pragma suppression
# ---------------------------------------------------------------------------
def test_pragma_suppresses_named_rule_on_its_line():
    src = """\
        import time

        def stamp():
            return time.time()  # repro: allow[RPL001]
    """
    assert lint(src, rule="RPL001") == []


def test_pragma_on_comment_line_covers_next_line():
    src = """\
        import time

        def stamp():
            # repro: allow[RPL001]
            return time.time()
    """
    assert lint(src, rule="RPL001") == []


def test_pragma_wrong_rule_does_not_suppress():
    src = """\
        import time

        def stamp():
            return time.time()  # repro: allow[RPL006]
    """
    assert len(lint(src, rule="RPL001")) == 1


def test_pragma_star_suppresses_everything():
    src = """\
        import time

        def stamp():
            return time.time()  # repro: allow[*]
    """
    assert lint(src) == []


# ---------------------------------------------------------------------------
# baseline filtering
# ---------------------------------------------------------------------------
def _one_finding():
    out = lint(RPL006_BAD, rule="RPL006")
    assert len(out) == 1
    return out[0]


def test_baseline_filters_by_fingerprint_not_line(tmp_path):
    f = _one_finding()
    bl = Baseline.from_findings([f])
    # same content on a different line: fingerprint is line-free
    moved = Finding(f.rule, f.path, f.line + 40, f.col, f.message, f.snippet)
    fresh, eaten = bl.filter([moved])
    assert fresh == [] and eaten == 1
    # a different violation is NOT covered
    other = Finding(f.rule, f.path, 3, 0, f.message, "ledger.upload(z, 9)")
    fresh, eaten = bl.filter([other])
    assert fresh == [other] and eaten == 0


def test_baseline_counts_cap_duplicates():
    f = _one_finding()
    bl = Baseline.from_findings([f])          # budget: 1 occurrence
    fresh, eaten = bl.filter([f, f])
    assert eaten == 1 and len(fresh) == 1


def test_baseline_roundtrips_through_disk(tmp_path):
    f = _one_finding()
    path = str(tmp_path / "bl.json")
    Baseline.from_findings([f]).write(path)
    loaded = Baseline.load(path)
    assert loaded.counts == {f.fingerprint(): 1}
    assert Baseline.load(str(tmp_path / "missing.json")).counts == {}


# ---------------------------------------------------------------------------
# CLI: exit codes, JSON schema, parse errors
# ---------------------------------------------------------------------------
def _write(tmp_path, name, src):
    p = tmp_path / name
    p.write_text(textwrap.dedent(src))
    return str(p)


def test_cli_exit_codes_and_json_schema(tmp_path, capsys):
    bad = _write(tmp_path, "mod.py",
                 "def f(ledger, d, k):\n    ledger.upload(d, k)\n")
    rc = cli_main(["--format", "json", "--no-baseline", bad])
    payload = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert payload["version"] == 1
    assert set(payload["rules"]) == set(names())
    (f,) = payload["findings"]
    assert set(f) == {"rule", "path", "line", "col", "message", "snippet",
                      "fingerprint"}
    assert f["rule"] == "RPL006" and f["line"] == 2

    ok = _write(tmp_path, "ok.py", "x = 1\n")
    assert cli_main(["--no-baseline", ok]) == 0
    capsys.readouterr()


def test_cli_baseline_workflow(tmp_path, capsys):
    bad = _write(tmp_path, "mod.py",
                 "def f(ledger, d, k):\n    ledger.upload(d, k)\n")
    bl = str(tmp_path / "baseline.json")
    assert cli_main(["--baseline", bl, "--write-baseline", bad]) == 0
    assert cli_main(["--baseline", bl, bad]) == 0         # grandfathered
    assert cli_main(["--baseline", bl, "--no-baseline", bad]) == 1
    capsys.readouterr()


def test_cli_parse_error_is_a_finding(tmp_path, capsys):
    broken = _write(tmp_path, "broken.py", "def f(:\n")
    rc = cli_main(["--format", "json", "--no-baseline", broken])
    payload = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert payload["findings"][0]["rule"] == "PARSE"


def test_cli_select_unknown_rule_errors():
    with pytest.raises(SystemExit):
        cli_main(["--select", "NOPE01", "src/repro/analysis"])


def test_run_paths_walks_directories(tmp_path):
    sub = tmp_path / "pkg"
    sub.mkdir()
    (sub / "a.py").write_text("def f(ledger, d, k):\n    ledger.upload(d, k)\n")
    (sub / "b.txt").write_text("not python")
    out = run_paths([str(tmp_path)])
    assert [f.rule for f in out] == ["RPL006"]


# ---------------------------------------------------------------------------
# the analyzer must never import what it lints
# ---------------------------------------------------------------------------
def test_analyzer_is_pure_stdlib():
    code = ("import sys; import repro.analysis.cli; "
            "bad = [m for m in ('jax', 'numpy', 'repro.fed', 'repro.edge', "
            "'repro.obs') if m in sys.modules]; "
            "sys.exit(1 if bad else 0)")
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr


def test_repo_tree_is_clean_under_committed_baseline():
    """The acceptance gate, as a test: src+benchmarks+examples lint
    clean against the committed baseline."""
    import os
    root = os.path.join(os.path.dirname(__file__), "..")
    rc = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "src", "benchmarks",
         "examples"],
        cwd=root, capture_output=True, text=True,
        env={**os.environ,
             "PYTHONPATH": os.path.join(root, "src")
             + os.pathsep + os.environ.get("PYTHONPATH", "")})
    assert rc.returncode == 0, rc.stdout + rc.stderr
