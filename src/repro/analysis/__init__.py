"""repro.analysis — the repo's runtime contracts as static AST checks.

Six built-in rules turn invariants that the test matrices only catch at
runtime (and only on exercised paths) into structural properties that
fail in seconds on a bare Python install:

  ======== ================== ==============================================
  RPL001   sim-determinism    no wall clocks / global RNG in edge, fed, obs
  RPL002   x64-hygiene        no module-level jax.config.update; fleet
                              kernels under ``with jax.enable_x64(True):``
  RPL003   jit-purity         no host syncs / Python branching on tracers
                              inside jitted kernels
  RPL004   registry-contract  registered strategies/codecs/policies declare
                              what the generic drivers consume
  RPL005   tracer-noop        telemetry work is skipped, not discarded,
                              under NULL_TRACER
  RPL006   ledger-discipline  every upload billed at explicit wire_bytes
  ======== ================== ==============================================

CLI: ``python -m repro.analysis [--format text|json] [--baseline FILE]
[paths...]``.  Suppress one site with ``# repro: allow[RPL001]``;
grandfather existing findings into the committed baseline with
``--write-baseline``.  The package is pure stdlib and never imports the
modules it lints.
"""
from repro.analysis.core import (Baseline, Finding, ModuleSource,  # noqa: F401
                                 Rule, all_rules, check_module, get, names,
                                 register, run_paths)
