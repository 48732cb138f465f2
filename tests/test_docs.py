"""Docs checks: the architecture doc must mention every src/repro package,
and the README must carry the quickstart + tier-1 commands. CI runs these on
every push (.github/workflows/ci.yml) so docs cannot silently rot."""
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent


def _packages():
    src = REPO / "src" / "repro"
    return sorted(p.name for p in src.iterdir()
                  if p.is_dir() and (p / "__init__.py").exists())


def test_architecture_doc_mentions_every_package():
    doc = (REPO / "docs" / "architecture.md").read_text()
    missing = [pkg for pkg in _packages()
               if f"repro.{pkg}" not in doc and f"repro/{pkg}" not in doc]
    assert not missing, f"docs/architecture.md misses packages: {missing}"


def test_readme_has_quickstart_and_tier1_command():
    readme = (REPO / "README.md").read_text()
    assert "examples/quickstart.py" in readme
    assert "python -m pytest -x -q" in readme
    assert "benchmarks" in readme


def test_benchmarks_readme_covers_every_module():
    doc = (REPO / "benchmarks" / "README.md").read_text()
    mods = [p.stem for p in (REPO / "benchmarks").glob("*.py")
            if p.stem not in ("common", "run", "__init__")]
    missing = [m for m in mods if f"{m}.py" not in doc]
    assert not missing, f"benchmarks/README.md misses: {missing}"


def test_architecture_doc_has_policy_registry_guide():
    """The extension guide must exist and name every registered policy, so
    a policy shipped without docs fails tier-1."""
    from repro.core.policies import list_policies
    doc = (REPO / "docs" / "architecture.md").read_text()
    assert "Policy registry & extension guide" in doc
    assert "register_policy" in doc and "GenomeSpec" in doc
    missing = [p for p in list_policies() if f"`{p}`" not in doc]
    assert not missing, \
        f"docs/architecture.md policy guide misses policies: {missing}"


def test_readme_mentions_policy_registry():
    readme = (REPO / "README.md").read_text()
    assert "core/policies" in readme
    assert "p2c-hedge" in readme and "budget" in readme
    assert "disagg" in readme


def test_architecture_doc_has_disagg_section():
    """The disaggregated-serving section must exist and cover roles, the
    link model, transfer accounting, failure semantics, and the
    route-valued registry-extension note."""
    doc = (REPO / "docs" / "architecture.md").read_text()
    assert "Disaggregated prefill/decode & KV handoff" in doc
    for needle in ("route table", "kv_bw_bps", "disagg_testbed",
                   "EvalConfig(disaggregated=True)", "export_blocks",
                   "prefill_only", "transfer-in-flight",
                   'decides = "route"'):
        assert needle in doc, f"disagg docs miss: {needle}"


def test_benchmarks_readme_names_disagg():
    doc = (REPO / "benchmarks" / "README.md").read_text()
    assert "disagg.py" in doc and "split fraction" in doc


def test_architecture_doc_has_fleet_section():
    """The fleet-vectorized-serving section must exist and cover the cohort
    grouping rules, the host/device split, and the fallback conditions."""
    doc = (REPO / "docs" / "architecture.md").read_text()
    assert "Fleet-vectorized serving" in doc
    for needle in ("Cohort grouping rules", "build_cohorts", "fleet_ok",
                   "FleetState", "FleetMemberStore", "Fallback conditions",
                   "O(#cohorts)", "fleet=False", "fleet_testbed",
                   "record_fleet", "byte-identical"):
        assert needle in doc, f"fleet docs miss: {needle}"


def test_readme_and_bench_readme_name_fleet():
    readme = (REPO / "README.md").read_text()
    assert "serving/fleet.py" in readme and "cohort" in readme
    bench = (REPO / "benchmarks" / "README.md").read_text()
    assert "fleet_scale.py" in bench and "fleet_testbed" in bench
    assert "dispatches per saturated tick" in bench


def test_architecture_doc_has_observability_section():
    """The observability section must exist and cover the span model, the
    metric vocabulary, clock discipline, the audit, export, and the
    overhead budget."""
    doc = (REPO / "docs" / "architecture.md").read_text()
    assert "## Observability" in doc
    for needle in ("span conservation", "accounting mirror", "NOOP_TRACER",
                   "MetricsRegistry", "RouteAudit", "chrome_trace",
                   "Obs.noop()", "kv-transfer", "route-decision",
                   "scheduler ticks", "byte-identical",
                   'stats()["percentiles"]', "ewma_initialized",
                   "DeprecationWarning"):
        assert needle in doc, f"observability docs miss: {needle}"
    # the documented vocabulary stays in lockstep with the code
    from repro.obs.metrics import METRIC_NAMES
    from repro.obs.trace import EVENT_NAMES, PHASE_NAMES
    for name in PHASE_NAMES + EVENT_NAMES + METRIC_NAMES:
        assert name in doc, f"observability docs miss vocabulary: {name}"


def test_architecture_doc_names_timeline_vocabulary():
    """Every timeline span, counter and device scope name is documented
    in the observability section, in lockstep with the code."""
    doc = (REPO / "docs" / "architecture.md").read_text()
    from repro.obs.timeline import COUNTER_NAMES, SCOPE_NAMES, SPAN_NAMES
    for name in SPAN_NAMES + COUNTER_NAMES + SCOPE_NAMES:
        assert f"`{name}`" in doc, f"timeline docs miss: {name}"
    for needle in ("NOOP_TIMELINE", "perf_counter_ns", "TraceAnnotation",
                   "self_times", "gc.callbacks"):
        assert needle in doc, f"timeline docs miss: {needle}"


def test_readme_and_bench_readme_name_obs():
    readme = (REPO / "README.md").read_text()
    assert "src/repro/obs/" in readme and "obs_overhead.py" in readme
    assert "p50/p95/p99" in readme
    bench = (REPO / "benchmarks" / "README.md").read_text()
    assert "obs_overhead.py" in bench and "BENCH_obs.json" in bench
    assert "Chrome-trace" in bench


def test_architecture_doc_has_resilience_section():
    """The resilience section must exist and cover the fault vocabulary,
    three-layer equivalence, breaker state machine, retry-budget semantics,
    slow-credit straggling, and the shed policy."""
    doc = (REPO / "docs" / "architecture.md").read_text()
    assert "Resilience & fault injection" in doc
    for needle in ("FaultSchedule", "CrashWindow", "Straggler", "LinkFlap",
                   "HeartbeatLoss", "TransientErrors", "crash_storm",
                   "EvalConfig.faulty", "half-open", "breaker_threshold",
                   "Retry-budget semantics", "backoff_jitter_u",
                   "deadline-aware", "slow-credit", "Shed policy",
                   "shed_threshold", "brownout", "ResilienceConfig",
                   "reset_breaker", "chaos.py"):
        assert needle in doc, f"resilience docs miss: {needle}"


def test_architecture_doc_has_learning_section():
    """The online-learning section must exist and cover both update rules,
    the cold-start/residual contract, the clock/feature contract, the
    PolicyInputs override, and the bandit policy."""
    doc = (REPO / "docs" / "architecture.md").read_text()
    assert "Online-learned estimators & bandit routing" in doc
    for needle in ("repro.learn", "LearnConfig", "Sherman–Morrison",
                   "EvalConfig(learned=True", "OnlineEstimator",
                   "corrected_rows", "feed_estimator", "cold start",
                   "Clock/feature contract", "PolicyInputs override",
                   "`bandit`", "learn_state", "residual",
                   'requires={"quality"}'):
        assert needle in doc, f"learning docs miss: {needle}"


def test_readme_and_bench_readme_name_learning():
    readme = (REPO / "README.md").read_text()
    assert "src/repro/learn/" in readme and "bandit" in readme
    assert "learned" in readme
    bench = (REPO / "benchmarks" / "README.md").read_text()
    assert "online_learning.py" in bench and "BENCH_learning.json" in bench
    assert "estimator error" in bench


def test_readme_and_bench_readme_name_chaos():
    readme = (REPO / "README.md").read_text()
    assert "src/repro/faults/" in readme and "chaos.py" in readme
    assert "circuit breaker" in readme and "shed" in readme
    bench = (REPO / "benchmarks" / "README.md").read_text()
    assert "chaos.py" in bench and "BENCH_chaos.json" in bench
    assert "crash-storm" in bench and "SLO attainment" in bench
