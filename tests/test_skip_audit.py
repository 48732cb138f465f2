"""Skip hygiene guard: skipping a test is a conscious, recorded choice.

Tier-1 historically carried 25 silent skips — every ``@given`` property
vanished in containers without hypothesis. The ``_hypothesis_compat`` shim
now runs those properties as deterministic fixed-sample sweeps instead, so
the suite's only remaining skip site is the shim's unsupported-strategy
escape hatch. This module fails the build if

* a skip/xfail site appears outside the recorded allowlist (new skips must
  be added here deliberately),
* a skip site omits an explicit ``reason`` string, or
* a ``@given`` declares a strategy the deterministic fallback cannot sample
  (which would silently re-introduce environment-dependent skips).
"""
import re
from pathlib import Path

TESTS = Path(__file__).resolve().parent

# file -> number of skip/xfail *sites* it is allowed to contain
SKIP_SITE_ALLOWLIST = {
    # the shim's escape hatch for strategies without a fallback sampler;
    # unreachable today (see test_given_strategies_* below) but kept so an
    # unsupported strategy degrades loudly instead of crashing collection
    "_hypothesis_compat.py": 1,
    # its fixture skips only where the TPU compiler library cannot describe
    # a chip; where it can, every compile test must run
    "test_tpu_compile.py": 1,
}

_SKIP_PAT = re.compile(
    r"pytest\s*\.\s*(?:mark\s*\.\s*)?(?:skip|skipif|importorskip|xfail)\b")
_FALLBACK_STRATEGIES = {"integers", "floats", "booleans"}


def _source_files():
    return [p for p in sorted(TESTS.glob("*.py"))
            if p.name != Path(__file__).name]


def test_skip_sites_are_allowlisted_with_reasons():
    for path in _source_files():
        lines = path.read_text().splitlines()
        hits = [(i + 1, ln) for i, ln in enumerate(lines)
                if _SKIP_PAT.search(ln)
                and not ln.lstrip().startswith(("#", "`"))]
        allowed = SKIP_SITE_ALLOWLIST.get(path.name, 0)
        assert len(hits) <= allowed, (
            f"{path.name} has {len(hits)} skip site(s), allowlist permits "
            f"{allowed}: {hits}\nadd it to SKIP_SITE_ALLOWLIST only as a "
            f"conscious choice")
        for lineno, _ in hits:
            stmt = " ".join(lines[lineno - 1:lineno + 2])
            assert "reason" in stmt, (
                f"{path.name}:{lineno} skips without an explicit reason")


def test_given_strategies_have_deterministic_fallback():
    """Every ``@given`` must stay runnable without hypothesis: its strategies
    must all be ones the shim can sample deterministically."""
    pat = re.compile(r"@given\(([^)]*)\)")
    for path in _source_files():
        for m in pat.finditer(path.read_text()):
            used = set(re.findall(r"st\.(\w+)", m.group(1)))
            unsupported = used - _FALLBACK_STRATEGIES
            assert not unsupported, (
                f"{path.name}: @given uses st.{unsupported} which the "
                f"deterministic fallback in _hypothesis_compat.py cannot "
                f"sample — extend the shim or the property will skip")
