"""Fleet cohort: device self time of the cohort's own data movement over
all device self time of the ``_cohort_decode_chunk`` executables in the
traced slice, in %. The movement is what runs under the ``cohort.gather``
and ``cohort.merge`` scopes, and the copies XLA makes of the stacked state
argument to feed them (``argument``: a relayout, or the copy a scatter
into an argument that is not donated needs)."""

JIT = "_cohort_decode_chunk"
COPIES = ("cohort.gather", "cohort.merge", "argument")


def read(rec):
    scopes = (rec.get("program") or {}).get("scopes")
    if not scopes:
        return None
    by = [s for mod, s in scopes.items() if JIT in mod]
    total = sum(t for s in by for t in s.values())
    copies = sum(s.get(k, 0.0) for s in by for k in COPIES)
    return 100.0 * copies / total if total > 0 else None
