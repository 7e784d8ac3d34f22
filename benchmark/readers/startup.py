"""Where the process's start-up went, by the program's own ledger:
`transmogrifai_tpu.utils.platform.startup_record()`, kept from the package's
import to the close of the first root job span (the warm-up job), all on
`time.time()`. `key` is one of the record's totals: the six seconds
`startup_import_s`, `startup_reach_device_s`, `startup_trace_lower_s`,
`startup_cache_load_s`, `startup_compile_s`, `startup_run_s`, which add up
to its `first_contact_s`, and the count `startup_programs`.

The record is read once a run and kept whole in the report's notes
(`startup_record`: the totals, `setup_s` has its own line, and the
per-program rows, slowest first).

A program older than its ledger (no `startup_record`: the parent of the PR
that brought it) recorded nothing: every key reads 0, which is what that
program holds, as `host_span` reads a trace without annotations. A program
that has the record, but not `key` in it, or no number there, reads
nothing: a field was renamed or has gone, and on the chip that fails the
run."""

ROWS_KEPT = 40


def _record(ctx):
    if "startup_record" not in ctx.notes:
        from transmogrifai_tpu.utils import platform
        fn = getattr(platform, "startup_record", None)
        rec = fn() if fn is not None else None
        if rec is not None:
            rec = dict(rec, programs=rec["programs"][:ROWS_KEPT],
                       later_programs=rec["later_programs"][:ROWS_KEPT])
        ctx.notes["startup_record"] = rec
    return ctx.notes["startup_record"]


def read(ctx, args):
    rec = _record(ctx)
    if rec is None:
        return 0
    value = rec.get(args["key"])
    if isinstance(value, bool) or not isinstance(value, (int, float)):
        return None
    return value
