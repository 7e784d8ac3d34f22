"""`readers/roofline.py` for a work model in ANY module of benchmark/: the
layer file names the module (`opcount`, e.g. "opcount_forest"), its
function (`work`) and, argument by argument, the counter of the run that
fills it (`counters`). The least time the chip could take for the counted
work (the larger of operations over the bf16 peak and bytes over the HBM
roof; peaks from benchmark/peaks.json) over the selected ops' device time
per job, in percent. A later cell's roofline is a layer file and a work
function, not another copy of this reader (PERF.md §7 (h), D8). A program
that keeps no such counters (the parent of the PR that brought them) reads
nothing."""
import importlib

from benchmark import harness, opcount


def read(ctx, args):
    if ctx.peaks is None:
        return None
    seconds = harness.load_module("readers", "trace_time").read(ctx, args)
    if not seconds or any(key not in ctx.counters
                          for key in args["counters"].values()):
        return None
    if not harness.NAME_RE.match(args["opcount"]):
        raise harness.BenchFailure(f"bad opcount module {args['opcount']!r}")
    work = getattr(importlib.import_module("benchmark." + args["opcount"]),
                   args["work"])
    flops, byts = work(**{arg: ctx.counters[key]
                          for arg, key in args["counters"].items()})
    least, roof = opcount.least_seconds(flops, byts, ctx.peaks)
    ctx.notes.setdefault("rooflines", {})[args["work"]] = {
        "flops": flops, "bytes": byts, "least_s": least, "roof": roof,
        "kernel_s": seconds}
    return 100.0 * least / seconds
