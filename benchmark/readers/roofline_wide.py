"""`readers/roofline.py` for the work models of benchmark/opcount_wide.py:
the least time the chip could take for the counted work (the larger of
operations over the bf16 peak and bytes over the HBM roof; peaks from
benchmark/peaks.json) over the selected modules' device time per job, in
percent. The layer file names the work function (`work`) and, argument by
argument, the counter of the run that fills it (`counters`). A program
that keeps no such counters (the parent of the PR that brought them) reads
nothing."""
from benchmark import harness, opcount, opcount_wide


def read(ctx, args):
    if ctx.peaks is None:
        return None
    seconds = harness.load_module("readers", "trace_time").read(ctx, args)
    if not seconds or any(key not in ctx.counters
                          for key in args["counters"].values()):
        return None
    flops, byts = getattr(opcount_wide, args["work"])(
        **{arg: ctx.counters[key] for arg, key in args["counters"].items()})
    least, roof = opcount.least_seconds(flops, byts, ctx.peaks)
    ctx.notes.setdefault("rooflines", {})[args["work"]] = {
        "flops": flops, "bytes": byts, "least_s": least, "roof": roof,
        "kernel_s": seconds}
    return 100.0 * least / seconds
