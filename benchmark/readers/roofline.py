"""A kernel's share of its roofline, in percent: the least time the chip
could take for the work (benchmark/opcount.py, from shapes; peaks from
benchmark/peaks.json) over the kernel's device time per job from the
trace. The layer file names the opcount function (`work`) and, argument
by argument, the counter of the run that fills it (`counters`): a roofline
for another kernel or driver is a new layer file, and a new opcount
function where the work is new."""
from benchmark import harness, opcount


def read(ctx, args):
    if ctx.peaks is None:
        return None
    seconds = harness.load_module("readers", "trace_time").read(ctx, args)
    if not seconds or any(key not in ctx.counters
                          for key in args["counters"].values()):
        return None
    flops, byts = getattr(opcount, args["work"])(
        **{arg: ctx.counters[key] for arg, key in args["counters"].items()})
    least, roof = opcount.least_seconds(flops, byts, ctx.peaks)
    ctx.notes.setdefault("rooflines", {})[args["work"]] = {
        "flops": flops, "bytes": byts, "least_s": least, "roof": roof,
        "kernel_s": seconds}
    return 100.0 * least / seconds
