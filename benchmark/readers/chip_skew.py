"""How unevenly the chips of a cell were loaded: per job, the busy seconds
(union of device-op intervals inside the job span) of the busiest chip less
those of the idlest, as a mean over the traced jobs. `module` and `op`
(regular expressions, as `trace_time` takes them) narrow the ops; without
them every op counts. A trace with fewer than two chips (one chip, or a
CPU rehearsal's stand-in) has no skew to read."""
from benchmark.reduce_trace import clip, union_ns


def read(ctx, args):
    red = ctx.reduced
    if red is None or len(red.chips) < 2:
        return None
    jobs = red.jobs(ctx.cell["job_span"])
    ops = red.select(args.get("module"), args.get("op"))
    if not jobs or not ops:
        return None
    skews = []
    for job in jobs:
        busy = [union_ns(clip(((o.start, o.end) for o in ops
                               if o.chip == chip), job.start, job.end))
                for chip in red.chips]
        skews.append(max(busy) - min(busy))
    ctx.notes.setdefault("chip_busy_s", [b / 1e9 for b in busy])
    return sum(skews) / len(skews) / 1e9
