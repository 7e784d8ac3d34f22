"""Where a job's host seconds go, by the program's own spans: the host
annotations `tmog.<kind>:<name>` that `collector.trace_span` writes into
the profiler's trace (transmogrifai_tpu/utils/metrics.py), on the thread
of the job span and inside it. `name` is a regular expression on the
event's name; `stat` one of

- `exposed_s`: the union of the matching spans' intervals, less the time
  an op ran on the device inside that union: seconds the chip idled while
  the host was in that phase;
- `count`: how many spans match;
- `uncovered_s`: the job's idle time (wall less device-busy) that falls
  under no `tmog.` span other than those `name` matches (the root): host
  work that still has no name.

All are means over the traced jobs. Spans that do not overlap (the
top-level phases of validate()) split the host gap between them: their
`exposed_s` and the `uncovered_s` add up to what `host_gap` reads.

A trace with no `tmog.` span at all comes from a program older than its
annotations: it reads 0 spans, 0 s exposed and the whole idle time
uncovered, which is what that trace shows. A trace that has them, but
none that `name` matches, reads nothing: a span was renamed or has gone,
and on the chip that fails the run."""
import re

PREFIX = "tmog."


def _idle_ns(red, intervals) -> float:
    """Length of the union of the intervals, less device-busy time."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return sum((e - s) - red.busy_ns(s, e) for s, e in merged)


def read(ctx, args):
    red = ctx.reduced
    jobs = red.jobs(ctx.cell["job_span"]) if red is not None else []
    if not jobs:
        return None
    stat, pattern = args["stat"], re.compile(args["name"])
    if stat not in ("exposed_s", "count", "uncovered_s"):
        raise ValueError(f"host_span: no such stat {stat!r}")
    values, annotated, matched = [], False, False
    for job in jobs:
        spans = [(max(s, job.start), min(e, job.end), pattern.search(name))
                 for s, e, name in red.host.get(job.line, ())
                 if name.startswith(PREFIX) and s < job.end and e > job.start]
        hits = [(s, e) for s, e, hit in spans if hit]
        annotated, matched = annotated or bool(spans), matched or bool(hits)
        if stat == "count":
            values.append(len(hits))
        elif stat == "exposed_s":
            values.append(_idle_ns(red, hits) / 1e9)
        else:
            cover = [(s, e) for s, e, hit in spans if not hit]
            values.append((_idle_ns(red, [(job.start, job.end)])
                           - _idle_ns(red, cover)) / 1e9)
    if annotated and not matched:
        return None
    return sum(values) / len(values)
