"""Seconds per job in which no op ran on the device: the job span's wall
less the union of device-op intervals inside it."""


def read(ctx, args):
    red = ctx.reduced
    jobs = red.jobs(ctx.cell["job_span"]) if red is not None else []
    if not jobs:
        return None
    gap = sum((j.end - j.start) - red.busy_ns(j.start, j.end) for j in jobs)
    return gap / len(jobs) / 1e9
