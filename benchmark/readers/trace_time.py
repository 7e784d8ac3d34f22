"""Device seconds per job of the ops a layer file's patterns select:
`module` and `op` are regular expressions on the XLA module and op name.
The time is the union of those ops' intervals inside each job span."""


def read(ctx, args):
    red = ctx.reduced
    if red is None:
        return None
    ops = red.select(args.get("module"), args.get("op"))
    if not ops:
        return None
    return red.per_job_s(ops, ctx.cell["job_span"])
