"""A count the run made: `key` in the run's counters (compiles from
jax.monitoring, telemetry the program keeps)."""


def read(ctx, args):
    return ctx.counters.get(args["key"])
