"""Reaching the device in two, and the seconds before the package's import,
by the program's own ledger: the fields that
`transmogrifai_tpu.utils.platform.startup_record()` holds since its
`ledger_version` 2 (PR 49). `key` is `startup_backend_up_s` (end of the
package's import to the instant the backend came up),
`startup_first_dispatch_s` (from there to the first program event; the two
add up to `startup_reach_device_s`) or `startup_backend_up_cpu_s` (the
process's CPU seconds inside the first of them).

The record is `readers/startup.py`'s, read once a run and kept in the
report's notes (`startup_record`); this reader differs in one rule. A
record with no `ledger_version` is a program older than these fields (the
parent of PR 49, which the driver runs under this PR's benchmark files):
every key reads 0, which is what that program holds, and is not a
measurement. A record that HAS the version, but not `key`, or no number
there (no backend came up through the factories the program times: a field
was lost, or jax hides them), reads nothing, and on the chip that fails the
run."""
from benchmark import harness

startup = harness.load_module("readers", "startup")


def read(ctx, args):
    rec = startup._record(ctx)
    if rec is not None and "ledger_version" not in rec:
        return 0
    return startup.read(ctx, args)
