"""One run of one cell of BENCHMARK.json, in a new process that holds the
chip:

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

It makes the cell's inputs from the seed, warms the cell's own shapes
(set-up), measures for --seconds, checks correctness outside the window
and prints, as the LAST line of stdout, one JSON object with exactly the
keys `correct`, `attempted`, `failed`, `metrics`, `device` (and
`breakdown` when traced). With --trace 0 the metrics are the cell's
end-to-end metrics; with --trace 1 its per-layer metrics, read from a
profiler trace of a few jobs. Sample counts, routes, winners and walls go
on the line before and into <out>/<workload>.report.json.

Without a TPU whose device_kind is in benchmark/peaks.json it exits
non-zero and prints no result. `--rehearse` is for the tests only: sizes
from the files' `rehearsal` blocks, on the CPU, labelled `cpu`; nothing it
prints is a device figure.

The cell is benchmark/workloads/<name>.json; its configuration
benchmark/configs/<config>.json; its traffic code benchmark/drivers/
<driver>.py; every per-layer metric a benchmark/layers/*.json naming its
reader under benchmark/readers/. Nothing about one cell, configuration or
metric lives in this file.
"""
from __future__ import annotations

import time

_T0 = time.time()   # process start, as near as Python lets us see it

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)


def per_layer(ctx, harness, trace_whole: bool) -> dict:
    """Every layer file that lists this cell, read by its reader. The
    device-trace metrics of a trace that lost device events are left out.
    On the chip a metric of the cell whose reader finds nothing fails the
    run: a kernel or module the layer file names has gone or was renamed,
    and a metric that vanishes quietly would hide it. (Under --rehearse
    the CPU has no such names, and the metric is left out.)"""
    out = {}
    for spec in harness.layer_files():
        if ctx.cell["name"] not in spec.get("cells", [ctx.cell["name"]]):
            continue
        if spec["source"] == "device_trace" and not trace_whole:
            continue
        reader = harness.load_module("readers", spec["reader"])
        value = reader.read(ctx, spec.get("args", {}))
        if value is not None:
            out[spec["name"]] = {"value": value, "unit": spec["unit"]}
        elif not ctx.rehearse:
            raise harness.BenchFailure(
                f"per-layer metric {spec['name']} of {ctx.cell['name']}: "
                f"its reader {spec['reader']} {spec.get('args', {})} found "
                f"nothing in this run")
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tests only: rehearsal sizes on the CPU")
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out",
                                                  "benchmark"),
                    help="directory for the report, the trace and the "
                         "saved model")
    args = ap.parse_args()

    from benchmark import harness
    from benchmark.harness import BenchFailure, log

    # no plan may depend on another run's harvest: a fresh, empty corpus
    corpus = tempfile.TemporaryDirectory(prefix="bench-plan-corpus-")
    os.environ["TMOG_PLAN_CORPUS_DIR"] = corpus.name
    try:
        cell = harness.load_json("workloads", args.workload + ".json")
        config = harness.load_json("configs", cell["config"] + ".json")
        driver = harness.load_module("drivers", cell["driver"])

        import jax
        import transmogrifai_tpu  # noqa: F401 — settles the compile cache
        from transmogrifai_tpu.utils.platform import compile_cache_dir

        dev = jax.devices()[0]
        if args.rehearse:
            if dev.platform != "cpu":
                raise BenchFailure("--rehearse is the CPU test mode")
            peaks = None
        else:
            if dev.platform != "tpu":
                raise BenchFailure(
                    f"JAX found no TPU (platform {dev.platform!r}); "
                    f"nothing was measured")
            peaks = harness.load_peaks(dev.device_kind)
            if len(jax.devices()) < cell["chips"]:
                raise BenchFailure(
                    f"the cell needs {cell['chips']} chips, JAX reports "
                    f"{len(jax.devices())}")
        out_dir = os.path.join(args.out, args.workload)
        os.makedirs(out_dir, exist_ok=True)
        sizes = dict(config["sizes"])
        if args.rehearse:
            sizes.update(config["rehearsal"])
        ctx = harness.Ctx(
            cell=cell, config=config, sizes=sizes, seed=args.seed,
            seconds=args.seconds, trace=bool(args.trace),
            rehearse=args.rehearse, out_dir=out_dir,
            compile_log=harness.CompileLog(), peaks=peaks)
        log(f"cell {cell['name']} on {dev.device_kind} x"
            f"{len(jax.devices())}, compile cache {compile_cache_dir()}")

        state = driver.setup(ctx)
        at_window = ctx.compile_log.snapshot()
        setup_s = time.time() - _T0
        log(f"set-up {setup_s:.1f}s, {at_window}")
        result = driver.run_window(ctx, state)
        after = ctx.compile_log.snapshot()
        window_wall_s = time.time() - _T0 - setup_s
        # the peak of set-up and window: the references' own arrays in
        # verify() are not the cell's
        device = harness.device_report(cell["chips"])
        driver.verify(ctx, state)

        ctx.counters["programs_compiled"] = at_window["true_compiles"]
        ctx.counters["window_compiles"] = \
            after["true_compiles"] - at_window["true_compiles"]
        ctx.require(ctx.counters["window_compiles"] == 0,
                    f"{ctx.counters['window_compiles']} programs compiled "
                    f"inside the window")
        if args.rehearse:
            device["rehearsal"] = True
        end_to_end = dict(result.end_to_end, setup_s=setup_s)
        units = cell["units"]
        line = {"correct": not ctx.problems, "attempted": result.attempted,
                "failed": result.failed}
        if ctx.trace:
            from benchmark.reduce_trace import Reduced
            ctx.reduced = red = Reduced.from_file(ctx.notes["xplane"])
            if not (red.on_device or args.rehearse):
                raise BenchFailure("the trace holds no device plane")
            lo, hi = red.window()
            device["busy_s"] = red.busy_ns() / 1e9
            device["window_s"] = (hi - lo) / 1e9
            if not device["busy_s"] > 0:
                raise BenchFailure("no operation ran on the device in the "
                                   "traced window")
            lost = red.lost_dispatches()
            if lost:
                log(f"the trace lost device events: dispatched after its "
                    f"last device op: {lost[:8]}")
                ctx.notes["trace_lost_dispatches"] = lost
            line["metrics"] = per_layer(ctx, harness, not lost)
            line["breakdown"] = red.breakdown()
            ctx.notes["device_idle_share_pct"] = \
                100.0 * (1.0 - device["busy_s"] / device["window_s"])
            ctx.notes["traced_end_to_end"] = end_to_end
        else:
            line["metrics"] = {k: {"value": v, "unit": units[k]}
                               for k, v in end_to_end.items()}
        line["device"] = device
        report = {
            "workload": cell["name"], "seed": args.seed,
            "seconds": args.seconds, "trace": args.trace,
            "samples": result.attempted - result.failed,
            "window_wall_s": window_wall_s,
            "compiles": {"at_window": at_window, "after_window": after,
                         "slowest": ctx.compile_log.slowest()},
            "problems": ctx.problems, "notes": ctx.notes,
            "counters": {k: v for k, v in ctx.counters.items()
                         if not isinstance(v, dict)},
            "result": line}
        with open(os.path.join(
                args.out, f"{args.workload}.seed{args.seed}."
                          f"trace{args.trace}.report.json"), "w") as f:
            json.dump(report, f, indent=1, default=str)
        report.pop("result")
        print(json.dumps(report, default=str), flush=True)
        print(json.dumps(line), flush=True)
        return 0
    except BenchFailure as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 1
    except Exception:  # a failed run prints no result line
        traceback.print_exc()
        return 1
    finally:
        corpus.cleanup()


if __name__ == "__main__":
    sys.exit(main())
