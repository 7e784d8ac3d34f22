"""From the profiler's .xplane.pb to numbers: the one reduction every PR
shares.

Reads the file with jax.profiler.ProfileData and nothing else. What it
takes from the trace (see PERF.md, "What a chip trace holds"):

- device planes `/device:TPU:<n>`: the line `XLA Ops` holds one event per
  executed HLO op, named by its whole HLO line (while loops and their
  bodies nest; a Pallas kernel is a `custom-call` named after its jitted
  wrapper), the line `XLA Modules` one event per executed program,
  `jit_<fn>(<fingerprint>)`. An op belongs to the module event that
  contains it in time (its `hlo_module` stat where the backend writes one,
  as the CPU backend does). `Async XLA Ops` (copy-start..done) is not read.
- host plane `/host:CPU`: one line per thread (several are called
  `python`), TraceMe events nested by time. The benchmark's own spans are
  the events named `bench.*`.
- the device side is finite: past a few million op events the profiler
  records no more of the device while the host lines go on
  (`lost_dispatches`); device times from such a trace are withheld.
- the two clocks: on the v5e a device event is stamped about 1 ms EARLIER
  than the host event that dispatched it (recorded_v5e.xplane.pb: dispatch
  at 42.838 ms, its program at 41.851 ms). Nothing is corrected for it:
  against jobs of seconds it is 0.02 %, and it can move an op to the
  neighbouring span only in a trace of millisecond jobs.

Busy time is the UNION of op intervals on a chip, so nesting and overlap
never count twice; device time of a group of ops is the union of that
group's intervals. With several chips, times are averaged over the chips
that ran anything. A CPU rehearsal has no device plane: the host events
that carry an `hlo_module` stat stand in, and nothing read from them is a
device figure.

    python benchmark/reduce_trace.py <file.xplane.pb>   # describe a trace
"""
from __future__ import annotations

import bisect
import dataclasses
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
MAX_ATTRIBUTED_GAPS = 2000
SCAN_BACK = 512
LOST_TAIL_SHARE = 0.02   # of the window, with no device op at its end


@dataclasses.dataclass(frozen=True)
class Op:
    start: float      # ns
    end: float
    name: str         # the HLO instruction's own name: `_hist_pallas_jit.11`
    module: str       # the program, without its fingerprint: `jit_fit_gbt_folds`
    chip: int
    text: str         # the event's name as the trace has it (the HLO line)


def short_op(text: str) -> str:
    """`%fusion.7 = f32[8]{0} fusion(...)` -> `fusion.7`: on the TPU an op
    event is named by its whole HLO line."""
    return text.split(" = ", 1)[0].lstrip("%")


def short_module(text: str) -> str:
    """`jit_fit_gbt_folds(17539221240569292468)` -> `jit_fit_gbt_folds`."""
    return re.sub(r"\(\d+\)$", "", text)


@dataclasses.dataclass(frozen=True)
class Span:
    start: float
    end: float
    name: str
    line: str         # host thread


def union_ns(intervals) -> float:
    """Total length of the union of (start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def clip(intervals, lo, hi):
    for s, e in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            yield s, e


def _innermost(index, t):
    """The latest-starting event of a start-sorted list that contains t:
    with events nested by time, the innermost. Looks back a bounded
    number of events."""
    starts, evs = index
    i = bisect.bisect_right(starts, t) - 1
    for j in range(i, max(i - SCAN_BACK, -1), -1):
        if evs[j][1] > t:
            return evs[j]
    return None


def _stat(event, key):
    for k, v in event.stats:
        if k == key:
            return v
    return None


class Reduced:
    """A trace, reduced to device ops, benchmark spans and host events."""

    def __init__(self, ops, spans, host, on_device: bool):
        self.ops = sorted(ops, key=lambda o: (o.chip, o.start, -o.end))
        self.spans = sorted(spans, key=lambda s: s.start)
        self.host = host            # line name -> [(start, end, name)]
        self.on_device = on_device  # False: a CPU rehearsal's stand-in
        self.chips = sorted({o.chip for o in self.ops})
        # start-sorted (starts, events) pairs for _innermost
        spans_ix = [(s.start, s.end, s.name, s.line) for s in self.spans]
        self._span_index = ([e[0] for e in spans_ix], spans_ix)
        self._host_index = {}
        for line, evs in host.items():
            evs = [e for e in evs if not e[2].startswith(SPAN_PREFIX)]
            self._host_index[line] = ([e[0] for e in evs], evs)

    # -- loading ------------------------------------------------------------
    @classmethod
    def from_file(cls, path: str) -> "Reduced":
        from jax.profiler import ProfileData
        return cls.from_profile(ProfileData.from_file(path))

    @classmethod
    def from_profile(cls, data) -> "Reduced":
        ops, spans, host = [], [], {}
        planes = list(data.planes)
        device = [(int(DEVICE_PLANE.match(p.name).group(1)), p)
                  for p in planes if DEVICE_PLANE.match(p.name)]
        for chip, plane in device:
            lines = {ln.name: ln for ln in plane.lines}
            mods = sorted((e.start_ns, e.end_ns, e.name) for e in
                          (lines[MODULES_LINE].events
                           if MODULES_LINE in lines else ()))
            starts = [m[0] for m in mods]
            for e in (lines[OPS_LINE].events if OPS_LINE in lines else ()):
                module = _stat(e, "hlo_module")
                if module is None:
                    i = bisect.bisect_right(starts, e.start_ns) - 1
                    module = mods[i][2] if i >= 0 and \
                        e.start_ns < mods[i][1] else "?"
                ops.append(Op(e.start_ns, e.end_ns, short_op(e.name),
                              short_module(str(module)), chip, e.name))
        for plane in planes:
            if plane.name != HOST_PLANE:
                continue
            for i, ln in enumerate(plane.lines):
                evs, line = [], f"{ln.name}#{i}"   # thread names repeat
                for e in ln.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append(Span(e.start_ns, e.end_ns, e.name,
                                          line))
                    module = None if device else _stat(e, "hlo_module")
                    if module is not None:
                        ops.append(Op(e.start_ns, e.end_ns, short_op(e.name),
                                      short_module(str(module)), 0, e.name))
                    elif e.duration_ns > 0:
                        evs.append((e.start_ns, e.end_ns, e.name))
                if evs:
                    host[line] = sorted(evs)
        return cls(ops, spans, host, on_device=bool(device))

    # -- windows --------------------------------------------------------------
    def jobs(self, name: str) -> list:
        """The benchmark spans called `name`, in time order."""
        return [s for s in self.spans if s.name == name]

    def window(self) -> tuple:
        """The traced window: first benchmark span's start to the last
        one's end (the profiler's own start-up and shutdown are outside)."""
        if not self.spans:
            if not self.ops:
                return (0.0, 0.0)
            return (min(o.start for o in self.ops),
                    max(o.end for o in self.ops))
        return (min(s.start for s in self.spans),
                max(s.end for s in self.spans))

    # -- device time -------------------------------------------------------------
    def select(self, module: str | None = None, op: str | None = None):
        """Ops whose module name and HLO line match the regular
        expressions (`op` sees the whole line: name, shapes, opcode)."""
        mre = re.compile(module) if module else None
        ore = re.compile(op) if op else None
        return [o for o in self.ops
                if (mre is None or mre.search(o.module))
                and (ore is None or ore.search(o.text))]

    def device_ns(self, ops, lo=None, hi=None) -> float:
        """Union of the ops' intervals inside [lo, hi], averaged over the
        chips that ran anything in the trace."""
        if not self.chips:
            return 0.0
        if lo is None:
            lo, hi = self.window()
        total = 0.0
        for chip in self.chips:
            total += union_ns(clip(((o.start, o.end) for o in ops
                                    if o.chip == chip), lo, hi))
        return total / len(self.chips)

    def busy_ns(self, lo=None, hi=None) -> float:
        return self.device_ns(self.ops, lo, hi)

    def per_job_s(self, ops, job_span: str) -> float | None:
        """Mean device seconds of `ops` inside one `job_span` span."""
        jobs = self.jobs(job_span)
        if not jobs:
            return None
        return sum(self.device_ns(ops, j.start, j.end)
                   for j in jobs) / len(jobs) / 1e9

    def lost_dispatches(self, slack_ns: float = 5e6) -> list:
        """Programs the host dispatched inside the window AFTER the last
        device op the trace holds, where that op ends well before the
        window does: the profiler stopped recording the device (its buffer
        holds a few million events), so every device time read from this
        trace would be short. Empty for a whole trace."""
        lo, hi = self.window()
        last = max((o.end for o in self.ops if o.start < hi), default=lo)
        if hi - last <= LOST_TAIL_SHARE * (hi - lo):
            return []       # a whole trace ends with its last job's ops
        lost = []
        for evs in self.host.values():
            for s, _, name in evs:
                if last + slack_ns < s < hi and \
                        name.startswith("PjitFunction("):
                    lost.append(name[len("PjitFunction("):-1])
        return sorted(set(lost))

    # -- the breakdown ---------------------------------------------------------------
    def top_ops(self, k: int = 10) -> list:
        """Device ops by SELF time (an op's interval less what its nested
        ops cover) inside the window, on the first chip; one entry per
        `module:op`."""
        if not self.chips:
            return []
        lo, hi = self.window()
        chip = self.chips[0]
        totals, stack = {}, []

        def close(upto):
            while stack and stack[-1][1] <= upto:
                s, e, key, child = stack.pop()
                totals[key] = totals.get(key, 0.0) + (e - s) - child
                if stack:
                    stack[-1][3] += e - s
        for o in self.ops:
            if o.chip != chip:
                continue
            s, e = max(o.start, lo), min(o.end, hi)
            if e <= s:
                continue
            close(s)
            stack.append([s, e, f"{o.module}:{o.name}", 0.0])
        close(float("inf"))
        top = sorted(totals.items(), key=lambda kv: -kv[1])[:k]
        return [[name[:120], ns / 1e9] for name, ns in top]

    def idle_gaps(self, k: int = 10) -> list:
        """Idle time of the first chip inside the window, summed by what
        the host was doing in each gap (cut at span edges): the benchmark
        span the piece's middle falls in, and the innermost host event
        under it on that thread."""
        if not self.chips:
            return []
        lo, hi = self.window()
        chip = self.chips[0]
        busy = sorted(clip(((o.start, o.end) for o in self.ops
                            if o.chip == chip), lo, hi))
        gaps, at = [], lo
        for s, e in busy:
            if s > at:
                gaps.append((at, s))
            at = max(at, e)
        if hi > at:
            gaps.append((at, hi))
        totals = {}
        # host attribution for the longest gaps; the many short ones
        # between back-to-back ops are summed under one name
        gaps.sort(key=lambda g: g[0] - g[1])
        edges = sorted({t for sp in self.spans for t in (sp.start, sp.end)})
        for s, e in gaps[:MAX_ATTRIBUTED_GAPS]:
            # a gap that crosses a span's edge is cut there, so that the
            # tail of one job and the head of the next are named apart
            i = bisect.bisect_right(edges, s)
            while s < e:
                cut = edges[i] if i < len(edges) and edges[i] < e else e
                name = self._host_doing((s + cut) / 2)
                totals[name] = totals.get(name, 0.0) + (cut - s)
                s, i = cut, i + 1
        rest = sum(e - s for s, e in gaps[MAX_ATTRIBUTED_GAPS:])
        if rest:
            totals["shorter_gaps"] = rest
        top = sorted(totals.items(), key=lambda kv: -kv[1])[:k]
        return [[name[:120], ns / 1e9] for name, ns in top]

    def _host_doing(self, t) -> str:
        span = _innermost(self._span_index, t)
        if span is None:
            return "outside_spans"
        inner = _innermost(self._host_index[span[3]], t)
        return span[2] + (">" + inner[2] if inner else "")

    def breakdown(self) -> dict:
        return {"device_ops": self.top_ops(10),
                "idle_gaps": self.idle_gaps(10)}


def describe(path: str, k: int = 12) -> None:
    """What a trace holds, for a reader who has not seen one: planes,
    lines, event counts, and the first events of each line with their
    stats."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    for plane in data.planes:
        print(f"PLANE {plane.name!r} stats={list(plane.stats)[:8]}")
        for ln in plane.lines:
            evs = list(ln.events)
            print(f"  LINE {ln.name!r} events={len(evs)}")
            for e in evs[:k]:
                print(f"    {e.name[:90]!r} start={e.start_ns:.0f} "
                      f"dur={e.duration_ns:.0f} stats={list(e.stats)[:8]}")
    red = Reduced.from_file(path)
    lo, hi = red.window()
    print(f"window {(hi - lo) / 1e9:.6f}s busy {red.busy_ns() / 1e9:.6f}s "
          f"chips {red.chips} on_device={red.on_device} "
          f"spans {len(red.spans)} ops {len(red.ops)}")
    mods = {}
    for o in red.ops:
        mods.setdefault(o.module, []).append(o)
    for m, ops in sorted(mods.items(),
                         key=lambda kv: -red.device_ns(kv[1]))[:30]:
        print(f"  module {m[:80]!r}: {red.device_ns(ops) / 1e9:.6f}s "
              f"{len(ops)} ops")
    print("top ops:", red.top_ops(20))
    print("idle gaps:", red.idle_gaps(20))


if __name__ == "__main__":
    describe(sys.argv[1])
