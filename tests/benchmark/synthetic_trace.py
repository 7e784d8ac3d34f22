"""A hand-made trace whose every number can be worked out on paper.

One chip, times in microseconds from the trace's start:

  XLA Modules   jit_alpha(1) [100, 400)        jit_beta(2) [600, 900)
  XLA Ops       while.1 [100, 400)             sort.5      [600, 700)
                  fusion.2 [120, 220)          _hist_pallas_jit.7 [750, 850)
                  custom-call.3 [250, 350)     (outside any span:)
                                               fusion.9 [1500, 1600) in jit_alpha(1) [1500, 1600)
  host thread A bench.job [50, 500)            bench.job [550, 1000)
                  Dispatch(alpha) [60, 90)       HostPrep [560, 590)
                                                 Dispatch(beta) [700, 745)
  host thread B (no benchmark span) Dispatcher::Loop [0, 2000)

The two custom calls are named as the TPU names an op, by its HLO line:
`%custom-call.3 = ... custom-call(...)` and `%_hist_pallas_jit.7 = ...`.
"""

US = 1_000_000  # picoseconds in a microsecond


def _events(rows):
    return "\n".join(
        f"    events {{ metadata_id: {m} offset_ps: {s * US} "
        f"duration_ps: {(e - s) * US} }}" for m, s, e in rows)


def _meta(names):
    return "\n".join(
        f'  event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
        for i, n in names.items())


def text_proto() -> str:
    dev_names = {1: "jit_alpha(1)", 2: "jit_beta(2)", 3: "while.1",
                 4: "fusion.2",
                 5: "%custom-call.3 = f32[8]{0} custom-call(s8[64,128]{1,0} %x)",
                 6: "sort.5",
                 7: "%_hist_pallas_jit.7 = f32[8]{0} custom-call(f32[8]{0} %y)",
                 8: "fusion.9"}
    host_names = {1: "bench.job", 2: "Dispatch(alpha)", 3: "HostPrep",
                  4: "Dispatch(beta)", 5: "Dispatcher::Loop"}
    return f"""
planes {{
  id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Modules" timestamp_ns: 0
{_events([(1, 100, 400), (2, 600, 900), (1, 1500, 1600)])}
  }}
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 0
{_events([(3, 100, 400), (4, 120, 220), (5, 250, 350), (6, 600, 700),
          (7, 750, 850), (8, 1500, 1600)])}
  }}
{_meta(dev_names)}
}}
planes {{
  id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 0
{_events([(1, 50, 500), (2, 60, 90), (1, 550, 1000), (3, 560, 590),
          (4, 700, 745)])}
  }}
  lines {{ id: 2 name: "python" timestamp_ns: 0
{_events([(5, 0, 2000)])}
  }}
{_meta(host_names)}
}}
"""
