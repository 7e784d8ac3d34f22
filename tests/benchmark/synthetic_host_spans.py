"""A hand-made trace of two jobs with the program's `tmog.` spans in it,
every number of the `host_span` reader worked out on paper.

One chip, times in microseconds from the trace's start. Device ops (all
in jit_fit(1)): A [200, 400), B [600, 700), C [1400, 1900).

  thread A  bench.job [100, 1000)
              tmog.validate:CV [110, 990)
                validate_phase:fold_assign  [120, 180)   idle 60
                validate_phase:device_place [180, 250)   A covers 50: idle 20
                sweep_fit:glm               [260, 500)   A covers 140: idle 100
                  sweep_round:glm_round[8]  [270, 490)
                    host_step:round_prep    [270, 280)
                    host_step:round_fetch   [300, 490)
                sweep_eval:glm_eval         [510, 900)   B covers 100: idle 290
                  host_step:metric_fetch    [520, 710)  and  [800, 850)
                validate_phase:winner       [950, 980)   idle 30
            the device's gap [400, 600) straddles sweep_fit (100 of it),
            the 10 between the phases, and sweep_eval (90).
            job: wall 900, busy 300, idle 600; under the phases 500;
            under none 100 = [100,120) + [500,510) + [900,950) + [980,1000)
            ([250, 260) is under no phase either, but A is running).
            bench.job [1100, 2000)
              tmog.validate:CV [1110, 1990)
                validate_phase:fold_assign  [1120, 1320) idle 200
                sweep_fit:glm               [1350, 1950) C covers 500: idle 100
                  host_step:round_fetch     [1360, 1940)
            job: wall 900, busy 500, idle 400; under the phases 300; none 100
  thread B  tmog.stage:elsewhere [0, 2100): another thread's span covers
            nothing of these jobs.

Means over the two jobs, in microseconds: fold_assign 130, device_place 10,
sweep_fit 100, sweep_eval 145, winner 15 (sum 400); uncovered 100; host gap
500 = 400 + 100; fetches (3 + 1) / 2 = 2.
"""
from synthetic_trace import _events, _meta


def text_proto() -> str:
    dev = {1: "jit_fit(1)", 2: "fusion.A", 3: "fusion.B", 4: "fusion.C"}
    host = {1: "bench.job", 2: "tmog.validate:CV",
            3: "tmog.validate_phase:fold_assign",
            4: "tmog.validate_phase:device_place", 5: "tmog.sweep_fit:glm",
            6: "tmog.sweep_round:glm_round[8]",
            7: "tmog.host_step:round_prep", 8: "tmog.host_step:round_fetch",
            9: "tmog.sweep_eval:glm_eval", 10: "tmog.host_step:metric_fetch",
            11: "tmog.validate_phase:winner", 12: "tmog.stage:elsewhere"}
    return f"""
planes {{
  id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Modules" timestamp_ns: 0
{_events([(1, 200, 400), (1, 600, 700), (1, 1400, 1900)])}
  }}
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 0
{_events([(2, 200, 400), (3, 600, 700), (4, 1400, 1900)])}
  }}
{_meta(dev)}
}}
planes {{
  id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 0
{_events([(1, 100, 1000), (2, 110, 990), (3, 120, 180), (4, 180, 250),
          (5, 260, 500), (6, 270, 490), (7, 270, 280), (8, 300, 490),
          (9, 510, 900), (10, 520, 710), (10, 800, 850), (11, 950, 980),
          (1, 1100, 2000), (2, 1110, 1990), (3, 1120, 1320),
          (5, 1350, 1950), (8, 1360, 1940)])}
  }}
  lines {{ id: 2 name: "python" timestamp_ns: 0
{_events([(12, 0, 2100)])}
  }}
{_meta(host)}
}}
"""
