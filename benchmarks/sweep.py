"""Find the highest rate an open-loop cell sustains, once, on the chip:

    python benchmarks/sweep.py --workload <cell> --rates 2,2.8,4,5.6 --seconds 30 --step-ms 50

One server, one set-up; each rate of the grid gets the cell's ramp, a window
and a drain. A rate is *sustained* when, over the whole window: every request
due got every token it asked for, the server refused nothing, the degradation
ladder made no transition, the queue at the window's close was no longer than
at its opening, and the generator's lateness p99 stayed under one engine
step. R* is the highest sustained rate of the grid; the cell's offered rate
(``cells/<cell>.json``, ``rate_rps``) is a stated share of it. Prints one
line per rate (with the run's ``latencies (ms)`` candidates, so that a rate
given several times shows how each statistic spreads from seed to seed) and a
last line with R*; it is a tool for the PR that defines or re-centres a cell,
not part of a measured run.
"""

import argparse
import json
import pathlib
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="benchmarks/sweep.py")
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True)
    p.add_argument("--seconds", type=float, default=30.0)
    p.add_argument("--seed", type=int, default=1,
                   help="the i-th rate of the list is offered with seed + i, so "
                        "a rate given several times shows its run-to-run spread")
    p.add_argument("--step-ms", type=float, required=True,
                   help="one engine step, the limit on the generator's "
                        "lateness: the cell's engine_step_p50_ms from a traced "
                        "run (PERF.md section 6; 49.8 in the chat cell, PR 23)")
    args = p.parse_args(argv)

    sys.path.insert(0, str(ROOT))
    from benchmarks.harness import cells, device, readers, run_serve
    bench = cells.load_benchmark(ROOT)
    cell = cells.find_cell(bench, args.workload, ROOT)
    devices = device.require_chips(cell.chips, "benchmarks/sweep.py")
    say = device.Say(devices)
    device.enable_compile_cache(ROOT)
    server, logits_ok, _ = run_serve.prepare(cell, bench, args.seed, False, say)
    best = None
    try:
        for i, rate in enumerate(float(x) for x in args.rates.split(",")):
            schedule = run_serve.make_schedule(cell, args.seconds,
                                               args.seed + i, rate_rps=rate)
            obs = run_serve.measure(cell, server, schedule, args.seconds,
                                    False, say)
            done = [r for r in obs.requests if r.measured and r.ok]
            ttft = np.array([r.stamps[0] - r.due for r in done]) * 1e3
            tpot = np.array([(r.stamps[-1] - r.stamps[0]) / (len(r.stamps) - 1)
                             for r in done if len(r.stamps) > 1]) * 1e3
            late = np.array([r.sent - r.due for r in obs.requests
                             if r.measured and r.sent is not None]) * 1e3
            c = obs.counters
            sustained = bool(
                obs.failed == 0 and c["requests_shed"] == 0
                and c["requests_rejected"] == 0 and c["ladder_transitions"] == 0
                and c["queue_depth_at_close"] <= c["queue_depth_at_open"]
                and np.percentile(late, 99) < args.step_ms)
            say("sweep " + json.dumps({
                "rate_rps": rate, "sustained": sustained,
                "attempted": obs.attempted, "failed": obs.failed,
                "ttft_ms_p50_p90_max": [float(np.percentile(ttft, 50)),
                                        float(np.percentile(ttft, 90)),
                                        float(ttft.max())] if len(ttft) else None,
                "tpot_ms_p50_p90": [float(np.percentile(tpot, 50)),
                                    float(np.percentile(tpot, 90))] if len(tpot) else None,
                "late_ms_p99": float(np.percentile(late, 99)),
                "ladder_transitions": c["ladder_transitions"],
                "shed": c["requests_shed"], "refused": c["requests_rejected"],
                "refusals": sorted({r.refused for r in obs.requests
                                    if r.refused}),
                "queue_open_close": [c["queue_depth_at_open"],
                                     c["queue_depth_at_close"]],
                "compiles": c["compiles_in_window"],
                "drain_s": c["run_end"] - obs.window[1],
                "latencies": readers.latency_summary(obs)}))
            if sustained:
                best = rate if best is None else max(best, rate)
            time.sleep(2.0)
    finally:
        server.stop(drain_timeout=10.0)
    say("sweep result " + json.dumps({"r_star_rps": best,
                                      "reference_check": logits_ok}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
