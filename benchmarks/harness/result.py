"""From a run's observations to the one JSON line the driver reads."""

import json
import time
from typing import Optional

from benchmarks.harness import cells, device, trace as tr

#: a reader slower than this is named on a line of the run
SLOW_READER_S = 1.0


def read_metrics(cell: cells.Cell, bench: dict, obs, traced: bool, say) -> dict:
    """``--trace 0``: the cell's end-to-end metrics; ``--trace 1``: its
    per-layer metrics. Each is read by ``metrics/<name>.py``'s ``read(obs)``;
    a reader that finds nothing to read returns None and the metric is left
    out of the line. Readers run after the window and inside the run's time
    limit, so one that took over a second is named with its seconds."""
    out = {}
    for entry in (cell.per_layer if traced else cell.end_to_end):
        module = cells.load_module(cell.root, bench, "metrics", entry["name"])
        if module is None:
            say(f"no metrics/{entry['name']}.py: metric left out")
            continue
        t0 = time.monotonic()
        value = module.read(obs)
        took = time.monotonic() - t0
        if took > SLOW_READER_S:
            say(f"metric {entry['name']}: read in {took:.1f} s")
        if value is None:
            say(f"metric {entry['name']}: nothing to read, left out")
            continue
        out[entry["name"]] = {"value": float(value), "unit": entry["unit"]}
    return out


def device_object(obs, traced: bool) -> dict:
    out = device.describe(obs.devices)
    out["memory_peak_bytes"] = device.memory_peak_bytes(obs.devices)
    if traced and obs.trace is not None:
        window = obs.trace.window
        out["busy_s"] = tr.busy_seconds(obs.trace, window)
        out["window_s"] = window[1] - window[0]
    return out


def breakdown(obs) -> Optional[dict]:
    if obs.trace is None or not obs.trace.devices:
        return None
    window = obs.trace.window
    return {"device_ops": [[k, v] for k, v in tr.top_ops(obs.trace, window)],
            "idle_gaps": [[k, v] for k, v in tr.longest_gaps(
                obs.trace, window, obs.trace.devices[0])]}


def result_line(cell, bench, obs, traced: bool, say) -> str:
    """The line, and before it what the run spent between its window's close
    and this line: that time counts against the run's limit like any other,
    and the readers' part of it grows with the run's events."""
    t0 = time.monotonic()
    line = {"correct": bool(obs.correct), "attempted": int(obs.attempted),
            "failed": int(obs.failed),
            "metrics": read_metrics(cell, bench, obs, traced, say),
            "device": device_object(obs, traced)}
    if traced:
        parts = breakdown(obs)
        if parts is not None:
            line["breakdown"] = parts
    now = time.monotonic()
    say(f"from the window's close to the result line {now - obs.window[1]:.1f}"
        f" s, of which reading the metrics {now - t0:.1f} s")
    return json.dumps(line)
