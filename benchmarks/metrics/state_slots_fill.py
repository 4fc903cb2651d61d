"""State slots that sequences hold over the slots there are, a tick at a
time, mean over the window's ticks, in percent: ``state_slots_held`` of the
engine's per-tick counters over the configuration's
``serve.engine.max_tracked_sequences``. A slot is held from admission to the
reap after a sequence's last token was read, which with a step in flight is a
tick after it was sampled: under callers that refill at once, what is short
of 100 is that tick and the admission's own. None where the program counts no
state slots."""

from benchmarks.harness import readers


def read(obs):
    slots = obs.cell.config.get("serve", {}).get("engine", {}).get(
        "max_tracked_sequences")
    held = [t[2]["state_slots_held"] for t in readers.steady_ticks(obs)
            if "state_slots_held" in t[2]]
    if not slots or not held:
        return None
    return 100.0 * sum(held) / len(held) / slots
