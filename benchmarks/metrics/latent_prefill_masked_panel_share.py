"""Panels of the latent prefill kernel that build and apply the causal mask
over all its panels that compute, in percent: sum of ``latent_panels_masked``
over sum of ``latent_panels`` (what the program puts on a
``serve/prefill_chunk`` span over a latent pool: one head's grid of one
layer's call, the panels the chunk's causal horizon crosses and the panels
some row sees some key of), on the program's spans in the window. It says how
often the kernel's cheap step, which masks nothing, engages: the rest of the
panels lie wholly under the horizon. None where no span carries the counts
(a program older than PR 46, or a pool that is not latent) and, like the
other shares of the program's spans (``step_ahead_share``), on a run with no
device in its trace."""

from benchmarks.harness import idle_readers
from benchmarks.harness import program_events as pe


def read(obs):
    if not idle_readers.on_a_device(obs):
        return None
    chunks = [e for e in idle_readers.windowed(obs)
              if e.name == pe.PREFILL_CHUNK
              and e.arg("latent_panels") is not None]
    panels = sum(e.arg("latent_panels") for e in chunks)
    if not panels:
        return None
    return 100.0 * sum(e.arg("latent_panels_masked") or 0
                       for e in chunks) / panels
