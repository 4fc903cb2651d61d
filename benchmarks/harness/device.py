"""The chip: refusing anything else, naming it on every line, its memory
peak, and the one place the compile cache is put."""

import os
import sys


def require_chips(chips: int, who: str = "benchmark") -> list:
    """The first ``chips`` devices, when jax finds at least that many TPUs;
    otherwise one line on stderr and a nonzero exit, before any result is
    printed. The program's own refusal (``accelerator.require_tpu``) decides
    what a TPU is."""
    from deepspeed_tpu.accelerator import require_tpu
    devices = require_tpu(who)                 # SystemExit without a TPU
    if len(devices) < chips:
        raise SystemExit(f"{who}: the cell needs {chips} chip(s) and jax "
                         f"found {len(devices)}")
    return list(devices[:chips])


def describe(devices: list) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_peak_bytes(devices: list) -> int:
    """Allocator peak on the fullest chip. It counts live arrays; XLA's
    per-program temporaries are not in it on this backend (PERF.md)."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def enable_compile_cache(root) -> str:
    """JAX's persistent compilation cache: the directory given in
    ``JAX_COMPILATION_CACHE_DIR`` when set, otherwise the fixed
    ``.jax_cache/`` inside the checkout (the path is part of the cache's key,
    so it never moves). Every program is kept, however quick to compile, and
    a size limit found in the environment is lifted, so that a cell's second
    run in a checkout compiles nothing: the chip machines set
    ``JAX_COMPILATION_CACHE_MAX_SIZE`` to 192 MiB, a served cell's 95
    programs take 3.3 GB, and least-recently-used eviction under a cyclic
    access pattern then hits nothing at all (0 hits / 95 misses in every one
    of 18 runs, 235 s of compiling each; PERF.md, PR 23). Returns the words
    for the run's first line: the directory, and the limit that was lifted,
    so that whoever runs the check sees the disk it will take."""
    import jax
    given = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    path = given or os.path.join(str(root), ".jax_cache")
    if not given:
        jax.config.update("jax_compilation_cache_dir", path)
    limit = int(jax.config.jax_compilation_cache_max_size)
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    if limit >= 0:
        return (f"{path}, its size limit of {limit} bytes lifted (a served "
                f"cell keeps about 3.3 GB of programs there)")
    return f"{path}, no size limit"


class CompileClock:
    """Seconds jax spent in backend compiles (on a persistent-cache hit: in
    reading and loading the cached program) and the cache's hits and misses,
    from jax's own monitoring. Printed at the end of set-up, so that a run
    that found its programs in the cache can be told from one that compiled."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += seconds

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def __str__(self):
        return (f"{self.seconds:.1f} s compiling or loading programs, "
                f"persistent cache {self.hits} hits / {self.misses} misses")


class Say:
    """Progress lines on stdout, each naming the device, so that no number
    can be read without knowing what it ran on."""

    def __init__(self, devices: list):
        d = describe(devices)
        self.prefix = (f"[platform={d['platform']} device_kind={d['kind']!r} "
                       f"count={d['count']}]")

    def __call__(self, text: str) -> None:
        print(f"{self.prefix} {text}", flush=True)
        sys.stdout.flush()


def device_seed(seed: int) -> int:
    """``--seed`` may pass 2**31; jax's PRNGKey takes a 32-bit value."""
    import numpy as np
    return int(np.random.SeedSequence(int(seed)).generate_state(1)[0]
               & 0x7FFFFFFF)
