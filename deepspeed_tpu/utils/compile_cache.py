"""Where compiled programs are kept between runs — the one rule every entry
point (``chip_smoke.py``, the bench scripts, ``dstpu_serve``, the fleet
worker, the test session) goes through.

Compiling the full-width train step and the serving bucket ladder takes
minutes on a cold start, and JAX keys its persistent cache on the directory's
path: a directory that moves (a temp name, a pid, a time) never hits. So:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and this module sets
  nothing — a directory given from outside is never overridden or cleared;
- unset: one fixed directory beside the package, ``.jax_cache/`` at the root of
  the checkout (git-ignored).
"""

import os

import jax

CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Apply the rule above; returns the directory in use."""
    given = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if given:
        return given
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
