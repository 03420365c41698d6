"""Where JAX's persistent compilation cache lives.

A BERT-Large step program costs the TPU compiler a minute or more, and a
machine that runs one command and is thrown away pays it on every start
unless the executables outlive the process. Every entry point that compiles
(run_pretraining, run_finetune / run_distill, run_server, the chip_smoke
children) calls `enable_compile_cache()` before its first compile.

The directory is decided OUTSIDE the program where possible:

- `JAX_COMPILATION_CACHE_DIR` set: JAX reads the variable itself; no code
  here sets another directory over it.
- otherwise: `<checkout>/.jax_cache` (git-ignored). A fixed path, never a
  temporary name, pid or time — the path takes part in how entries are
  found, so a directory that moves never hits.

Turning the cache off is JAX's own switch (`JAX_ENABLE_COMPILATION_CACHE=0`):
the SIGKILL drills use it (a killed writer can tear an entry) and so does the
test suite (hermetic runs).
"""

from __future__ import annotations

import os

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")
ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache() -> str:
    """Point JAX at the persistent cache directory (see module docstring)
    and return it. Call before the first compile."""
    inherited = os.environ.get(ENV_VAR)
    if inherited:
        return inherited
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
