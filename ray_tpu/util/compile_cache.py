"""Where XLA's persistent compilation cache lives.

A cold GPT-2 serve warm-up is about a minute of compilation and a train step
ten seconds; every process that compiles for the chip pays it again unless
the executables are kept on disk. JAX keys an entry by the program, the
compiler and the cache directory itself, so the directory must not move
between runs: it is either where ``JAX_COMPILATION_CACHE_DIR`` says, or one
fixed path in the checkout — never a temporary name.
"""

from __future__ import annotations

import os
import sys

_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")


def enable_compile_cache() -> str:
    """Put the persistent compile cache in force; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here. Where it is not, the cache goes to ``.jax_cache`` in
    the checkout, exported through the environment so that worker processes
    spawned from this one use the same directory. Call before the first
    compile: JAX decides once per process whether the cache is in use.
    """
    path = os.environ.get(_ENV)
    if path:
        return path
    os.environ[_ENV] = _CHECKOUT_DIR
    jax = sys.modules.get("jax")
    if jax is not None:
        # Already imported: its config took the environment's value then.
        jax.config.update("jax_compilation_cache_dir", _CHECKOUT_DIR)
    return _CHECKOUT_DIR
