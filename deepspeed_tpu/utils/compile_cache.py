"""Where the persistent XLA compile cache lives — for the repo's entry
scripts (``chip_smoke.py``, ``bench.py``, ``__graft_entry__.py``, the
``tools/`` scripts meant for the chip), which call
:func:`configure_compile_cache` before their first compile.

The rule: if ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it
and nothing is configured in code. Otherwise the cache goes to ONE fixed
directory inside the checkout (``<repo>/.jax_cache``, git-ignored) — the
directory is part of the cache key, so a ``tempfile``, pid or timestamp
path would never hit. The library entry points (``initialize``,
``init_serving``, ``init_inference``) do not touch cache configuration:
where a user's job caches is the user's decision.

What the cache is keyed by is set in either case: JAX leaves an op's
metadata (its ``jax.named_scope`` path, its source line) out of the key by
default, so a cache filled by another version of the program hands back
executables whose ops bear THAT version's names. A profile then shows
scopes this program does not have and misses the ones it has (seen on the
chip: a checkout without the ``ds.*`` scopes loaded executables that had
them; PERF.md, PR 24). With the metadata in the key a hit is the same
program, names included; the price is a cold compile after an edit that
only moves lines in a traced file.
"""

import os

import jax

__all__ = ["CACHE_DIR_ENV", "DEFAULT_CACHE_DIR", "configure_compile_cache"]

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def configure_compile_cache() -> str:
    """Place the compile cache per the rule above; returns the directory
    in force (for the caller to print)."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    from_env = os.environ.get(CACHE_DIR_ENV)
    if from_env:
        return from_env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
