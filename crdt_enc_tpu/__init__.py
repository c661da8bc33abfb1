"""crdt-enc-tpu: a TPU-native encrypted-CRDT persistence/replication framework.

Capability surface of chpio/crdt-enc (see SURVEY.md), rebuilt JAX-first:
immutable content-addressed op/state files on a passively synced filesystem,
LUKS-style layered key management, and bulk merge/compaction running as
batched tensor folds on TPU.

The primary surface re-exports lazily (PEP 562) so ``import crdt_enc_tpu``
stays light — jax loads only when the accelerator or kernels are touched::

    from crdt_enc_tpu import Core, OpenOptions, orset_adapter
"""

import importlib

__version__ = "0.1.0"

# name -> submodule that defines it (resolved on first attribute access)
_LAZY = {
    "Core": "core",
    "CoreError": "core",
    "OpenOptions": "core",
    "empty_adapter": "core",
    "gcounter_adapter": "core",
    "lwwmap_adapter": "core",
    "mvreg_adapter": "core",
    "orset_adapter": "core",
    "pncounter_adapter": "core",
    "TpuAccelerator": "parallel",
    "canonical_bytes": "models",
}

__all__ = ["__version__", "enable_compilation_cache", *sorted(_LAZY)]


def enable_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache for the fold kernels.

    First compilation of a fold shape costs tens of seconds on TPU; a
    compaction process that exits afterwards pays it again next run.  With
    the cache enabled, recompiles of previously-seen shapes load from disk
    in milliseconds — call this once at process start (before the first
    fold) in any deployment that runs compactions as short-lived jobs.

    The directory is placed from OUTSIDE the program: when
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX's own setting stands and no
    directory is set in code.  Otherwise the cache lives at the fixed
    ``.jax_cache`` beside the package (the checkout root) — the path is
    part of the cache key, so it never varies by process, time or user.
    Returns the cache directory in use.
    """
    import os

    import jax
    from jax.experimental.compilation_cache import compilation_cache

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            ".jax_cache",
        )
        jax.config.update("jax_compilation_cache_dir", path)
    # jax initializes the cache module lazily at the FIRST compile and
    # then latches: enabling a dir after any compile has happened would
    # silently do nothing.  Reset so the dir takes effect now.
    compilation_cache.reset_cache()
    return path


def __getattr__(name):
    mod = _LAZY.get(name)
    if mod is None:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    return getattr(importlib.import_module(f".{mod}", __name__), name)


def __dir__():
    return sorted(list(globals()) + list(_LAZY))
