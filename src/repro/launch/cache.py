"""Where the entry points keep JAX's persistent compilation cache."""
from __future__ import annotations

import os
from pathlib import Path

import jax

# Fixed and inside the checkout, so a later run finds what an earlier one
# compiled (never a temporary name, a pid or a time).
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> None:
    """Cache compiled programs under ``CACHE_DIR``, unless
    ``JAX_COMPILATION_CACHE_DIR`` is set: JAX then reads it itself."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    # Key on the HLO metadata too.  Without it a program that differs only
    # in its op_name scopes (models/layers.scoped) reads an executable
    # compiled without them, and the device trace loses its layer names.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
