"""Small shared utilities: compile cache, tree accounting, formatting."""

from __future__ import annotations

import os
from typing import Any

import jax
import numpy as np

# repository root: src/repro/common/util.py -> three levels up from src/
REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
)


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, names the directory and is left
    alone (JAX reads it itself).  Otherwise the cache lives at one fixed path
    inside the checkout, ``.jax_cache/`` — the path is part of the cache key,
    so a directory that moved between runs would never hit.  Entry points
    call this at the start of ``main``; importing a module never does."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def bytes_of_tree(tree: Any) -> int:
    """Total nbytes across all array leaves of a pytree."""
    leaves = jax.tree_util.tree_leaves(tree)
    total = 0
    for leaf in leaves:
        if hasattr(leaf, "nbytes"):
            total += int(leaf.nbytes)
        elif hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            total += int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
    return total


def param_count(tree: Any) -> int:
    leaves = jax.tree_util.tree_leaves(tree)
    return sum(int(np.prod(l.shape)) for l in leaves if hasattr(l, "shape"))


def human_bytes(n: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB", "TiB", "PiB"):
        if abs(n) < 1024.0:
            return f"{n:.2f} {unit}"
        n /= 1024.0
    return f"{n:.2f} EiB"


def human_flops(n: float) -> str:
    for unit in ("FLOP", "KFLOP", "MFLOP", "GFLOP", "TFLOP", "PFLOP"):
        if abs(n) < 1000.0:
            return f"{n:.2f} {unit}"
        n /= 1000.0
    return f"{n:.2f} EFLOP"
