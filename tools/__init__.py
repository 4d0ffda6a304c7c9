"""Repo tooling (benches, profilers, analyzers) — run as ``python -m tools.<x>``."""

import os
import shutil

_BENCH_OUT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "bench_out"
)


def empty_cache_dir(name: str) -> str:
    """``bench_out/<name>``, emptied: the compile-cache directory of a tool
    whose first leg must start cold.  A fixed name (never a temp dir): the
    tool's processes find each other's entries through
    ``JAX_COMPILATION_CACHE_DIR``, and nothing is left outside the
    git-ignored ``bench_out/``."""
    path = os.path.join(_BENCH_OUT, name)
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path)
    return path
