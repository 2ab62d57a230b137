"""Process environment for the benchmark's entry points, set before JAX is
imported: every cache stays inside the checkout, at fixed paths (the
compile cache's key includes its directory), and the compile cache never
evicts, so every run after a checkout's first finds all its programs (one
service cell's programs alone fill the 192 MB an environment may allow)."""

import os
import pathlib
import sys


def setup(root: pathlib.Path) -> None:
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(root / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    os.environ["SPIN_PLAN_CACHE"] = str(root / ".plan_cache" / "plans.json")
    sys.path[:0] = [str(root), str(root / "src")]
