"""JAX's persistent compilation cache for the entry points.

``use_compile_cache()`` is called from ``main()`` of ``launch/serve.py``,
``launch/train.py`` and ``chip_smoke.py``, never at import.  Where the environment
sets ``JAX_COMPILATION_CACHE_DIR``, JAX already reads it and nothing here
overrides it.  Otherwise the cache lives at a fixed path inside the
checkout: the directory is part of each entry's key, so a path that moves
between runs never hits.
"""

from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> tuple[str, bool]:
    """Turn the cache on; returns (directory in use, whether it came from the
    environment) and prints both."""
    from_env = bool(os.environ.get(ENV_VAR))
    if from_env:
        path = os.environ[ENV_VAR]
    else:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    print(f"compile cache: {path} ({'from ' + ENV_VAR if from_env else 'in-checkout default'})",
          flush=True)
    return path, from_env
