"""Process set-up shared by the entry points that run on a device
(``launch/serve.py``, ``launch/train.py``, ``chip_smoke.py``): JAX's
persistent compilation cache and the device they run on.

Nothing here runs at import: tests on the CPU import the launchers'
modules and must write nothing into the checkout.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Dict

import jax

# <checkout>/ (this file is src/repro/launch/runtime.py).
REPO_ROOT = Path(__file__).resolve().parents[3]
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    this sets no other directory. Otherwise the cache lives at the fixed
    ``<checkout>/.jax_cache`` (git-ignored): the path is part of what a
    later run must find again, so it never carries a temp name, pid or
    time. Programs that compile in under a second (the per-slice pack
    kernels) are cached too."""
    path = os.environ.get(CACHE_ENV)
    if not path:
        path = str(REPO_ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def device_summary() -> Dict[str, object]:
    """The device JAX runs on, as a result line names it."""
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_tpu(what: str) -> None:
    """Exit with a message naming the missing TPU unless JAX runs on one:
    a TPU path must not quietly carry on on the CPU."""
    platform = jax.devices()[0].platform
    if platform != "tpu":
        raise SystemExit(f"{what} needs a TPU, but JAX runs on "
                         f"{platform!r} (jax.devices()[0].platform)")
