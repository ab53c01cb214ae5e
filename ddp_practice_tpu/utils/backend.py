"""Which device the program runs on, and where its compiled code is kept.

Every gate that chooses between a compiled Pallas kernel and its
interpret-mode / reference twin, turns buffer donation on, or works
around an XLA:CPU runtime quirk asks `on_tpu()` — one spelling, so a
compile rehearsal (tests/test_tpu_compile.py) steers all of them by
patching one function, and a device that is neither answer cannot take
half of each branch.
"""

from __future__ import annotations

import os
from typing import Optional

import jax

from ddp_practice_tpu.utils.logging import get_logger

# fixed, inside the checkout, git-ignored: the directory is part of the
# cache key, so a path made from a temp name, a pid or the time never hits
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))),
    ".jax_compile_cache",
)


def on_tpu() -> bool:
    """True when jitted programs execute compiled on TPU hardware."""
    return jax.default_backend() == "tpu"


def enable_compile_cache(setting: str = "auto") -> Optional[str]:
    """Turn on XLA's persistent compilation cache; returns the directory
    in use, or None when it is off or could not be made.

    Called first by every entry point (cli train/serve, serve/worker.py,
    generate.py, chip_smoke.py). Where
    `JAX_COMPILATION_CACHE_DIR` is set JAX already points there and this
    sets no other directory; otherwise the cache lives in
    `COMPILE_CACHE_DIR`. "off" is for tests that count compiles.
    Idempotent."""
    if setting == "off":
        return None
    if setting != "auto":
        raise ValueError(
            f"compilation_cache={setting!r}: want 'auto' or 'off' — place "
            "the cache from outside with JAX_COMPILATION_CACHE_DIR"
        )
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    try:
        os.makedirs(COMPILE_CACHE_DIR, exist_ok=True)
    except OSError as e:  # read-only checkout: run uncached, and say so
        get_logger().warning("compilation cache disabled: %s", e)
        return None
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR
