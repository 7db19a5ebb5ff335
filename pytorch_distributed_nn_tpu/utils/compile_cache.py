"""Where jax's persistent compilation cache lives — decided in one place.

jax reads ``JAX_COMPILATION_CACHE_DIR`` itself; where it is set, nothing
here (or anywhere else in the repo) names another directory. Where it is
not set, a process that holds an accelerator caches under one FIXED
directory inside the checkout: the directory is part of the cache's key,
so a path that moves with a sweep dir, a temp name, a pid or a timestamp
never hits twice. CPU processes (the test suite, the CPU tools) get no
cache unless the variable asks for one — they must not start filling the
checkout unasked.

Called once by every entry point whose process takes the device:
``cli`` (train / single / evaluator / serve), the sweep trial child and
``chip_smoke.py``.
"""

from __future__ import annotations

import os
from typing import Optional

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: ``<checkout>/.jax_cache`` (git-ignored)
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)
    ))),
    ".jax_cache",
)


def configure() -> Optional[str]:
    """Point jax's compile cache at its directory; returns that directory
    (None: no cache). Initializes the backend — call it only from a
    process that is about to use the device anyway."""
    outer = os.environ.get(ENV_VAR)
    if outer:
        return outer
    import jax

    if jax.default_backend() == "cpu":
        return None
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
