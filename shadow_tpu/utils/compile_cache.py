"""Where JAX's persistent compilation cache lives.

The entry points that run simulations (the CLI, bench.py,
chip_smoke.py, __graft_entry__.py) call `enable_compile_cache()` once,
before their first compile.  `JAX_COMPILATION_CACHE_DIR`, when set,
wins (JAX reads it itself); otherwise the cache goes to a fixed,
gitignored directory inside the checkout.  The path is part of the
cache's key, so it is never built from a temp name, pid or time.

A persistent cache turns off carry donation
(`experimental.tpu_donate_buffers`, ops/span_mesh.py): a donated
executable loaded back from the cache corrupts the heap.
"""

from __future__ import annotations

import os

CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one directory
    and return that path."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        CHECKOUT_CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    return path
