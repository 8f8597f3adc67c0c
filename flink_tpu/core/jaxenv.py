"""Process-level JAX setup for the entry points (``bench.py``,
``chip_smoke.py``, the cluster worker): where compiled programs are
cached, and a device check that refuses JAX's silent fallback to the CPU.

Nothing here runs at import; each entry point calls what it needs before
its first JAX computation.
"""

from __future__ import annotations

import os

# fixed and inside the checkout: the cache's path is part of its key, so a
# directory that moves between runs never hits. Listed in .gitignore.
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))),
    ".jax_cache",
)


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, where set, is the directory: JAX reads
    it itself and no other is set here. Otherwise the cache lives at
    :data:`CACHE_DIR`. CPU-forced children and tests turn the cache off
    explicitly (``jax_enable_compilation_cache=False``) instead of calling
    this: executables deserialized from it on the CPU backend have
    produced wrong results under donation (tests/conftest.py)."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def refuse_cpu_fallback() -> None:
    """With no ``JAX_PLATFORMS`` named, restrict JAX to the TPU, so that a
    chip that fails to initialize raises instead of leaving the process on
    the CPU. For processes that exist to drive a chip (cluster and DCN
    workers); call before JAX initializes a backend, and so before
    ``jax.distributed.initialize``. A CPU run names ``JAX_PLATFORMS=cpu``."""
    if not os.environ.get("JAX_PLATFORMS"):
        import jax

        jax.config.update("jax_platforms", "tpu")


def require_accelerator() -> list:
    """The process's devices, or ``RuntimeError`` when JAX came up on the
    CPU. JAX falls back to the CPU quietly when the accelerator fails to
    initialize, so a run meant for the chip checks before it measures."""
    import jax

    devices = jax.devices()
    if devices[0].platform == "cpu":
        raise RuntimeError(
            "no accelerator: JAX initialized on the CPU "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}); a "
            "chip run refuses to fall back"
        )
    return devices


def device_stamp() -> dict:
    """``{"platform", "kind", "count"}`` as JAX reports the devices."""
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}
