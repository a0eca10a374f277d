"""Platform selection and compile-cache placement.

One rule, for every entry point that runs device code (cli,
__graft_entry__.py, chip_smoke.py, benchmark/): an explicit CPU request —
``-processor.backend cpu``, or ``JAX_PLATFORMS`` naming only ``cpu`` —
pins the CPU; otherwise the process requires a TPU and exits non-zero
with one line when JAX's default backend is anything else. There is no
probe and no fallback: a run that silently lands on the CPU reports CPU
numbers under a device's name. The check initialises the backend in THIS
process, so no child ever holds the chip its parent needs.
"""

from __future__ import annotations

import os

# <checkout>/.jax_cache: the path is part of the cache key, so it must
# never carry a temp name, a pid or a timestamp
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_COMPILE_CACHE = os.path.join(_REPO_ROOT, ".jax_cache")


def force_cpu() -> None:
    """Pin this process (and its children, via the env var) to the CPU
    backend. Tests run on the CPU."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")


def cpu_requested() -> bool:
    """True iff JAX_PLATFORMS names cpu as the only platform ("tpu,cpu"
    priority lists are NOT a CPU request)."""
    return [
        p.strip() for p in os.environ.get("JAX_PLATFORMS", "").split(",")
        if p.strip()
    ] == ["cpu"]


def select_platform(backend: str = "tpu") -> str:
    """Apply the platform rule and place the compile cache — the one
    call an entry point makes before its first compile. Returns the
    platform this process runs on ("cpu" or "tpu"); exits the process
    when a TPU is required and JAX's first device is not one."""
    if backend not in ("tpu", "cpu"):
        raise ValueError(f"backend must be tpu|cpu, got {backend!r}")
    if backend == "cpu" or cpu_requested():
        force_cpu()
        platform = "cpu"
    else:
        import jax

        try:
            platform = jax.devices()[0].platform
        except RuntimeError as e:  # backend init failed: no usable device
            lines = str(e).strip().splitlines()
            platform = (f"none "
                        f"({lines[-1] if lines else 'backend init failed'})")
        if platform != "tpu":
            raise SystemExit(
                f"flow_pipeline_tpu: a TPU is required but jax.devices()[0] "
                f"is {platform}; pass -processor.backend cpu or set "
                f"JAX_PLATFORMS=cpu to run on the CPU")
    configure_compile_cache()
    return platform


def configure_compile_cache() -> str:
    """Place JAX's persistent compilation cache (select_platform calls
    this); returns its directory. Where JAX_COMPILATION_CACHE_DIR is set
    JAX reads it itself and nothing is set in code; otherwise the cache
    lives at the fixed in-checkout path."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_COMPILE_CACHE)
    return DEFAULT_COMPILE_CACHE
