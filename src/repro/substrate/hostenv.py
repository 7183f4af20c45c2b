"""Process environment set-up: host-platform device counts (CPU SPMD
testing) and the persistent compilation cache.

The `--xla_force_host_platform_device_count=N` flag must reach XLA
before the backend initializes; previously every test/benchmark probe
re-spelled the `os.environ["XLA_FLAGS"]` incantation by hand. The
helpers here centralize it, both for the current process (call before
the first device query) and for subprocess environments.

`enable_compile_cache` is the one place the program points JAX's
persistent compilation cache at a directory; each entry point
(`chip_smoke.py`, `examples/*.py`, `benchmarks/run.py`) calls it once.

This module deliberately does not import jax at module scope.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Mapping, MutableMapping

_FLAG = "--xla_force_host_platform_device_count"


def _merge_xla_flags(existing: str, n: int) -> str:
    flags = [f for f in existing.split() if not f.startswith(_FLAG + "=")]
    flags.append(f"{_FLAG}={n}")
    return " ".join(flags)


def force_host_device_count(n: int, env: MutableMapping[str, str] | None = None) -> None:
    """Set XLA_FLAGS so the host platform exposes `n` devices.

    With `env=None` this mutates `os.environ` for the current process;
    it must run before jax initializes a backend (raises if too late and
    the count would change).
    """
    target = os.environ if env is None else env
    target["XLA_FLAGS"] = _merge_xla_flags(target.get("XLA_FLAGS", ""), n)
    if env is None:
        # too-late detection; jax has no public "is the backend up"
        # probe, so this reads the private one directly
        from jax._src import xla_bridge
        if xla_bridge.backends_are_initialized():
            import jax
            if jax.device_count() != n:
                raise RuntimeError(
                    f"jax backend already initialized with "
                    f"{jax.device_count()} devices; "
                    f"force_host_device_count({n}) must run first")


def host_device_env(n: int, extra_pythonpath: str | None = None,
                    base: Mapping[str, str] | None = None) -> dict:
    """Environment dict for a subprocess that needs `n` host devices.

    Merges XLA_FLAGS into a copy of `base` (default: os.environ), pins
    the child to the CPU platform (`JAX_PLATFORMS=cpu`: a child on a
    machine with an accelerator must not reach for the chip its parent
    holds) and optionally prepends `extra_pythonpath` to PYTHONPATH.
    """
    env = dict(os.environ if base is None else base)
    force_host_device_count(n, env)
    env["JAX_PLATFORMS"] = "cpu"
    if extra_pythonpath:
        prev = env.get("PYTHONPATH", "")
        env["PYTHONPATH"] = extra_pythonpath + (os.pathsep + prev if prev else "")
    return env


_REPO_ROOT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a fixed directory and
    return it. Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it
    itself and this sets nothing; otherwise the cache lives at
    `<repo>/.cache/jax` (the path is part of the cache key, so it never
    depends on a temporary name, a process id or the time)."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    import jax
    path = str(_REPO_ROOT / ".cache" / "jax")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
