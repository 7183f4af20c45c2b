"""Substrate: version-portable distributed/compat layer.

Single import point for everything that historically broke across jax
releases (shard_map location and kwargs, ambient-mesh context, mesh
construction) plus the host-device-count and subprocess-probe plumbing
shared by tests and benchmarks.
"""
from repro.substrate.collectives import (
    all_gather_tasks, all_to_all_experts, psum_stats,
)
from repro.substrate.compat import make_mesh, shard_map, use_mesh
from repro.substrate.feed import chunk_specs, feed_chunk, feed_shards
from repro.substrate.hostenv import (
    enable_compile_cache, force_host_device_count, host_device_env,
)
from repro.substrate.mesh import data_model_mesh, data_task_mesh, task_mesh
from repro.substrate.probes import REPO_ROOT, popen_probe, run_probe

__all__ = [
    "all_gather_tasks", "all_to_all_experts", "psum_stats",
    "make_mesh", "shard_map", "use_mesh",
    "chunk_specs", "feed_chunk", "feed_shards",
    "enable_compile_cache", "force_host_device_count", "host_device_env",
    "data_model_mesh", "data_task_mesh", "task_mesh",
    "REPO_ROOT", "popen_probe", "run_probe",
]
