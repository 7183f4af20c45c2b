"""Optional bridge to `jax.profiler`.

Kept out of `repro.obs.__init__` so the telemetry core never imports
jax (zero-dependency contract, DESIGN.md §14). Import this module
explicitly when you want XLA-level traces alongside the obs timeline:

    from repro.obs import jaxprof
    jaxprof.annotate_spans()
    with jaxprof.profiler_trace("/tmp/jax-trace"):
        run_workload()

`annotate_spans()` puts every `obs.span` into whatever profiler trace is
active (the streaming service turns it on when it is imported), so the
program's spans share the trace's clock with the device's ops.
"""
from __future__ import annotations

import contextlib


def annotate_spans() -> None:
    """Open a `jax.profiler.TraceAnnotation` named after each `obs.span`
    while a profiler trace is active. With no trace active, a span pays
    one call of the hook and one `TraceMe.is_enabled` check. Idempotent."""
    from jax.profiler import TraceAnnotation

    from repro.obs import registry as _registry

    is_enabled = TraceAnnotation.is_enabled

    def hook(name: str):
        return TraceAnnotation(name) if is_enabled() else None

    _registry.set_annotation_hook(hook)


@contextlib.contextmanager
def profiler_trace(log_dir: str, **kwargs):
    """Wrap a block in `jax.profiler.trace(log_dir)`; degrades to a
    no-op (with a registry counter marking the skip) when jax is not
    importable, so callers never need their own try/except."""
    from repro.obs import registry as _registry
    try:
        import jax
    except Exception:
        _registry.inc("obs.jaxprof.unavailable")
        yield
        return
    _registry.inc("obs.jaxprof.trace")
    with jax.profiler.trace(log_dir, **kwargs):
        yield
