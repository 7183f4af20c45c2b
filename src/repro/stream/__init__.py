"""Streaming DSML: online sufficient-statistics estimation and serving.

The paper's statistics `(Sigma, c)` are additive over samples, so the
whole DSML pipeline runs online: minibatches fold into a fixed-size
`StreamState` (optionally decayed / windowed / SPMD-reduced over a
data x task mesh), and `refit` re-runs Algorithm 1 from the state with
warm starts. `StreamingDsmlService` is the serving driver. DESIGN.md §9.
"""
from repro.stream.accumulate import (
    accumulate_stats_fn, ingest_sharded,
)
from repro.stream.guard import IngestGuard, QuarantineRecord
from repro.stream.health import RefitHealth, refit_health
from repro.stream.refit import (
    RefitInfo, jaccard_support, refit, refit_logistic,
)
from repro.stream.serve import (
    ModelGeneration, ServeResult, ServingFront, bucket_rows,
)
from repro.stream.service import StreamingDsmlService
from repro.stream.state import (
    StreamState, WindowState, ingest, ingest_stats, init_stream_state,
    init_window, merge, window_ingest, window_stats,
)

__all__ = [
    "accumulate_stats_fn", "ingest_sharded",
    "IngestGuard", "QuarantineRecord",
    "RefitHealth", "refit_health",
    "RefitInfo", "jaccard_support", "refit", "refit_logistic",
    "ModelGeneration", "ServeResult", "ServingFront", "bucket_rows",
    "StreamingDsmlService",
    "StreamState", "WindowState", "ingest", "ingest_stats",
    "init_stream_state", "init_window", "merge", "window_ingest",
    "window_stats",
]
