"""Guarded ingest: the validation gate in front of `(Sigma, c)`.

The streaming state's statistics are additive and *irreversible*: once
a chunk folds into the running `(Sigma, c)` means there is no inverse
update that removes it (the decayed/windowed variants only forget
slowly). A single NaN row therefore poisons every future refit, and a
fat-fingered 1e12 feature swamps the covariance for as long as the
decay horizon. `IngestGuard` rejects such chunks *before* the fold:

* **non-finite** — any NaN/Inf in X or y quarantines the chunk;
* **magnitude** — an optional absolute ceiling on max|x| (off by
  default: scale is workload-specific);
* **outlier** — a relative gate: once `warmup_chunks` chunks have been
  accepted, a chunk whose RMS exceeds `outlier_factor` x the
  exponential moving average RMS of accepted traffic is quarantined.
  The reference scale only learns from *accepted* chunks, so a burst
  of garbage cannot drag the gate open.

Overhead model (DESIGN.md §15): the health probe is ONE fused jitted
reduction over the chunk — O(m·n·p) element reads pulled to the host
as three scalars — in front of a fold that does O(m·n·p²) MACs; the
relative cost is ~1/p and `benchmarks/check_regression.py` gates the
guarded path at <2% of unguarded ingest. The probe does force a device
sync per chunk (the admission *decision* is a host branch), which is
the honest price of refusing to fold a chunk you have not looked at.

Rejected chunks land in a bounded quarantine ledger (newest
`ledger_capacity` records; older ones drop with a counter, never
unbounded growth) and are counted per reason under
`stream.quarantine{reason}`.
"""
from __future__ import annotations

from collections import deque
from functools import lru_cache
from typing import Deque, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.stream.state import ingest_stats, sufficient_stats
from repro.substrate import all_gather_tasks, chunk_specs, shard_map


class QuarantineRecord(NamedTuple):
    seq: int                 # ingest sequence number of the rejected chunk
    reason: str              # "nonfinite" | "magnitude" | "outlier"
    shape: Tuple[int, ...]   # (m, n, p) of the offending chunk
    stat: float              # the statistic that tripped the gate
    threshold: float         # the bound it violated


def _chunk_health(X: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    """[all_finite, rms, max_abs] as a (3,) f32 — two reductions over
    the raw chunk, cheap enough to ride inside the fold's own dispatch.

    NaN and Inf both propagate through `max(|.|)`, so the single
    `isfinite(max_abs)` scalar covers the whole finiteness check with
    no extra pass. A non-finite chunk's rms may itself be NaN; every
    consumer checks the finite flag (or compares NaN-safely) first.
    """
    rms = jnp.sqrt(jnp.mean(jnp.square(X.astype(jnp.float32))))
    max_abs = jnp.maximum(jnp.max(jnp.abs(X)),
                          jnp.max(jnp.abs(y))).astype(jnp.float32)
    finite = jnp.isfinite(max_abs)
    return jnp.stack([finite.astype(jnp.float32), rms, max_abs])


_batch_health = jax.jit(_chunk_health)


@lru_cache(maxsize=8)
def mesh_health(mesh, data_axis: str = "data", task_axis: str = "task"):
    """`_chunk_health` of a chunk laid out on `mesh` by
    `substrate.feed_chunk`, as one compiled (X, y) -> (3,) program:
    each device reduces its own block to [sum of x^2, max(|x|, |y|)] and
    ONE all-gather of those pairs gives every device the whole chunk's
    health. The chunk never leaves the devices it was fed to."""
    axes = (data_axis, task_axis)

    def local(X, y):
        part = jnp.stack([
            jnp.sum(jnp.square(X.astype(jnp.float32))),
            jnp.maximum(jnp.max(jnp.abs(X)),
                        jnp.max(jnp.abs(y))).astype(jnp.float32)])
        return all_gather_tasks(part[None], axes)

    parts_of = shard_map(local, mesh=mesh,
                         in_specs=chunk_specs(data_axis, task_axis),
                         out_specs=P())

    def health(X, y):
        parts = parts_of(X, y)
        rms = jnp.sqrt(jnp.sum(parts[:, 0]) / X.size)
        max_abs = jnp.max(parts[:, 1])
        return jnp.stack([jnp.isfinite(max_abs).astype(jnp.float32), rms,
                          max_abs])

    return jax.jit(health)


@jax.jit
def _guarded_fold(state, X: jnp.ndarray, y: jnp.ndarray, decay):
    """Speculative fold + health derived from the fold's OWN chunk
    statistics, one dispatch, O(m·p) probe cost.

    The host classifies the pulled health and simply keeps the old
    state object when the chunk is rejected — the folded (possibly
    poisoned) state is discarded unassigned, so rejection is bitwise
    exact by construction (no select pass; a device-side mask of the
    running mean would re-round it anyway).

    The health costs next to nothing because it reads the chunk
    statistics the fold computes regardless, never the raw chunk (an
    explicit O(m·n·p) reduction over X measured 8-20% of the fold on
    CPU — XLA's scalar reduce loop against Eigen's threaded matmul):

    * `diag(Sigma_b)[t, j] = mean_i X[t,i,j]^2` — every element of X
      appears squared in its own diagonal entry, so one NaN/Inf
      anywhere makes `sum(diag)` non-finite, and
      `sqrt(mean(diag)) == rms(X)` exactly;
    * `c_b = X^T y / n` catches the y side: a non-finite y[t, i]
      reaches every c_b[t, :] entry it touches (IEEE `0 * Inf = NaN`,
      so even an all-zero X row cannot launder it).

    max|x| is NOT derivable from the fold's statistics, so the fused
    path carries no absolute-magnitude verdict (health[2] = NaN); a
    guard configured with `max_abs=` routes through the standalone
    `admit` probe instead and pays its separate dispatch.
    """
    n = X.shape[1]
    Sigma_b, c_b = sufficient_stats(X, y)
    count_b = jnp.full(state.counts.shape, n, state.counts.dtype)
    folded = ingest_stats(state, Sigma_b, c_b, count_b, decay)
    diag = jnp.diagonal(Sigma_b, axis1=1, axis2=2)
    ss, cs_ss = jnp.sum(diag), jnp.sum(jnp.square(c_b))
    finite = jnp.isfinite(ss) & jnp.isfinite(cs_ss)
    rms = jnp.sqrt(jnp.mean(diag))
    health = jnp.stack([finite.astype(jnp.float32),
                        rms.astype(jnp.float32),
                        jnp.full((), jnp.nan, jnp.float32)])
    return folded, health


class IngestGuard:
    """Admission gate for streaming minibatches.

    `admit(X, y)` returns `(ok, reason)`; on `ok=False` the caller must
    not fold the chunk (the service path simply skips `ingest`, leaving
    `(Sigma, c)` bitwise untouched). The guard is host-side state — it
    is not part of the checkpointed pytree; a restarted service starts
    with a fresh (warming-up) reference scale.
    """

    def __init__(self, *, max_abs: Optional[float] = None,
                 outlier_factor: Optional[float] = 10.0,
                 warmup_chunks: int = 5,
                 ema_decay: float = 0.99,
                 ledger_capacity: int = 256):
        if outlier_factor is not None and outlier_factor <= 1.0:
            raise ValueError(f"outlier_factor must be > 1 (or None to "
                             f"disable), got {outlier_factor}")
        if not 0.0 < ema_decay < 1.0:
            raise ValueError(f"ema_decay must be in (0, 1), got {ema_decay}")
        self.max_abs = max_abs
        self.outlier_factor = outlier_factor
        self.warmup_chunks = int(warmup_chunks)
        self.ema_decay = float(ema_decay)
        self.ledger: Deque[QuarantineRecord] = deque(maxlen=ledger_capacity)
        self.dropped_records = 0     # quarantines evicted past capacity
        self.total_quarantined = 0
        self.accepted = 0
        self._seq = 0
        self._ema_rms: Optional[float] = None

    # -- admission --------------------------------------------------------

    def limits(self) -> Tuple[float, float]:
        """Current (rms_limit, abs_limit) for the device-side verdict,
        +inf where a gate is disabled or still warming up. Rounded to
        f32 so the fused fold's comparison and `record`'s host
        classification see the same thresholds."""
        abs_limit = self.max_abs if self.max_abs is not None \
            else float("inf")
        if (self.outlier_factor is not None and self._ema_rms is not None
                and self.accepted >= self.warmup_chunks):
            rms_limit = float(np.float32(self.outlier_factor
                                         * self._ema_rms))
        else:
            rms_limit = float("inf")
        return rms_limit, float(np.float32(abs_limit))

    def admit(self, X_batch, y_batch) -> Tuple[bool, Optional[str]]:
        """Decide one chunk standalone (its own probe dispatch; the
        dense service path fuses the probe into the fold and calls
        `record` with the health directly). Returns (True, None) or
        (False, reason)."""
        health = np.asarray(_batch_health(X_batch, y_batch))
        return self.record(health, tuple(int(s) for s in X_batch.shape))

    def record(self, health, shape) -> Tuple[bool, Optional[str]]:
        """Classify one chunk's `[finite, rms, max_abs]` probe result:
        ledger + counters on reject, EMA reference update on accept."""
        self._seq += 1
        rms_limit, abs_limit = self.limits()
        finite = bool(health[0])
        rms, max_abs = float(health[1]), float(health[2])
        if not finite:
            self._quarantine("nonfinite", shape, max_abs, float("inf"))
            return False, "nonfinite"
        if max_abs > abs_limit:
            self._quarantine("magnitude", shape, max_abs, abs_limit)
            return False, "magnitude"
        if not rms <= rms_limit:     # NaN-safe: an unreadable rms rejects
            self._quarantine("outlier", shape, rms, rms_limit)
            return False, "outlier"
        self.accepted += 1
        if np.isfinite(rms):     # an overflowed (inf) rms must never
            if self._ema_rms is None:   # poison the reference scale
                self._ema_rms = rms
            else:
                d = self.ema_decay
                self._ema_rms = d * self._ema_rms + (1.0 - d) * rms
        return True, None

    def _quarantine(self, reason: str, shape, stat: float,
                    threshold: float) -> None:
        if len(self.ledger) == self.ledger.maxlen:
            self.dropped_records += 1
            obs.inc("stream.quarantine_dropped")
        self.ledger.append(QuarantineRecord(self._seq, reason, shape,
                                            stat, threshold))
        self.total_quarantined += 1
        obs.inc("stream.quarantine", reason=reason)

    # -- introspection ----------------------------------------------------

    @property
    def reference_rms(self) -> Optional[float]:
        """EMA RMS of accepted traffic (None until the first accept)."""
        return self._ema_rms

    def summary(self) -> dict:
        by_reason: dict = {}
        for rec in self.ledger:
            by_reason[rec.reason] = by_reason.get(rec.reason, 0) + 1
        return {"accepted": self.accepted,
                "quarantined": self.total_quarantined,
                "ledger": len(self.ledger),
                "dropped_records": self.dropped_records,
                "by_reason_in_ledger": by_reason,
                "reference_rms": self._ema_rms}
