"""The streaming service task-sharded over a data=1 x task=4 mesh, on four
CPU devices in a subprocess, against the same service on one device and
against the benchmark's plain reference (`chipbench/reference.py`).

One probe runs the whole sequence (construction, guarded folds, refits,
a rollback, a quarantined chunk, publishes, served predicts) at m=8,
p=32, n=16 and prints what the tests below check.
"""
import json
import re

import pytest

from repro.substrate import run_probe

_PROBE = r"""
import json
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from chipbench import check, reference
from repro import obs
from repro.stream import ServingFront, StreamingDsmlService, service
from repro.substrate import data_task_mesh

M, PP, N, CHUNKS = 8, 32, 16, 6
CFG = {"m": M, "p": PP, "chunk_n": N, "service": {"penalty_rows": 2 * N},
       "reference": {"power_iters": 64, "lasso_iters": 1000,
                     "debias_iters": 600, "task_block": 4}}
lam, mu, Lam = check.penalties(CFG)
key = jax.random.PRNGKey(5)
chol = reference.ar_cholesky(PP, 0.5)
B, _ = reference.coefficients(key, m=M, p=PP, s=3, low=0.0, high=1.0)
# host copies that own their memory, as a client's chunks would
pool = [tuple(np.array(a, copy=True) for a in reference.chunk(
    jax.random.fold_in(key, k), chol, B, m=M, n=N, noise=1.0))
    for k in range(CHUNKS)]
rows = np.asarray(reference.design_rows(jax.random.fold_in(key, 99), chol,
                                        rows=16), np.float32)
mesh = data_task_mesh(n_task=4, n_data=1)
TASK = ("Sigmas", "cs", "counts", "beta_local", "Ms", "beta_u", "beta_tilde")


def make(mesh_):
    return StreamingDsmlService(
        M, PP, lam=lam, mu=mu, Lam=Lam, refit_every=2 * N,
        max_refit_interval=2 * N, lasso_iters=400, debias_iters=600,
        warm_lasso_iters=100, warm_debias_iters=150, refit_tol=1e-5,
        chunk_n=N, mesh=mesh_)


def layout(st):
    # every per-task field split over the four devices, M / 4 tasks
    # each; support and generation replicated
    bad = []
    for f in TASK:
        a = getattr(st, f)
        ok = (isinstance(a.sharding, NamedSharding)
              and a.sharding.spec[:1] == P("task")
              and len(a.sharding.device_set) == 4
              and all(s.data.shape[0] == M // 4 for s in a.addressable_shards))
        if not ok:
            bad.append(f)
    for f in ("support", "generation"):
        a = getattr(st, f)
        if not (len(a.sharding.device_set) == 4
                and a.sharding.is_fully_replicated):
            bad.append(f)
    return bad


def whole_chunks_on_one_device():
    # a live array of a chunk's shape that is not split over the mesh
    return sum(1 for a in jax.live_arrays()
               if a.shape in ((M, N, PP), (M, N))
               and any(s.data.shape[0] != M // 4 for s in a.addressable_shards))


seen = {"whole_chunk": 0, "folds": 0, "probes": 0}
orig_fold, orig_probe = service.ingest_sharded, service.mesh_health


def fold(state, X, y, *args, **kw):
    seen["whole_chunk"] += whole_chunks_on_one_device()
    seen["folds"] += 1
    return orig_fold(state, X, y, *args, **kw)


def probe(*args, **kw):
    run = orig_probe(*args, **kw)

    def checked(X, y):
        seen["whole_chunk"] += whole_chunks_on_one_device()
        seen["probes"] += 1
        return run(X, y)
    return checked


def no_host_probe(*a, **k):
    raise AssertionError("the whole chunk was probed on one device")


service.ingest_sharded, service.mesh_health = fold, probe
service.IngestGuard.admit = no_host_probe

one, four = make(None), make(mesh)
out = {"layout": {"init": layout(four.state)}, "steps": [], "folds": []}
obs.reset()
published = {}
for k, (X, y) in enumerate(pool):
    i1, i4 = one.ingest(X, y), four.ingest(X, y)
    out["folds"].append(max(
        float(np.max(np.abs(np.asarray(four.state.Sigmas)
                            - np.asarray(one.state.Sigmas)))),
        float(np.max(np.abs(np.asarray(four.state.cs)
                            - np.asarray(one.state.cs))))))
    out["layout"][f"fold{k}"] = layout(four.state)
    if i4 is not None:
        s1, s4 = one.state, four.state
        out["steps"].append({
            "generation": [int(s1.generation), int(s4.generation)],
            "support_equal": bool(np.array_equal(np.asarray(s1.support),
                                                 np.asarray(s4.support))),
            "gap": {f: float(np.max(np.abs(np.asarray(getattr(s1, f))
                                           - np.asarray(getattr(s4, f)))))
                    for f in ("beta_local", "Ms", "beta_u", "beta_tilde")},
            "shard_debias": np.asarray(i4.shard_debias_iters).tolist(),
            "debias_run": int(i4.debias_iters_run)})
        out["layout"][f"refit{k}"] = layout(four.state)
        snap = four.serving()
        published[snap.generation] = np.asarray(snap.beta_tilde)
        out["layout"][f"publish{k}"] = layout(four.state._replace(
            beta_tilde=snap.beta_tilde, support=snap.support))

hists = {h["name"]: h["count"]
         for h in obs.get_registry().snapshot()["histograms"]}
out["spans"] = {n: hists.get(n, 0) for n in (
    "stream.ingest.ms", "stream.ingest.feed.ms", "stream.ingest.probe.ms",
    "stream.ingest.fold.ms", "stream.refit.shard_debias_iters",
    "stream.refit.ms")}

# served predicts: one gather of the (m, rows) scores per batch
front = ServingFront(four, max_batch=8, max_delay_ms=5.0).start()
futs = [front.submit(r) for r in rows]
res = [f.result(60) for f in futs]
front.stop()
out["scores_on_host"] = all(isinstance(r.scores, np.ndarray) for r in res)
state = four.state
seq = list(range(CHUNKS))
numbers = check.compare({
    "sequence": seq, "generation": int(state.generation),
    **{k: np.asarray(getattr(state, k)) for k in (
        "Sigmas", "cs", "beta_local", "Ms", "beta_u", "beta_tilde",
        "support")},
    "published": published,
    "served_rows": np.arange(len(rows)),
    "served_generations": np.array([r.generation for r in res]),
    "served_scores": np.stack([r.scores[:, 0] for r in res]),
    "responses": len(res)},
    (np.stack([X for X, _ in pool]), np.stack([y for _, y in pool]), rows),
    CFG)
out["check"] = numbers
out["verdict"] = bool(check.verdict(numbers, check.load_limits("tenants4.refit")))

# a rejected candidate keeps the state and its layout
before = four.state
four.refit_kkt_ceiling = -1.0
info = four.refit()
out["rollback"] = {"kept": four.state is before,
                   "generation": int(info.generation),
                   "layout": layout(four.state)}
four.refit_kkt_ceiling = 1.0

# a restored checkpoint comes back in the mesh's layout
import os, tempfile
path = os.path.join(tempfile.mkdtemp(), "state")
four.save(path)
four.load(path)
out["restored"] = {"layout": layout(four.state),
                   "Sigmas_equal": bool(np.array_equal(
                       np.asarray(before.Sigmas), np.asarray(four.state.Sigmas)))}

# a poisoned chunk is probed on the mesh, quarantined, never folded
S0, c0 = np.asarray(four.state.Sigmas), np.asarray(four.state.cs)
folds0 = seen["folds"]
Xbad = pool[0][0].copy()
Xbad[3, 2, 7] = np.nan
out["quarantine"] = {
    "returned": four.ingest(Xbad, pool[0][1]) is None,
    "folded": seen["folds"] - folds0,
    "Sigmas_equal": bool(np.array_equal(S0, np.asarray(four.state.Sigmas))),
    "cs_equal": bool(np.array_equal(c0, np.asarray(four.state.cs))),
    "quarantined": four.guard.total_quarantined}
out["seen"] = seen
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def probe():
    res = run_probe(_PROBE, n_devices=4, timeout=900)
    assert res.returncode == 0, res.stderr[-3000:]
    m = re.search(r"^RESULT (.*)$", res.stdout, re.M)
    assert m, res.stdout[-2000:]
    return json.loads(m.group(1))


def test_sharded_statistics_and_refits_match_one_device(probe):
    assert max(probe["folds"]) < 1e-5
    steps = probe["steps"]
    assert [s["generation"] for s in steps] == [[1, 1], [2, 2], [3, 3]]
    for s in steps:
        assert s["support_equal"], s
        # each shard's early exit looks at its own tasks only, so the
        # solves agree to the solver's tolerance, not bitwise
        assert max(s["gap"].values()) < 1e-3, s


def test_sharded_service_passes_the_reference_check(probe):
    assert probe["verdict"], probe["check"]
    assert probe["check"]["threshold_diff"] == 0
    assert probe["check"]["unknown_generation"] == 0


def test_state_stays_task_sharded(probe):
    layouts = probe["layout"]
    assert {"init", "fold0", "refit1", "publish1"} <= set(layouts)
    assert all(bad == [] for bad in layouts.values()), layouts
    assert probe["rollback"]["kept"] and probe["rollback"]["layout"] == []
    assert probe["rollback"]["generation"] == 3
    assert probe["restored"] == {"layout": [], "Sigmas_equal": True}


def test_quarantined_chunk_is_probed_on_the_mesh_and_never_folded(probe):
    q = probe["quarantine"]
    assert q["returned"] and q["folded"] == 0 and q["quarantined"] == 1
    assert q["Sigmas_equal"] and q["cs_equal"]
    seen = probe["seen"]
    # every chunk was probed and folded where it was fed: no device ever
    # held a whole chunk
    assert seen["probes"] == 7 and seen["folds"] == 6
    assert seen["whole_chunk"] == 0


def test_mesh_spans_and_per_shard_iterations_are_recorded(probe):
    # both services record into one registry: the one-device service's
    # six ingests and three refits, and the mesh's
    spans = probe["spans"]
    assert spans["stream.ingest.feed.ms"] == 6, spans
    assert spans["stream.ingest.probe.ms"] == 6, spans
    assert spans["stream.ingest.ms"] == spans["stream.ingest.fold.ms"] == 12
    assert spans["stream.refit.ms"] == 6
    assert spans["stream.refit.shard_debias_iters"] == 4 * 3
    for s in probe["steps"]:
        assert len(s["shard_debias"]) == 4
        assert max(s["shard_debias"]) == s["debias_run"]


def test_front_gathers_each_batch_to_the_host(probe):
    assert probe["scores_on_host"]
