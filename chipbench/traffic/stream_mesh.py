"""The stream driver (`stream.py`: closed-loop chunks, the refit cadence,
open-loop predicts, the window closing at the first publish after
`--seconds`) with the service task-sharded over the configuration's
`mesh`, {"data": d, "task": t}, of d x t devices.

The configuration's (m, p, p) stacks fit the chips only as task shards,
so the state has to be born on the mesh (`repro.stream.state
.state_shardings`). A program without that cannot hold the deployment:
the driver stops before set-up, with a non-zero exit, rather than build
the whole state on one chip.

A process with fewer devices than the mesh runs the same traffic on the
one-device service and says so. That happens only off the chip (the
benchmark's CPU tests of every cell): on the chip the harness refuses
to run a cell without the chips it names.
"""
from __future__ import annotations

import numpy as np

from chipbench.traffic import stream


class StreamRun(stream.StreamRun):

    def mesh(self):
        """The configuration's mesh, or None with fewer devices than it
        needs."""
        import jax

        from repro.stream import state
        from repro.substrate import data_task_mesh
        shape = self.cfg["mesh"]
        have = len(jax.devices())
        if have < shape["data"] * shape["task"]:
            self.log(f"{have} device(s) for a data={shape['data']} x "
                     f"task={shape['task']} mesh: running the one-device "
                     f"service")
            return None
        if not hasattr(state, "state_shardings"):
            raise SystemExit("bench: this program builds the stream state on "
                             "one device; the configuration needs it born "
                             "task-sharded over the mesh")
        return data_task_mesh(n_task=shape["task"], n_data=shape["data"])

    def setup(self):
        import jax
        import jax.numpy as jnp

        from chipbench.check import penalties
        from repro.stream import ServingFront, StreamingDsmlService
        from repro.stream.serve import bucket_rows
        s, tp = self.cfg["service"], self.tp
        mesh = self.mesh()
        self.make_pool()
        lam, mu, Lam = penalties(self.cfg)
        self.svc = StreamingDsmlService(
            self.m, self.p, lam=lam, mu=mu, Lam=Lam, decay=s["decay"],
            refit_every=tp["refit_every_rows"],
            max_refit_interval=tp["max_refit_interval_rows"],
            lasso_iters=s["lasso_iters"], debias_iters=s["debias_iters"],
            warm_lasso_iters=s["warm_lasso_iters"],
            warm_debias_iters=s["warm_debias_iters"],
            refit_tol=s["refit_tol"], chunk_n=self.n, guard=s["guard"],
            mesh=mesh)
        refit = self.svc.refit

        def annotated_refit():
            # puts the refit, called from ingest, on the trace's host
            # timeline
            with jax.profiler.TraceAnnotation("bench.refit"):
                return refit()
        self.svc.refit = annotated_refit
        f = self.cfg["front"]
        self.front = ServingFront(self.svc, max_batch=f["max_batch"],
                                  max_delay_ms=f["max_delay_ms"])
        for b in sorted({bucket_rows(r) for r in range(1, f["max_batch"] + 1)}):
            np.asarray(self.svc.predict(jnp.zeros((b, self.p), jnp.float32)))
        self.offered, self.unfolded, self.sequence = 0, 0, []
        self.published = {}
        for _ in range(tp["setup_chunks"]):
            self._ingest(self._next_chunk())
        jax.block_until_ready(self.svc.state)
        if self.svc.generation < 2:
            raise RuntimeError(f"set-up ran {self.svc.generation} refits; the "
                               f"cold and the warm refit must both run")
        self.front.start()
