"""One Spark-style CG job: send X and Y, ``cg_solve`` on their random-
feature expansion, fetch W, free all three.

Set-up generates the pool of distinct jobs (``pool`` in the mix), so
neither upload dedup nor the routine cache answers a job, and no job's W
solves another's equations. The check evaluates the normal-equations
residual of each sampled W against its own job, with the reference's own
features, at full float32 precision by blocks of rows.
"""
from __future__ import annotations

import time

from harness import data, reference


class Kind:
    def __init__(self, run, name: str, params: dict):
        cfg = self.cfg = run.config
        self.iters, self.tol = params["max_iters"], params["tol"]
        # the random-feature map is part of the deployment, fixed by its
        # configuration: a new map per run would compile a new expansion
        self.rf_seed = cfg["rf_seed"]
        # one job more than the window may send: the warm-up's
        self.pool = data.SpeechPool(cfg["rows"], cfg["feats"],
                                    cfg["classes"], run.seed,
                                    jobs=run.mix["pool"] + 1)
        self.next = 0
        self.spans = run.spans
        self.client = run.served.client(name)
        self.sk = self.client.library("skylark")

    def warm(self) -> None:
        self.do({})

    def do(self, req: dict) -> dict:
        if self.next >= len(self.pool.jobs):
            raise RuntimeError("the pool of distinct jobs is spent")
        job, self.next = self.next, self.next + 1
        x, y = self.pool.jobs[job]
        cfg = self.cfg
        t0 = time.perf_counter()
        with self.spans("send"):
            X = self.client.send_matrix(x)
            Y = self.client.send_matrix(y)
            X.result()
            Y.result()
        send_s = time.perf_counter() - t0
        with self.spans("solve"):
            W = self.sk.cg_solve(X, Y, lam=cfg["lam"], rf_dim=cfg["rf_dim"],
                                 bandwidth=cfg["bandwidth"],
                                 max_iters=self.iters, tol=self.tol,
                                 seed=self.rf_seed)
            W.result()
        with self.spans("fetch"):
            w = W.to_numpy()
            iterations = W.stats()["iterations"]
        with self.spans("free"):
            for m in (X, Y, W):
                m.free()
        return {"W": w, "job": job, "send_s": send_s,
                "iterations": iterations}

    def numbers(self, answers: list[dict]) -> dict:
        return compare(self.cfg, self.pool, answers)


def compare(cfg: dict, pool: data.SpeechPool, answers: list[dict]) -> dict:
    """The worst normal-equations residual of the answers' W, each against
    its own job's X and Y, with the reference's features."""
    worst = 0.0
    for a in answers:
        x, y = pool.jobs[a["job"]]
        worst = max(worst, reference.cg_residual(
            x, y, a["W"], cfg["lam"], cfg["rf_dim"], cfg["bandwidth"],
            cfg["rf_seed"]))
    return {"cg.residual": worst}
