"""``truncated_svd`` of the configuration's resident field.

Set-up builds the field on the device from the seed and hands it to the
engine through its ingest path for device arrays (no host copy and no
socket crossing: set-up is not what the window measures). Each request
is one ``truncated_svd(k)`` call over TCP with its own start-vector
seed, so the engine's routine cache never answers it, and U, S and V
are fetched to the client.

The check rebuilds the field in row blocks over the cell's own chips,
on a mesh of the benchmark's, takes the reference's top-k triplets once
there, and compares every answered call: singular values, right and left
singular vectors, each vector up to its sign.
"""
from __future__ import annotations

import numpy as np

from harness import data, reference
from harness.traffic import rng_for
from repro.core import transfer
from repro.core.expr import AlMatrix


class Kind:
    def __init__(self, run, name: str, params: dict):
        cfg = run.config
        self.shape = (cfg["rows"], cfg["cols"])
        self.k, self.oversample = cfg["rank"], cfg["oversample"]
        self.seed, self.spans = run.seed, run.spans
        self.chips = run.cell["chips"]
        self.client = run.served.client(name)
        self.el = self.client.library("elemental")
        engine = run.served.engine
        field = data.ocean_field(*self.shape, self.k, run.seed,
                                 sharding=engine.dist_sharding(self.shape))
        handle, _ = transfer.to_engine(engine, field,
                                       session=self.client.session)
        del field
        self.A = AlMatrix.wrap(self.client, handle)

    def warm(self) -> None:
        self.do({"seed": int(rng_for(self.seed, "warm").integers(2 ** 31))})

    def do(self, req: dict) -> dict:
        with self.spans("solve"):
            U, S, V = self.el.truncated_svd(self.A, k=self.k,
                                            oversample=self.oversample,
                                            seed=req["seed"])
            U.result()
        with self.spans("fetch"):
            out = {"U": U.to_numpy(), "S": S.to_numpy().ravel(),
                   "V": V.to_numpy(), "matvecs": U.stats()["matvecs"]}
        with self.spans("free"):
            for m in (U, S, V):
                m.free()
        return out

    def numbers(self, answers: list[dict]) -> dict:
        return compare(answers, triplets(self.shape, self.k, self.seed,
                                         self.chips))


def triplets(shape, k: int, seed: int, chips: int,
             precision: str = reference.HIGHEST) -> dict:
    """The reference's top-k triplets of the field of ``seed``, rebuilt in
    row blocks over the first ``chips`` devices, with its products at
    ``precision``."""
    a = data.ocean_field(*shape, k, seed, sharding=data.row_blocks(chips))
    prepared = reference.prepare(a, precision)
    del a
    return reference.truncated_svd(
        prepared, k, seed=int(rng_for(seed, "ref").integers(2 ** 31)))


def compare(answers: list[dict], ref: dict) -> dict:
    """Worst over the answers and the k triplets: relative error of each
    singular value, and distance of each unit singular vector from the
    reference's, after matching signs."""
    s_ref = np.asarray(ref["S"], np.float64)
    worst = {"svd.s_rel": 0.0, "svd.v_dist": 0.0, "svd.u_dist": 0.0}
    for ans in answers:
        s = np.asarray(ans["S"], np.float64)
        worst["svd.s_rel"] = max(worst["svd.s_rel"],
                                 float(np.max(np.abs(s - s_ref) / s_ref)))
        for key, name in (("V", "svd.v_dist"), ("U", "svd.u_dist")):
            got = np.asarray(ans[key], np.float64)
            want = np.asarray(ref[key], np.float64)
            sign = np.sign(np.sum(got * want, axis=0))
            dist = np.linalg.norm(got * sign - want, axis=0) \
                / np.linalg.norm(want, axis=0)
            worst[name] = max(worst[name], float(np.max(dist)))
    return worst
