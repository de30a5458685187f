"""Each cell's run, with the chip check skipped and the program broken
underneath, reads ``correct`` false; unbroken, it reads true. The fault
every cell can have is an answer altered where it is produced: one
singular value of each SVD, or the solution of each CG job, off by a
thousandth. A CG job can also be answered with another job's solution,
as a cache keyed too loosely would answer it. A cell over several chips
can leave out the exchange between them: each chip's Gram matvec with
its own rows and the partial products never summed. The four-chip cell
runs on four virtual CPU devices."""
import pytest

import tiny

#: replaces one registered routine of the program's jax backend by a
#: wrapper that alters one output by a thousandth of its size
FAULT = """
import dataclasses
import jax.numpy as jnp
from repro.core.backends.jax_backend import JaxBackend
key = ({library!r}, {routine!r})
impl = JaxBackend._registered[key]
def broken(*args, _fn=impl.fn, **kwargs):
    out = dict(_fn(*args, **kwargs))
    x = out[{output!r}]
    out[{output!r}] = x.at[(0,) * x.ndim].add(1e-3 * jnp.max(jnp.abs(x)))
    return out
JaxBackend._registered[key] = dataclasses.replace(impl, fn=broken)
"""

#: answers every CG solve after the first with a copy of the first's W
STALE = """
import dataclasses
import jax.numpy as jnp
from repro.core.backends.jax_backend import JaxBackend
key = ("skylark", "cg_solve")
impl = JaxBackend._registered[key]
first = {}
def stale(*args, _fn=impl.fn, **kwargs):
    out = dict(_fn(*args, **kwargs))
    first.setdefault("W", jnp.copy(out["W"]))
    out["W"] = jnp.copy(first["W"])
    return out
JaxBackend._registered[key] = dataclasses.replace(impl, fn=stale)
"""

#: the Gram matvec of the SVD as each chip computes it over its own rows,
#: X_i^T (X_i v), with the sum over the chips left out: what reaches the
#: Lanczos loop is the first chip's partial product
NO_EXCHANGE = """
import jax
from jax.sharding import PartitionSpec as P
from repro.core.backends import jax_backend
def local_only(x, v, path="xla"):
    rows = x.sharding.spec[0]
    return jax.shard_map(lambda xb, vb: xb.T @ (xb @ vb),
                         mesh=x.sharding.mesh, in_specs=(P(rows, None), P()),
                         out_specs=P(), check_vma=False)(x, v)
jax_backend._gram_matvec = local_only
"""

CELLS = {
    "ocean.svd": ("elemental", "truncated_svd", "S"),
    "ocean.svd_4chip": ("elemental", "truncated_svd", "S"),
    "speech.cg": ("skylark", "cg_solve", "W"),
}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.copy(str(tmp_path_factory.mktemp("bench")))


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_sound_run_is_correct(root, cell):
    rc, out, err = tiny.run(root, cell)
    assert rc == 0, err
    res = tiny.result(out)
    assert res["correct"] and res["failed"] == 0, err
    assert res["checks"]


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_altered_answer_is_not_correct(root, cell):
    library, routine, output = CELLS[cell]
    assert_not_correct(*tiny.run(root, cell, fault=FAULT.format(
        library=library, routine=routine, output=output)))


def test_another_jobs_solution_is_not_correct(root):
    assert_not_correct(*tiny.run(root, "speech.cg", fault=STALE))


def test_exchange_between_chips_left_out_is_not_correct(root):
    assert_not_correct(*tiny.run(root, "ocean.svd_4chip", fault=NO_EXCHANGE))


def assert_not_correct(rc, out, err):
    assert rc == 0, err
    res = tiny.result(out)
    assert res["correct"] is False, err
    assert res["failed"] == 0
    assert any(c["value"] > c["limit"] for c in res["checks"].values())
