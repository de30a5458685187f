"""Tests for the extension features: fused normal-matvec kernel, the
one-pass Gram matvec kernel, NMF routine, offloaded linear-head
fitting."""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core import AlchemistContext
from repro.core.backends import jax_backend
from repro.core.libraries import elemental, skylark
from repro.kernels.normal_matvec import ops as nm_ops
from repro.kernels.normal_matvec.normal_matvec import normal_matvec_pallas
from repro.kernels.normal_matvec.ops import normal_matvec
from repro.kernels.normal_matvec.ref import gram_matvec_ref, \
    normal_matvec_ref


@pytest.mark.parametrize("n,d,c", [(256, 64, 4), (300, 128, 1),
                                   (512, 440, 16), (1000, 37, 3)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_normal_matvec_matches_ref(n, d, c, dtype):
    key = jax.random.PRNGKey(n + d + c)
    x = jax.random.normal(key, (n, d), dtype)
    w = jax.random.normal(jax.random.PRNGKey(1), (d, c), jnp.float32)
    got = normal_matvec(x, w, use_pallas=True, bm=128)
    want = normal_matvec_ref(x, w)
    tol = 3e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol * float(jnp.abs(want).max()))


def test_normal_matvec_padding_is_exact():
    """Zero-row padding must not perturb X^T X w."""
    x = jax.random.normal(jax.random.PRNGKey(0), (130, 32), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (32, 2), jnp.float32)
    got = normal_matvec(x, w, use_pallas=True, bm=128)   # pads 130 -> 256
    want = normal_matvec_ref(x, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5,
                               atol=2e-4)


#: the Gram matvec's tolerance against the float64 product, in units of
#: its largest entry: about 16 float32 ulps, where the kernel read at most
#: 5.6e-7 (n = 1,000, d = 8,096) and XLA's two passes as much
GRAM_TOL = 2e-6


@pytest.mark.parametrize("n,d", [
    (1024, 256),          # d a multiple of 128, whole blocks of 512
    (2048, 200),          # d not a multiple of 128
    (512, 8096),          # the ocean field's width at a small n
    (600, 200),           # n not a multiple of the block: masked tail
    (100, 200),           # n below one block
    (1000, 37),           # d under one lane tile and one 8-row strip
    (513, 256),           # one row past a whole block
    (4096, 130),          # eight blocks, d just over one lane tile
    (700, 8),             # d of one 8-row strip exactly
])
def test_gram_matvec_matches_ref(n, d):
    """The one-pass kernel, interpreted: within ``GRAM_TOL`` of the
    float64 product, as the two-pass reference is."""
    rng = np.random.default_rng(n + d)
    x = rng.standard_normal((n, d)).astype(np.float32)
    v = rng.standard_normal(d).astype(np.float32)
    want = x.astype(np.float64).T @ (x.astype(np.float64) @ v)
    got = nm_ops.gram_matvec(jnp.asarray(x), jnp.asarray(v), path="cols")
    ref = gram_matvec_ref(jnp.asarray(x), jnp.asarray(v))
    assert got.shape == (d,) and got.dtype == jnp.float32
    scale = GRAM_TOL * np.abs(want).max()
    np.testing.assert_allclose(np.asarray(got, np.float64), want, rtol=0,
                               atol=scale)
    np.testing.assert_allclose(np.asarray(ref, np.float64), want, rtol=0,
                               atol=scale)


_MIB = 2 ** 20

#: X's rows in blocks over four devices, a full copy on each, its
#: columns split, and a 2-D split
ROWS, REPLICATED, COLS, GRID = (P("workers", None), P(), P(None, "workers"),
                                P("workers", "model"))


def _split(spec):
    """``(devices, row_blocks)`` for X over four devices by ``spec``, as
    ``_truncated_svd`` reads them (``jax_backend._row_split``); one device
    where ``spec`` is None."""
    if spec is None:
        return 1, False
    x = types.SimpleNamespace(sharding=types.SimpleNamespace(
        spec=spec, mesh="mesh", device_set=frozenset(range(4))))
    return 4, jax_backend._row_split(x) is not None


@pytest.mark.parametrize(
    "d,dtype,compiled,spec,column_major,vmem,path", [
        (8096, jnp.float32, True, None, True, 128 * _MIB, "cols"),  # ocean
        (8096, jnp.float32, True, None, False, 128 * _MIB, "xla"),  # row-maj
        (8096, jnp.float32, False, None, True, 0, "xla"),   # CPU: interpreted
        (8096, jnp.float32, True, ROWS, True, 128 * _MIB, "cols"),  # 4 chips
        (8096, jnp.float32, True, REPLICATED, True, 128 * _MIB, "xla"),
        (8096, jnp.float32, True, COLS, True, 128 * _MIB, "xla"),
        (8096, jnp.float32, True, GRID, True, 128 * _MIB, "xla"),
        (8096, jnp.float32, True, ROWS, False, 128 * _MIB, "xla"),
        (32768, jnp.float32, True, None, True, 128 * _MIB, "xla"),  # too wide
        (8096, jnp.float32, True, None, True, 64 * _MIB, "xla"),    # less VMEM
        (4096, jnp.float32, True, None, True, 64 * _MIB, "cols"),
        (8096, jnp.bfloat16, True, None, True, 128 * _MIB, "xla"),
    ])
def test_gram_path_selection(d, dtype, compiled, spec, column_major,
                             vmem, path):
    devices, row_blocks = _split(spec)
    assert nm_ops.gram_path(d, dtype, compiled=compiled, devices=devices,
                            row_blocks=row_blocks,
                            column_major=column_major, vmem=vmem) == path


def test_vmem_capacity_is_zero_off_tpu():
    """On the CPU Pallas describes no chip, so no block fits."""
    assert jax.default_backend() != "tpu"
    assert nm_ops.vmem_capacity() == 0


@pytest.mark.parametrize("n", [2048, 2100])
def test_truncated_svd_on_the_fused_matvec(monkeypatch, n):
    """The Lanczos SVD with its Gram matvec forced onto the kernel
    (interpreted here), whole blocks and a masked tail: the singular
    values match numpy's."""
    picked = []
    monkeypatch.setattr(nm_ops, "gram_path",
                        lambda *a, **k: picked.append("cols") or "cols")
    rng = np.random.default_rng(3)
    scales = np.linspace(40.0, 10.0, 5)
    x = (rng.standard_normal((n, 5)) * scales) \
        @ np.linalg.qr(rng.standard_normal((200, 5)))[0].T
    x = (x + rng.standard_normal((n, 200))).astype(np.float32)
    ac = AlchemistContext(num_workers=1)
    ac.register_library("elemental", elemental)
    try:
        res = ac.call("elemental", "truncated_svd", A=ac.send_matrix(x), k=5)
        s = ac.wrap(res["S"]).to_numpy().ravel()
    finally:
        ac.stop()
    assert picked == ["cols"]
    want = np.linalg.svd(x.astype(np.float64), compute_uv=False)[:5]
    np.testing.assert_allclose(s, want, rtol=1e-5)


def test_cg_with_fused_kernel_matches_direct():
    ac = AlchemistContext(num_workers=1)
    ac.register_library("skylark", skylark)
    rng = np.random.RandomState(0)
    x = rng.randn(256, 24).astype(np.float32)
    y = rng.randn(256, 2).astype(np.float32)
    res = ac.call("skylark", "cg_solve", X=ac.send_matrix(x),
                  Y=ac.send_matrix(y), lam=1e-3, max_iters=300, tol=1e-10,
                  use_pallas=True)
    w = ac.wrap(res["W"]).to_numpy()
    want = np.linalg.solve(x.T @ x + 256 * 1e-3 * np.eye(24), x.T @ y)
    np.testing.assert_allclose(w, want, atol=1e-4)


def test_nmf_reduces_residual_and_stays_nonnegative():
    ac = AlchemistContext(num_workers=1)
    ac.register_library("skylark", skylark)
    rng = np.random.RandomState(0)
    truth = rng.rand(80, 4) @ rng.rand(4, 30)
    res = ac.call("skylark", "nmf", A=ac.send_matrix(truth), k=4,
                  max_iters=200)
    w = ac.wrap(res["W"]).to_numpy()
    h = ac.wrap(res["H"]).to_numpy()
    assert (w >= 0).all() and (h >= 0).all()
    assert res["relative_residual"] < 0.05
    np.testing.assert_allclose(w @ h, truth, atol=0.3)


def test_offloaded_linear_probe_beats_chance():
    from repro.common.config import ShapeConfig
    from repro.configs import get_reduced
    from repro.data.pipeline import SyntheticLM
    from repro.models.model import build_model
    from repro.nn.core import init_params
    from repro.train.offload import (
        extract_features,
        fit_linear_head_cg,
        head_accuracy,
    )

    cfg = get_reduced("stablelm-1.6b")
    model = build_model(cfg)
    params = init_params(model.param_specs(), jax.random.PRNGKey(0))
    shape = ShapeConfig("probe", seq_len=16, global_batch=16, mode="train")
    data = SyntheticLM(cfg, shape, seed=0, bigram_q=1.0)
    feats, labels = extract_features(
        model, params, (data.batch(i) for i in range(6)), max_batches=6)
    # restrict to a small label space for a learnable probe
    labels = labels % 8

    ac = AlchemistContext(num_workers=1)
    ac.register_library("skylark", skylark)
    w, res = fit_linear_head_cg(ac, feats, labels, num_classes=8, lam=1e-4)
    acc = head_accuracy(w, feats, labels)
    assert acc > 1.5 / 8, acc          # comfortably above the 1/8 chance
