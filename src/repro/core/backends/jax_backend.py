"""The jax/pallas backend — the engine's accelerated execution
environment, and the one that **fuses chains**.

All of the bundled libraries' compute moved here from
``core/libraries/*.py`` (the library modules now carry only the typed
specs). Implementations are array-level: jax arrays in, jax arrays out;
the blocked Pallas kernels under ``src/repro/kernels`` are reused where
they exist (``gram``, ``rf_map``, ``normal_matvec``, each with a jnp
fallback). The platform decides how a requested kernel runs: compiled
on a TPU, interpreted on the CPU (``kernels.interpret_mode``).

**Precision.** Every routine registered here traces its matmuls at full
float32 precision (``jax.default_matmul_precision("highest")``, scoped
to the routine's own trace, never process-wide). At the default
precision, on a v5e at the paper's widths, the CG solve's true residual
stalled at 7e-3 instead of 2e-5 and a Gram matrix came 5e-4 off the
float64 one; the Lanczos matvec read the same at both. A faster
precision is a per-routine choice to be judged against the float64
reference.

**Chain fusion.** Implementations marked ``fusible`` are pure, traceable
array programs. When the engine drains a dependency chain of deferred
ops that a lazy client submitted in one burst (see
``scheduler.claim_chain`` / ``engine._run_fused``), :meth:`compile`
lowers the whole multi-step plan into a **single ``jax.jit`` program**:
one XLA dispatch for the entire chain, chain-internal values flowing as
SSA edges inside the program — never materialized engine-side, never
crossing to host — with every step's outputs returned together at the
end. Compiled programs are cached by plan structure
(:meth:`ExecutionPlan.signature`), so a tenant replaying the same chain
shape pays tracing once.

Host-loop drivers (Lanczos SVD, CG, NMF, and ``gram_svd`` with its host
eigensolve) are registered non-fusible:
they are reverse-communication loops around jitted matvecs, exactly like
ARPACK driving distributed matvecs in the paper's MPI implementation.
"""
from __future__ import annotations

import collections
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import kernels
from repro.analysis import locktrace
from repro.core import tracing

from repro.core.backends import base
from repro.core.backends.base import REPLICATED, ROWBLOCK
from repro.core.backends.reference import (
    _lanczos_gram,
    mllib_cg_solve,
    mllib_truncated_svd,
)
from repro.kernels.gram import ops as gram_ops
from repro.kernels.normal_matvec import ops as nm_ops
from repro.kernels.rf_map import ops as rf_ops

_DENSE = (ROWBLOCK, REPLICATED)

#: default bound on distinct compiled programs held live (LRU)
DEFAULT_MAX_PROGRAMS = 128


class JaxBackend(base.ExecutionBackend):
    """GSPMD execution on the engine mesh, single-program chain fusion.

    Compiled programs are held in a bounded LRU keyed by the plan's
    *shape-aware* signature (structure + operand shapes/dtypes): every
    distinct (chain x shape) is one attributable entry, AOT-compilable
    ahead of traffic via :meth:`get_or_compile` and evictable under the
    ``max_programs`` bound instead of growing for the engine's lifetime.
    """

    name = "jax"
    supports_fusion = True
    #: this backend can AOT-compile plans from abstract shapes
    #: (``lower(ShapeDtypeStruct...).compile()``) — what engine warmup
    #: and shape bucketing key off
    supports_aot = True

    def __init__(self, max_programs: int = DEFAULT_MAX_PROGRAMS):
        super().__init__()
        # shape-aware signature -> compiled program, LRU-ordered; scalars
        # are part of the key (they are baked into the trace as
        # constants), and so are operand shapes/dtypes via input_specs
        self._programs: "collections.OrderedDict[tuple, object]" = \
            collections.OrderedDict()
        self._programs_lock = locktrace.make_lock("backend.programs")
        self.max_programs = int(max_programs)
        #: programs dropped by the LRU bound since construction
        self.evictions = 0

    def to_native(self, array) -> jax.Array:
        return array if isinstance(array, jax.Array) else jnp.asarray(array)

    def is_array(self, value) -> bool:
        return isinstance(value, (jax.Array, np.ndarray)) and \
            getattr(value, "ndim", 0) >= 1

    # ---- bucket pad/unpad (the shape-collapse wrappers) -----------------
    def pad_to(self, array, shape) -> jax.Array:
        """Zero-pad an operand up to its bucket shape (trailing edge of
        every dimension). Zero padding is the correctness contract
        behind ``RoutineImpl.bucketable``: for the linear kernels the
        logical block of the padded result equals the unpadded result
        exactly, and pad regions stay zero through chains."""
        arr = self.to_native(array)
        target = tuple(int(d) for d in shape)
        if tuple(arr.shape) == target:
            return arr
        if len(target) != arr.ndim or \
                any(t < s for t, s in zip(target, arr.shape)):
            raise ValueError(
                f"cannot pad {tuple(arr.shape)} up to {target}")
        return jnp.pad(arr, [(0, t - s)
                             for s, t in zip(arr.shape, target)])

    def crop_to(self, array, shape):
        """Slice a padded program output back to its logical shape."""
        target = tuple(int(d) for d in shape)
        if tuple(array.shape) == target:
            return array
        return array[tuple(slice(0, d) for d in target)]

    # ---- program cache --------------------------------------------------
    def program_cache_info(self) -> dict:
        """Observability: live program count, bound, lifetime evictions."""
        with self._programs_lock:
            return {"programs": len(self._programs),
                    "max_programs": self.max_programs,
                    "evictions": self.evictions}

    def _cache_get(self, sig):
        with self._programs_lock:
            program = self._programs.get(sig)
            if program is not None:
                self._programs.move_to_end(sig)
            return program

    def _cache_put(self, sig, program) -> int:
        """Insert under the LRU bound; returns how many were evicted."""
        evicted = 0
        with self._programs_lock:
            self._programs[sig] = program
            self._programs.move_to_end(sig)
            while len(self._programs) > self.max_programs:
                self._programs.popitem(last=False)
                evicted += 1
            self.evictions += evicted
        return evicted

    def _fused_fn(self, plan: base.ExecutionPlan):
        def fused(inputs: dict) -> list[dict]:
            outs: list[dict] = []
            for step in plan.steps:
                outs.append(step.impl.fn(
                    **base.resolve_step_args(step, outs, inputs)))
            return outs
        return fused

    def get_or_compile(self, plan: base.ExecutionPlan
                       ) -> tuple[object, dict]:
        """The instrumented compile path: return ``(program, info)``
        where info reports whether the program was served from the cache
        and, if not, the measured compile seconds.

        When the plan carries ``input_specs`` the program is compiled
        **ahead of execution** from abstract ``ShapeDtypeStruct`` values
        (``jax.jit(...).lower(...).compile()`` — the maxtext AOT serving
        idiom): the trace+XLA compile happens *here*, attributably, not
        hidden inside the first call — and, with JAX's persistent
        compilation cache configured, the XLA compile is served from
        disk on a warm restart. Specless plans fall back to a plain
        ``jax.jit`` that traces on first call (and can therefore never
        be warmed — the engine always passes specs)."""
        sig = plan.signature()
        if sig is not None:
            program = self._cache_get(sig)
            if program is not None:
                return program, {"cached": True, "compile_s": 0.0,
                                 "aot": False, "evicted": 0}
        fused = self._fused_fn(plan)
        t0 = time.perf_counter()
        aot = plan.input_specs is not None and sig is not None
        if aot:
            # each input carries the sharding the engine placed it in: a
            # program compiled for unsharded inputs rejects row-block
            # operands on a multi-device mesh
            shardings = plan.input_shardings or {}
            abstract = {slot: jax.ShapeDtypeStruct(
                tuple(int(d) for d in shape), jnp.dtype(dtype),
                sharding=shardings.get(slot))
                for slot, (shape, dtype) in plan.input_specs.items()}
            program = jax.jit(fused).lower(abstract).compile()
        else:
            program = jax.jit(fused)
        compile_s = time.perf_counter() - t0
        evicted = self._cache_put(sig, program) if sig is not None else 0
        return program, {"cached": False, "compile_s": compile_s,
                         "aot": aot, "evicted": evicted}

    def compile(self, plan: base.ExecutionPlan):
        """Single-step plans run the impl directly (host-loop drivers
        must not be traced); multi-step plans — only ever built from
        fusible steps — become one cached ``jax.jit`` program (see
        :meth:`get_or_compile` for the instrumented/AOT form the engine
        uses)."""
        if len(plan.steps) == 1:
            return super().compile(plan)
        return self.get_or_compile(plan)[0]


def register(library: str, routine: str, **kw):
    """Register a jax implementation whose matmuls trace at full float32
    precision (see the module docstring); the wrapper is what the engine
    and the fused programs call, the bare function stays importable."""
    def wrap(fn):
        @functools.wraps(fn)
        def at_fp32(*args, **kwargs):
            with jax.default_matmul_precision("highest"):
                return fn(*args, **kwargs)
        JaxBackend.register(library, routine, **kw)(at_fp32)
        return fn
    return wrap


# ---------------------------------------------------------------------------
# elemental
# ---------------------------------------------------------------------------
@register("elemental", "random_matrix", fusible=True, accepts=_DENSE)
def _random_matrix(rows: int, cols: int, seed: int = 0, scale: float = 1.0,
                   name: str = "random"):
    key = jax.random.PRNGKey(seed)
    return {"A": scale * jax.random.normal(key, (rows, cols), jnp.float32)}


@register("elemental", "replicate_cols", fusible=True, accepts=_DENSE)
def _replicate_cols(A, times: int):
    return {"A": jnp.tile(A, (1, times))}


@register("elemental", "multiply", fusible=True, accepts=_DENSE,
          bucketable=True, out_shapes=base.shapes_multiply)
def _multiply(A, B):
    return {"C": A @ B}


@register("elemental", "add", fusible=True, accepts=_DENSE,
          bucketable=True, out_shapes=base.shapes_add)
def _add(A, B):
    if A.shape != B.shape:                   # shapes are static under jit
        raise ValueError(f"add expects equal shapes, got {tuple(A.shape)} "
                         f"and {tuple(B.shape)}")
    return {"C": A + B}


@register("elemental", "transpose", fusible=True, accepts=_DENSE,
          bucketable=True, out_shapes=base.shapes_transpose)
def _transpose(A):
    # no host materialization: the engine re-lands the result in its
    # distributed layout (the dist-sharding put path)
    return {"C": A.T}


@functools.partial(jax.jit, static_argnames="use_pallas")
def _gram_matrix(x, use_pallas: bool = False):
    """G = X^T X on the device: the Pallas Gram kernel when requested.
    One program, so X^T is never materialized (run op by op, a 7.91 GiB
    operand's transpose did not fit next to it on one v5e)."""
    return gram_ops.gram(x, use_pallas=use_pallas)


@register("elemental", "gram", fusible=True, accepts=_DENSE,
          bucketable=True, out_shapes=base.shapes_gram)
def _gram(A, use_pallas: bool = False):
    return {"G": _gram_matrix(A, use_pallas=use_pallas)}


@register("elemental", "qr", fusible=True, accepts=_DENSE)
def _qr(A):
    q, r = jnp.linalg.qr(A, mode="reduced")
    return {"Q": q, "R": r}


@functools.partial(jax.jit, static_argnames=("path", "rows"))
def _gram_matvec(x, v, path: str = "xla", rows=None):
    """v -> X^T (X v); never materializes X^T X. ``path`` is
    ``nm_ops.gram_path``'s: the one-pass kernel, or XLA's two passes.

    With ``rows`` (:func:`_row_split` of X) ``path`` runs on each
    device's own block of rows under a ``shard_map`` (GSPMD cannot
    partition the kernel, and would gather X onto every device), and one
    all-reduce adds the devices' (d,) partials. The sum is left to GSPMD,
    outside the ``shard_map``: it compiles to the same one collective as
    a ``psum`` inside would, but under the name ``all-reduce`` that the
    device trace's collective readers look for (a ``psum`` is named
    ``psum``)."""
    if rows is None:
        return nm_ops.gram_matvec(x, v, path=path)
    mesh, axis = rows
    parts = jax.shard_map(
        lambda xb, vb: nm_ops.gram_matvec(xb, vb, path=path)[None],
        mesh=mesh, in_specs=(P(axis, None), P()), out_specs=P(axis, None),
        check_vma=False)(x, v)
    return parts.sum(axis=0)


@register("elemental", "truncated_svd", accepts=_DENSE)
def _truncated_svd(A, k: int, oversample: int = 32, max_iters: int = 0,
                   seed: int = 0):
    """ARPACK-style driver: the shared host-side Lanczos loop
    (``reference._lanczos_gram`` — one copy, so a numerical fix can
    never leave the backends divergent) around a *jitted distributed*
    matvec, exactly like ARPACK's reverse-communication interface
    driving distributed matvecs in the paper's MPI implementation."""
    x = A
    n, d = x.shape
    m = min(d, k + oversample) if max_iters == 0 else min(d, max_iters)
    q0 = np.asarray(jax.random.normal(jax.random.PRNGKey(seed), (d,),
                                      x.dtype), np.float64)
    rows = _row_split(x)
    shards = 1 if rows is None else rows[0].shape[rows[1]]
    path = nm_ops.gram_path(
        d, x.dtype, compiled=not kernels.interpret_mode(),
        devices=len(x.sharding.device_set), row_blocks=rows is not None,
        column_major=x.format.layout.major_to_minor == (1, 0),
        vmem=nm_ops.vmem_capacity())
    # the split reaches the program only where it changes it: the kernel
    # on row blocks; every other path is called as on one device
    split = {"rows": rows} if path == "cols" and rows is not None else {}

    def matvec(q):
        # each Lanczos iteration re-enters here: the natural QoS
        # preemption boundary for the reverse-communication driver
        base.yield_check()
        with tracing.span(tracing.LANCZOS_MATVEC):
            return np.asarray(
                _gram_matvec(x, jnp.asarray(q, x.dtype), path=path,
                             **split),
                np.float64)

    with tracing.span(tracing.LANCZOS, path=path, shards=shards):
        sigma, V, iters, matvecs = _lanczos_gram(matvec, d, k, m, q0)
    v_dev = jnp.asarray(V, x.dtype)
    U = (x @ v_dev) / jnp.maximum(jnp.asarray(sigma, x.dtype), 1e-30)
    return {"U": U, "S": jnp.asarray(sigma, jnp.float32), "V": v_dev,
            "lanczos_iters": iters, "matvecs": matvecs}


@register("elemental", "gram_svd", accepts=_DENSE)
def _gram_svd(A, k: int, use_pallas: bool = False):
    """The Gram matrix on the device, its d x d eigenproblem on the host in
    float64, U = X V back on the device. XLA's TPU eigh is no option at
    the paper's widths: compiling it for a v5e took 69 s and 4.4 GiB of
    host memory at d = 1,024 already."""
    x = A
    g = np.asarray(_gram_matrix(x, use_pallas=use_pallas), np.float64)
    evals, evecs = np.linalg.eigh(g)
    order = np.argsort(evals)[::-1][:k]
    sigma = np.sqrt(np.maximum(evals[order], 0.0))
    v_dev = jnp.asarray(evecs[:, order], x.dtype)
    u = (x @ v_dev) / jnp.maximum(jnp.asarray(sigma, x.dtype), 1e-30)
    return {"U": u, "S": jnp.asarray(sigma, jnp.float32), "V": v_dev}


@register("elemental", "randomized_svd", accepts=_DENSE)
def _randomized_svd(A, k: int, oversample: int = 8, power_iters: int = 2,
                    seed: int = 0):
    x = A
    n, d = x.shape
    ell = min(d, k + oversample)
    key = jax.random.PRNGKey(seed)

    @jax.jit
    def sketch(x):
        omega = jax.random.normal(key, (d, ell), x.dtype)
        y = x @ omega
        for _ in range(power_iters):
            y = x @ (x.T @ y)
        q, _ = jnp.linalg.qr(y, mode="reduced")
        b = q.T @ x                                            # (ell, d)
        ub, s, vt = jnp.linalg.svd(b, full_matrices=False)
        return q @ ub[:, :k], s[:k], vt[:k].T

    u, s, v = sketch(x)
    return {"U": u, "S": s, "V": v}


# ---------------------------------------------------------------------------
# skylark
# ---------------------------------------------------------------------------
#: the random-feature expansion as one program: run op by op, X W, + b
#: and the cosine each hold an (n, D) temporary, and the speech CG peaked
#: at 16.06 GB of a 16 GB v5e
_rf_expand = jax.jit(rf_ops.rf_map, static_argnames=(
    "rf_dim", "bandwidth", "seed", "use_pallas"))


@register("skylark", "random_features", accepts=_DENSE)
def _random_features(X, rf_dim: int, bandwidth: float = 1.0, seed: int = 0,
                     use_pallas: bool = False):
    return {"Z": _rf_expand(X, rf_dim, bandwidth=bandwidth, seed=seed,
                            use_pallas=use_pallas)}


def _cg_step(x, lam_n, state, use_pallas=False):
    """One CG iteration on the normal equations; with use_pallas (and a
    width whose row block fits VMEM, ``nm_ops.uses_kernel``) the fused
    normal_matvec kernel streams X once per iteration instead of twice
    (the CG loop's dominant HBM traffic)."""
    w, r, p, rs = state
    ap = nm_ops.normal_matvec(x, p, use_pallas=use_pallas).astype(x.dtype) \
        + lam_n * p
    alpha = rs / jnp.sum(p * ap, axis=0)
    w = w + alpha * p
    r = r - alpha * ap
    rs_new = jnp.sum(r * r, axis=0)
    p = r + (rs_new / rs) * p
    return w, r, p, rs_new


@jax.jit
def _cg_rhs(x, y):
    """b = X^T Y as one program: run eagerly, X^T is a transpose op that
    materializes a second (n, D) buffer next to X."""
    return x.T @ y


#: rows per block of the residual evaluation (see :func:`_cg_residual`)
_RESIDUAL_BLOCK = 1024


def _row_split(a):
    """``(mesh, axis)`` where ``a``'s rows lie in equal blocks over a mesh
    axis, one block a device (the engine's row-block layout); None where
    one device holds all of them, or each device a full copy."""
    sharding = getattr(a, "sharding", None)
    spec = getattr(sharding, "spec", None)
    if not spec or not isinstance(spec[0], str) or any(spec[1:]) or \
            len(sharding.device_set) == 1:
        return None
    return sharding.mesh, spec[0]


def _residual_sum(x, y, w):
    """X^T (Y - X w), summed over blocks of rows."""
    n = x.shape[0]
    blk = min(_RESIDUAL_BLOCK, n)
    full = n // blk

    def part(xb, yb):
        return xb.T @ (yb - xb @ w)

    def body(i, acc):
        return acc + part(jax.lax.dynamic_slice_in_dim(x, i * blk, blk),
                          jax.lax.dynamic_slice_in_dim(y, i * blk, blk))

    acc = jax.lax.fori_loop(0, full, body, jnp.zeros_like(w))
    if full * blk < n:
        acc = acc + part(x[full * blk:], y[full * blk:])
    return acc


@functools.partial(jax.jit, static_argnames="rows")
def _cg_residual(x, y, lam_n, b_norm, w, rows=None):
    """max over columns of ||X^T Y - (X^T X + lam_n I) w|| / ||b||,
    evaluated on w itself rather than carried by the CG recurrence, as
    X^T (Y - X w) - lam_n w summed over blocks of rows. In one float32
    product over all rows the rounding is of the size of the residual it
    measures: at 131,072 x 10,000 on a v5e it read 1.34e-5 where float64
    read 1.88e-5; by blocks of 1,024 rows it read 1.88e-5.

    With ``rows`` (:func:`_row_split` of X) each device sums the blocks
    of its own rows and one ``psum`` adds the devices' (D, c) partials:
    sliced on a split row axis, the blocks would gather all of X onto
    every device (at 94,208 x 60,000 over four v5e chips, 22.6 GB on
    each)."""
    if rows is None:
        acc = _residual_sum(x, y, w)
    else:
        mesh, axis = rows
        acc = jax.shard_map(
            lambda xb, yb, wb: jax.lax.psum(_residual_sum(xb, yb, wb), axis),
            mesh=mesh, in_specs=(P(axis, None), P(axis, None), P()),
            out_specs=P(), check_vma=False)(x, y, w)
    return jnp.max(jnp.linalg.norm(acc - lam_n * w, axis=0)
                   / jnp.maximum(b_norm, 1e-30))


@register("skylark", "cg_solve", accepts=_DENSE)
def _cg_solve(X, Y, lam: float = 1e-5, rf_dim: int = 0,
              bandwidth: float = 1.0, max_iters: int = 200,
              tol: float = 1e-8, seed: int = 0, use_pallas: bool = False):
    # the expansion is row-local, so Z's rows lie where X's do
    rows = _row_split(X)
    shards = 1 if rows is None else rows[0].shape[rows[1]]
    with tracing.span(tracing.CG, shards=shards):
        x = X
        if rf_dim:
            with tracing.span(tracing.CG_RF_MAP, shards=shards):
                x = _rf_expand(x, rf_dim, bandwidth=bandwidth, seed=seed,
                               use_pallas=use_pallas)
        y = Y
        n, d = x.shape
        lam_n = jnp.asarray(n * lam, x.dtype)

        with tracing.span(tracing.CG_RHS, shards=shards):
            b = _cg_rhs(x, y)                        # (d, c) rhs
            b_norm = jnp.linalg.norm(b, axis=0)
            w = jnp.zeros(b.shape, x.dtype)
            r = b
            p = r
            rs = jnp.sum(r * r, axis=0)

        _step = jax.jit(lambda x, lam_n, st: _cg_step(x, lam_n, st,
                                                      use_pallas=use_pallas))

        iters = 0
        rel = float(jnp.max(jnp.sqrt(rs) / jnp.maximum(b_norm, 1e-30)))
        history = [rel]
        state = (w, r, p, rs)
        while iters < max_iters and rel > tol:
            base.yield_check()          # QoS iteration boundary
            with tracing.span(tracing.CG_STEP, shards=shards):
                state = _step(x, lam_n, state)
                iters += 1
                rel = float(jnp.max(jnp.sqrt(state[3])
                                    / jnp.maximum(b_norm, 1e-30)))
            history.append(rel)
        # in float32 the recurrence drifts from the true residual: on a
        # v5e it fell to 7e-8 while b - A w stalled at 2e-5. The loop
        # stops on the recurrence; what is reported is the true residual
        # of the W returned, one more pass over the rows.
        with tracing.span(tracing.CG_RESIDUAL, shards=shards):
            true_rel = float(_cg_residual(x, y.astype(x.dtype), lam_n,
                                          b_norm, state[0], rows=rows))

        return {
            "W": state[0],
            "iterations": iters,
            "relative_residual": true_rel,
            "residual_history": [float(h) for h in history],
            "expanded_dim": int(d),
            # which implementation ran each stage: a requested kernel
            # that gave way to the jnp reference by shape shows here
            "kernels": {
                "rf_map": ("pallas" if use_pallas else "jnp") if rf_dim
                else "none",
                "normal_matvec": "pallas"
                if nm_ops.uses_kernel(d, use_pallas) else "jnp"},
            # the devices Z's rows are split over, and the bytes of the
            # (D, c) partial product that each step sums across them
            "shards": shards,
            "reduce_bytes": b.size * b.dtype.itemsize if shards > 1 else 0,
        }


@register("skylark", "nmf", accepts=_DENSE)
def _nmf(A, k: int, max_iters: int = 100, seed: int = 0, eps: float = 1e-9):
    x = jnp.maximum(A, 0.0)
    n, d = x.shape
    kw, kh = jax.random.split(jax.random.PRNGKey(seed))
    scale = jnp.sqrt(jnp.mean(x) / k)
    w = scale * jax.random.uniform(kw, (n, k), x.dtype, 0.1, 1.0)
    h = scale * jax.random.uniform(kh, (k, d), x.dtype, 0.1, 1.0)

    @jax.jit
    def update(w, h):
        h = h * (w.T @ x) / (w.T @ (w @ h) + eps)
        w = w * (x @ h.T) / (w @ (h @ h.T) + eps)
        return w, h

    for _ in range(max_iters):
        base.yield_check()          # QoS iteration boundary
        w, h = update(w, h)
    resid = float(jnp.linalg.norm(x - w @ h) / jnp.linalg.norm(x))
    return {"W": w, "H": h, "relative_residual": resid,
            "iterations": max_iters}


# ---------------------------------------------------------------------------
# mllib — shared with the reference backend (see backends/reference.py:
# the pure-Spark baseline is client-side row-partitioned math by
# construction; accelerating it would unmake the comparison)
# ---------------------------------------------------------------------------
register("mllib", "cg_solve", accepts=_DENSE)(mllib_cg_solve)
register("mllib", "truncated_svd", accepts=_DENSE)(mllib_truncated_svd)
