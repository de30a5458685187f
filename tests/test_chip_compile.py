"""Compile rehearsals for a TPU v5e that is described, not attached: the
served kernels at the paper's widths with ``interpret=False``, one
fused engine program over a four-device mesh with row-block operands,
and the CG solve's programs at the 60,000-feature speech deployment's
size in row blocks over a 2x2 mesh.

The topology is described inside a module fixture (never at import), so
every test worker collects the same tests and only the worker given this
file loads the TPU compiler. JAX's persistent cache is off around these
compiles: an entry written for a described chip cannot be read back
without one.
"""
import functools
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

import repro.kernels
from repro.common.config import V5E
from repro.core.backends import base as backend_base
from repro.core.backends import jax_backend
from repro.core.backends.jax_backend import JaxBackend, _gram_matvec
from repro.kernels.gram import ops as gram_ops
from repro.kernels.normal_matvec import ops as nm_ops
from repro.kernels.rf_map import ops as rf_ops


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_kernels(monkeypatch):
    """The wrappers decide interpret mode from the default backend, which
    is the CPU here; steer them to the compiled kernel, as on the chip."""
    monkeypatch.setattr(repro.kernels, "interpret_mode", lambda: False)


def _spec(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


def _compile(fn, *args):
    with jax.default_matmul_precision("highest"):
        return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("kernel,shapes,fn", [
    # ocean width: 8,096 columns, padded to 8,192 by the wrapper
    ("gram", [(65_536, 8_096)],
     lambda a: gram_ops.gram(a, use_pallas=True)),
    # speech: d=440 raw features, 147 classes
    ("normal_matvec", [(131_072, 440), (440, 147)],
     lambda x, w: nm_ops.normal_matvec(x, w, use_pallas=True)),
    # speech: 440 -> 10,000 random features
    ("rf_map", [(131_072, 440), (440, 10_000), (10_000,)],
     lambda x, w, b: rf_ops.rf_map_apply(x, w, b, use_pallas=True)),
])
def test_kernel_compiles_for_v5e_at_paper_width(kernel, shapes, fn,
                                                 one_chip,
                                                 compiled_kernels):
    compiled = _compile(fn, *[_spec(s, one_chip) for s in shapes])
    assert "tpu_custom_call" in compiled.as_text(), kernel
    mem = compiled.memory_analysis()
    # one program's buffers fit the chip's 16 GB
    total = mem.argument_size_in_bytes + mem.output_size_in_bytes + \
        mem.temp_size_in_bytes
    assert total < 15 * 2 ** 30, (kernel, total)


def test_rf_map_kernel_writes_its_output_once(one_chip, compiled_kernels):
    """The expansion is the workload's largest tensor: the kernel writes
    (n, D) exactly, with no padded copy to slice and rescale afterwards.
    At D=10,000 (not a multiple of 128) the compiler still keeps one
    relayout temporary of about the output's size: two copies of Z, not
    the three a pad-and-slice wrapper held (about 15 GiB, next to no
    headroom on a 16 GB chip)."""
    n, d, dd = 131_072, 440, 10_000
    compiled = _compile(
        lambda x, w, b: rf_ops.rf_map_apply(x, w, b, use_pallas=True),
        _spec((n, d), one_chip), _spec((d, dd), one_chip),
        _spec((dd,), one_chip))
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes == n * dd * 4
    assert mem.temp_size_in_bytes < 1.25 * n * dd * 4


def test_fused_engine_program_compiles_on_four_chip_mesh(topo):
    """A bucketed gram -> multiply -> add chain, compiled by the backend's
    AOT path from row-block operands on a 2x2 v5e mesh: the program takes
    its inputs sharded, and the row-block Gram reduces across chips."""
    mesh = Mesh(np.array(topo.devices).reshape(4), ("workers",))
    rowblock = NamedSharding(mesh, P("workers", None))
    be = JaxBackend()
    gram = be.routine_impl("elemental", "gram")
    mul = be.routine_impl("elemental", "multiply")
    add = be.routine_impl("elemental", "add")
    plan = backend_base.ExecutionPlan(
        steps=[
            backend_base.PlanStep(
                library="elemental", routine="gram",
                args={"A": backend_base.Input("i0")}, impl=gram),
            backend_base.PlanStep(
                library="elemental", routine="multiply",
                args={"A": backend_base.StepRef(0, "G"),
                      "B": backend_base.StepRef(0, "G")}, impl=mul),
            backend_base.PlanStep(
                library="elemental", routine="add",
                args={"A": backend_base.StepRef(1, "C"),
                      "B": backend_base.StepRef(0, "G")}, impl=add),
        ],
        input_specs={"i0": ((262_144, 8_192), "float32")},
        input_layouts={"i0": "rowblock"},
        input_shardings={"i0": rowblock})
    program, info = be.get_or_compile(plan)
    assert info["aot"] and not info["cached"]
    text = program.as_text()
    assert "all-reduce" in text or "reduce-scatter" in text
    [in_sharding] = jax.tree_util.tree_leaves(program.input_shardings)
    assert in_sharding.is_equivalent_to(rowblock, 2)


#: a collective in a compiled program's text: its shape, then its op
COLLECTIVE = re.compile(r"= (\S+) (all-reduce|all-gather|reduce-scatter|"
                        r"all-to-all|collective-permute)(?:-start)?\(")


def _gram_path(x):
    """The Gram matvec path for a described ``x``, as ``_truncated_svd``
    picks it on the chip: X's split is its sharding's, each device's
    layout the one XLA gives the argument, and the VMEM a v5e's."""
    probe = jax.jit(lambda a: a).lower(x).compile()
    layout = probe.input_formats[0][0].layout.major_to_minor
    return nm_ops.gram_path(
        x.shape[1], x.dtype, compiled=True,
        devices=len(x.sharding.device_set),
        row_blocks=jax_backend._row_split(x) is not None,
        column_major=layout == (1, 0), vmem=int(V5E.vmem_bytes))


@pytest.mark.parametrize("d,path", [(8_096, "cols"), (8_192, "xla")])
def test_gram_matvec_kernel_reads_x_in_place_on_one_chip(d, path, one_chip,
                                                         compiled_kernels):
    """The Lanczos Gram matvec at the ocean field's height: XLA lays
    8,096 columns out column-major, and the program is the kernel over X
    as it lies; 8,192 columns lie row-major, which the kernel does not
    read, and the program is XLA's two passes. Neither copies X (6.3 GB)."""
    x = _spec((193_536, d), one_chip)
    assert _gram_path(x) == path
    with jax.default_matmul_precision("highest"):
        compiled = _gram_matvec.lower(x, _spec((d,), one_chip),
                                      path=path).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == (path == "cols")
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2 ** 20


def test_gram_matvec_on_four_chip_mesh_runs_the_kernel_per_chip(
        topo, compiled_kernels):
    """On a row-block mesh (a 2x2 v5e, 65,536 rows of 8,096 a chip) each
    chip's block lies column-major, and the Gram matvec is the one-pass
    kernel on each chip's rows under a ``shard_map``: one kernel, one
    all-reduce of the (d,) partials, and no copy of X."""
    mesh = Mesh(np.array(topo.devices).reshape(4), ("workers",))
    rowblock = NamedSharding(mesh, P("workers", None))
    x = _spec((262_144, 8_096), rowblock)
    path = _gram_path(x)
    assert path == "cols"
    with jax.default_matmul_precision("highest"):
        compiled = _gram_matvec.lower(
            x, _spec((8_096,), NamedSharding(mesh, P())), path=path,
            rows=jax_backend._row_split(x)).compile()
    text = compiled.as_text()
    assert text.startswith("HloModule jit__gram_matvec,")
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    # the kernel's op under its own name, as the device trace shows it
    assert re.search(r"%gram_matvec_pallas[.\d]* = .*tpu_custom_call", text)
    collectives = [(op, shape.split("{")[0]) for shape, op in
                   COLLECTIVE.findall(text)]
    assert collectives == [("all-reduce", "f32[8096]")], collectives
    assert re.search(r"%all-reduce[.\d]* = ", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2 ** 20


@pytest.mark.parametrize("spec", [P(), P(None, "workers")])
def test_gram_matvec_off_row_blocks_stays_two_pass(spec, topo):
    """A full copy of X on each chip, or its columns split: no chip holds
    whole rows of its own, and the path is XLA's two passes."""
    mesh = Mesh(np.array(topo.devices).reshape(4), ("workers",))
    x = _spec((262_144, 8_096), NamedSharding(mesh, spec))
    assert jax_backend._row_split(x) is None
    assert _gram_path(x) == "xla"


#: the 60,000-feature speech deployment on four chips: 94,208 rows of
#: 440 features, 147 classes, Z = 94,208 x 60,000 float32 (22.6 GB)
CG_ROWS, CG_FEATS, CG_CLASSES, CG_WIDTH = 94_208, 440, 147, 60_000


def _cg_programs(mesh):
    """The programs of ``_cg_solve`` as it runs them on a row-block X,
    with their operands: the expansion, the right-hand side, the step
    (a jit of a lambda, as the solve builds it) and the true residual."""
    rows = NamedSharding(mesh, P("workers", None))
    whole = NamedSharding(mesh, P())
    n, f, c, d = CG_ROWS, CG_FEATS, CG_CLASSES, CG_WIDTH
    state = (_spec((d, c), whole),) * 3 + (_spec((c,), whole),)
    step = jax.jit(lambda x, lam_n, st: jax_backend._cg_step(x, lam_n, st))
    return {
        "rf_expand": (jax_backend._rf_expand, (_spec((n, f), rows),),
                      {"rf_dim": d, "bandwidth": 20.0, "seed": 0,
                       "use_pallas": False}),
        "cg_rhs": (jax_backend._cg_rhs,
                   (_spec((n, d), rows), _spec((n, c), rows)), {}),
        "cg_step": (step, (_spec((n, d), rows), _spec((), whole), state), {}),
        "cg_residual": (jax_backend._cg_residual, (
            _spec((n, d), rows), _spec((n, c), rows), _spec((), whole),
            _spec((c,), whole), _spec((d, c), whole)),
            {"rows": (mesh, "workers")}),
    }


@pytest.mark.parametrize("name", ["rf_expand", "cg_rhs", "cg_step",
                                  "cg_residual"])
def test_cg_program_fits_a_chip_in_row_blocks_over_four(name, topo):
    """Each program of the solve compiles for a 2x2 v5e within one chip's
    16 GB, and none gathers Z (5.65 GB a chip, 22.6 GB in all): the
    expansion runs on each chip's rows; the right-hand side and the step
    sum one (60,000, 147) partial product across the chips; the residual
    runs each chip's own rows in blocks and sums its partials the same
    way."""
    mesh = Mesh(np.array(topo.devices).reshape(4), ("workers",))
    fn, args, static = _cg_programs(mesh)[name]
    with jax.default_matmul_precision("highest"):
        lowered = fn.lower(*args, **static)
    # every product at full float32 precision, inside the shard_map too
    dots = [ln for ln in lowered.as_text().splitlines()
            if "dot_general" in ln]
    assert dots and all("HIGHEST" in ln for ln in dots), name
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    total = mem.argument_size_in_bytes + mem.output_size_in_bytes + \
        mem.temp_size_in_bytes
    assert total < 15 * 2 ** 30, (name, total)
    collectives = [(op, shape.split("{")[0]) for shape, op in
                   COLLECTIVE.findall(compiled.as_text())]
    if name == "rf_expand":
        assert collectives == []
    else:
        assert collectives == [("all-reduce", "f32[60000,147]")], collectives


#: the tables of source files, functions and frames in a program's text
DEBUG_TABLE = re.compile(r"^(FileNames|FunctionNames|FileLocations|"
                         r"StackFrames|\d+ [{\"].*)$")


def _program_text(compiled) -> str:
    """A compiled program's HLO without its source locations."""
    return "\n".join(re.sub(r", metadata=\{[^}]*\}", "", line)
                     for line in compiled.as_text().splitlines()
                     if not DEBUG_TABLE.match(line))


def test_one_chip_cg_residual_is_unchanged(one_chip):
    """On one chip (``speech``: 140,800 x 10,000) the residual compiles to
    the program it compiled to before the row-block path existed."""
    def _cg_residual(x, y, lam_n, b_norm, w):
        # the residual before the row-block path, word for word, under
        # its name (the program's)
        n, d = x.shape
        blk = min(jax_backend._RESIDUAL_BLOCK, n)
        full = n // blk

        def part(xb, yb):
            return xb.T @ (yb - xb @ w)

        def body(i, acc):
            return acc + part(
                jax.lax.dynamic_slice_in_dim(x, i * blk, blk),
                jax.lax.dynamic_slice_in_dim(y, i * blk, blk))

        acc = jax.lax.fori_loop(0, full, body, jnp.zeros_like(w))
        if full * blk < n:
            acc = acc + part(x[full * blk:], y[full * blk:])
        return jnp.max(jnp.linalg.norm(acc - lam_n * w, axis=0)
                       / jnp.maximum(b_norm, 1e-30))

    parents = jax.jit(_cg_residual)
    args = (_spec((140_800, 10_000), one_chip),
            _spec((140_800, 147), one_chip), _spec((), one_chip),
            _spec((147,), one_chip), _spec((10_000, 147), one_chip))
    with jax.default_matmul_precision("highest"):
        now = jax_backend._cg_residual.lower(*args).compile()
        before = parents.lower(*args).compile()
    text = _program_text(before)
    assert text.startswith("HloModule jit__cg_residual,")
    assert "ENTRY" in text and "while" in text and "stack_frame" not in text
    assert _program_text(now) == text


def test_one_chip_gram_matvec_is_unchanged(one_chip, compiled_kernels):
    """On one chip (``ocean``: 193,536 x 8,096) the Gram matvec compiles
    to the program it compiled to before the row-block path existed."""
    @functools.partial(jax.jit, static_argnames="path")
    def _gram_matvec(x, v, path: str = "xla"):
        # the matvec before the row-block path, word for word, under its
        # name (the program's)
        return nm_ops.gram_matvec(x, v, path=path)

    args = (_spec((193_536, 8_096), one_chip), _spec((8_096,), one_chip))
    with jax.default_matmul_precision("highest"):
        now = jax_backend._gram_matvec.lower(*args, path="cols").compile()
        before = _gram_matvec.lower(*args, path="cols").compile()
    text = _program_text(before)
    assert text.startswith("HloModule jit__gram_matvec,")
    assert "tpu_custom_call" in text
    assert _program_text(now) == text
