"""TRC/PKL/LCK — source-level rules over the accelerator and wire code.

* **TRC001** trace purity — no host synchronization, I/O, or lock
  acquisition inside functions that are traced by ``jax.jit`` or run as
  Pallas kernels (and inside every impl registered ``fusible=True``,
  since those are exactly what the engine may merge into a jitted
  chain). A ``block_until_ready`` / ``np.asarray`` / ``print`` inside a
  trace either silently bakes a host round trip into every dispatch or
  fails only at fuse time on the request path — both are bugs that
  survive eager testing. A host span (``tracing.span``,
  ``TraceAnnotation``) is banned there too: it would time the trace,
  once, and never a run.
* **PKL001** no-pickle-on-wire — the user-data modules
  (``wire``/``transfer``/``protocol``/``server``) must never import or
  call ``pickle``-family deserializers (or ``eval``/``exec``). The
  transport's security stance is that a hostile peer can at worst hand
  back wrong numbers, never run code; one convenience ``pickle.loads``
  would end that.
* **LCK001** raw-lock discipline — ``repro.core`` must construct every
  lock through ``repro.analysis.locktrace``'s named factories. A raw
  ``threading.Lock()`` is invisible to the dynamic lock-order detector,
  which silently un-completes its view of the process.
* **LCK002** rank-table integrity — every rank in
  ``locktrace.LOCK_RANKS`` is unique (the table IS the total order, no
  ambiguous ties), and the rank table documented in
  ``docs/architecture.md`` (between the ``LOCK_RANK_TABLE`` markers)
  matches the code exactly — the docs-vs-code drift that rank
  renumbering would otherwise cause is a gate failure.

All are AST passes (plus registry introspection for the fusible set in
TRC001 and the rank registry in LCK002); suppression is by baseline
fingerprint, not inline comments — see docs/architecture.md.
"""
from __future__ import annotations

import ast
import inspect
import os
import textwrap
from typing import Iterable, Optional

from repro.analysis.findings import Finding


def _repo_src() -> str:
    return os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))


def _core_path(*parts) -> str:
    return os.path.join(_repo_src(), "repro", "core", *parts)


def _kernel_files() -> list[str]:
    root = os.path.join(_repo_src(), "repro", "kernels")
    out = []
    for dirpath, _dirs, files in os.walk(root):
        for f in sorted(files):
            if f.endswith(".py"):
                out.append(os.path.join(dirpath, f))
    return out


# ---- TRC001: trace purity ---------------------------------------------
#: attribute calls that force a device->host sync or do I/O
_BANNED_METHOD_CALLS = frozenset({
    "block_until_ready", "tolist", "item", "acquire", "release",
    "TraceAnnotation",
})
#: bare-name calls that are host-side I/O
_BANNED_NAME_CALLS = frozenset({"print", "open", "input"})
#: module-attr calls that materialize on host / block / take locks
_BANNED_MODULE_CALLS = {
    "np": {"asarray", "array", "save", "load", "frombuffer"},
    "numpy": {"asarray", "array", "save", "load", "frombuffer"},
    "jax": {"device_get"},
    "time": {"sleep", "time", "perf_counter", "monotonic"},
    "threading": None,          # any attribute
    "os": None,
    "socket": None,
    "tracing": None,            # host spans (repro.core.tracing)
}


def _is_jit_decorator(node: ast.expr) -> bool:
    """Matches ``@jax.jit``, ``@jit``, ``@functools.partial(jax.jit,
    ...)`` and ``@jax.jit(...)`` decorator shapes."""
    def names(n: ast.expr) -> str:
        if isinstance(n, ast.Attribute):
            return f"{names(n.value)}.{n.attr}"
        if isinstance(n, ast.Name):
            return n.id
        return ""
    if isinstance(node, ast.Call):
        fn = names(node.func)
        if fn.endswith("jit"):
            return True
        if fn.endswith("partial"):
            return any(names(a).endswith("jit") for a in node.args)
        return False
    return names(node).endswith("jit")


def _pallas_kernel_names(tree: ast.AST) -> set[str]:
    """Function names passed as the first argument to
    ``pl.pallas_call`` / ``pallas_call``."""
    out: set[str] = set()
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fn = node.func
        name = fn.attr if isinstance(fn, ast.Attribute) else \
            fn.id if isinstance(fn, ast.Name) else ""
        if name == "pallas_call" and node.args \
                and isinstance(node.args[0], ast.Name):
            out.add(node.args[0].id)
    return out


def _impure_nodes(fndef: ast.AST) -> Iterable[tuple[int, str]]:
    for node in ast.walk(fndef):
        if not isinstance(node, ast.Call):
            # `with lock:` inside a trace is as bad as .acquire()
            if isinstance(node, (ast.With, ast.AsyncWith)):
                for item in node.items:
                    ctx = item.context_expr
                    lock_name = None
                    if isinstance(ctx, ast.Attribute) \
                            and "lock" in ctx.attr.lower():
                        lock_name = ctx.attr
                    elif isinstance(ctx, ast.Name) \
                            and "lock" in ctx.id.lower():
                        lock_name = ctx.id
                    if lock_name is not None:
                        yield node.lineno, f"with {lock_name}: (lock " \
                            "held inside a traced function)"
            continue
        fn = node.func
        if isinstance(fn, ast.Name) and fn.id in _BANNED_NAME_CALLS:
            yield node.lineno, f"{fn.id}()"
        elif isinstance(fn, ast.Attribute):
            if fn.attr in _BANNED_METHOD_CALLS:
                yield node.lineno, f".{fn.attr}()"
            elif isinstance(fn.value, ast.Name) \
                    and fn.value.id in _BANNED_MODULE_CALLS:
                banned = _BANNED_MODULE_CALLS[fn.value.id]
                if banned is None or fn.attr in banned:
                    yield node.lineno, f"{fn.value.id}.{fn.attr}()"


def _traced_defs(tree: ast.AST) -> list[ast.FunctionDef]:
    kernels = _pallas_kernel_names(tree)
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if node.name in kernels \
                or any(_is_jit_decorator(d) for d in node.decorator_list):
            out.append(node)
    return out


def _scan_file_for_trace_purity(path: str) -> list[Finding]:
    with open(path, "r") as f:
        src = f.read()
    tree = ast.parse(src)
    out = []
    for fndef in _traced_defs(tree):
        for lineno, what in _impure_nodes(fndef):
            out.append(Finding(
                rule="TRC001", file=path, line=lineno,
                symbol=f"{os.path.basename(path)}:{fndef.name}",
                message=f"{what} inside traced function "
                        f"{fndef.name!r} — host sync/I-O/locking must "
                        "stay outside jit/Pallas traces"))
    return out


def _fusible_impl_findings() -> list[Finding]:
    """Fusible registrations are traced when chains fuse — hold their
    bodies to the same purity bar, via registry introspection."""
    from repro.core.backends.jax_backend import JaxBackend
    out: list[Finding] = []
    be = JaxBackend()
    for (lib, rt) in be.routines():
        impl = be.routine_impl(lib, rt)
        if not impl.fusible:
            continue
        try:
            src = textwrap.dedent(inspect.getsource(impl.fn))
            file = inspect.getsourcefile(impl.fn) or "?"
        except (OSError, TypeError):
            continue
        fndef = ast.parse(src).body[0]
        if not isinstance(fndef, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        base_line = inspect.getsourcelines(impl.fn)[1] - 1
        for lineno, what in _impure_nodes(fndef):
            out.append(Finding(
                rule="TRC001", file=file, line=base_line + lineno,
                symbol=f"{lib}.{rt}@fusible",
                message=f"{what} inside fusible impl of {lib}.{rt} — "
                        "fusible bodies are traced into jitted chains "
                        "and must stay pure"))
    return out


def check_trace_purity(paths: Optional[list[str]] = None,
                       include_fusible: bool = True) -> list[Finding]:
    if paths is None:
        paths = [_core_path("backends", "jax_backend.py")] \
            + _kernel_files()
    out: list[Finding] = []
    for p in paths:
        out.extend(_scan_file_for_trace_purity(p))
    if include_fusible:
        out.extend(_fusible_impl_findings())
    # one finding per (symbol, message-kind): dedup overlap between the
    # file scan and the fusible-registry scan
    seen: set[str] = set()
    deduped = []
    for f in out:
        key = f"{f.file}:{f.line}:{f.message}"
        if key not in seen:
            seen.add(key)
            deduped.append(f)
    return deduped


# ---- PKL001: no pickle on the wire ------------------------------------
_PICKLE_MODULES = frozenset({
    "pickle", "cPickle", "_pickle", "dill", "cloudpickle", "marshal",
    "shelve",
})


def check_no_pickle(paths: Optional[list[str]] = None) -> list[Finding]:
    if paths is None:
        paths = [_core_path(n) for n in
                 ("wire.py", "transfer.py", "protocol.py", "server.py")]
    out: list[Finding] = []
    for path in paths:
        with open(path, "r") as f:
            tree = ast.parse(f.read())
        base = os.path.basename(path)
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                for alias in node.names:
                    root = alias.name.split(".")[0]
                    if root in _PICKLE_MODULES:
                        out.append(Finding(
                            rule="PKL001", file=path, line=node.lineno,
                            symbol=f"{base}:import-{root}",
                            message=f"import {alias.name} in a wire-"
                                    "data module — user data must stay "
                                    "on raw tobytes/msgpack (a pickle "
                                    "deserializer is remote code "
                                    "execution)"))
            elif isinstance(node, ast.ImportFrom):
                root = (node.module or "").split(".")[0]
                if root in _PICKLE_MODULES:
                    out.append(Finding(
                        rule="PKL001", file=path, line=node.lineno,
                        symbol=f"{base}:import-{root}",
                        message=f"from {node.module} import ... in a "
                                "wire-data module — pickle-family "
                                "codecs are banned on user data paths"))
            elif isinstance(node, ast.Call):
                fn = node.func
                if isinstance(fn, ast.Name) and fn.id in ("eval", "exec"):
                    out.append(Finding(
                        rule="PKL001", file=path, line=node.lineno,
                        symbol=f"{base}:{fn.id}",
                        message=f"{fn.id}() in a wire-data module"))
                elif isinstance(fn, ast.Attribute) \
                        and isinstance(fn.value, ast.Name) \
                        and fn.value.id in _PICKLE_MODULES:
                    out.append(Finding(
                        rule="PKL001", file=path, line=node.lineno,
                        symbol=f"{base}:{fn.value.id}.{fn.attr}",
                        message=f"{fn.value.id}.{fn.attr}() in a "
                                "wire-data module"))
    return out


# ---- LCK001: raw-lock discipline --------------------------------------
_RAW_LOCK_CTORS = frozenset({"Lock", "RLock", "Condition", "Semaphore",
                             "BoundedSemaphore"})


def check_lock_discipline(paths: Optional[list[str]] = None
                          ) -> list[Finding]:
    if paths is None:
        root = _core_path()
        paths = []
        for dirpath, _dirs, files in os.walk(root):
            for f in sorted(files):
                if f.endswith(".py"):
                    paths.append(os.path.join(dirpath, f))
    out: list[Finding] = []
    for path in paths:
        with open(path, "r") as f:
            tree = ast.parse(f.read())
        base = os.path.basename(path)
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            if isinstance(fn, ast.Attribute) \
                    and isinstance(fn.value, ast.Name) \
                    and fn.value.id == "threading" \
                    and fn.attr in _RAW_LOCK_CTORS:
                out.append(Finding(
                    rule="LCK001", file=path, line=node.lineno,
                    symbol=f"{base}:threading.{fn.attr}",
                    message=f"raw threading.{fn.attr}() in core — "
                            "construct locks through repro.analysis."
                            "locktrace (make_lock/make_rlock/"
                            "make_condition) so the lock-order "
                            "detector sees every lock in the process"))
    return out


# ---- LCK002: rank-table integrity (code + docs) ------------------------
_RANK_TABLE_BEGIN = "<!-- LOCK_RANK_TABLE_BEGIN -->"
_RANK_TABLE_END = "<!-- LOCK_RANK_TABLE_END -->"


def _default_doc_path() -> str:
    root = os.path.dirname(_repo_src())         # .../src -> repo root
    return os.path.join(root, "docs", "architecture.md")


def _parse_rank_table(text: str, path: str
                      ) -> tuple[Optional[dict[str, int]], list[Finding]]:
    """lock name -> documented rank, read from the marked table rows
    (``| <rank> | `name` | prose |``)."""
    try:
        begin = text.index(_RANK_TABLE_BEGIN)
        end = text.index(_RANK_TABLE_END)
    except ValueError:
        return None, [Finding(
            rule="LCK002", file=path, line=1,
            symbol="docs:rank-table-markers",
            message=f"docs/architecture.md lacks the {_RANK_TABLE_BEGIN}"
                    f" / {_RANK_TABLE_END} markers around the lock rank "
                    "table — LCK002 cannot check docs against code")]
    out: dict[str, int] = {}
    findings: list[Finding] = []
    base_line = text[:begin].count("\n") + 1
    for i, line in enumerate(text[begin:end].splitlines()):
        row = line.strip()
        if not row.startswith("|") or set(row) <= {"|", "-", " "}:
            continue
        cells = [c.strip() for c in row.strip("|").split("|")]
        if len(cells) < 2 or cells[0] in ("rank", ""):
            continue
        m = None
        if cells[1].startswith("`") and cells[1].endswith("`"):
            m = cells[1].strip("`")
        try:
            rank = int(cells[0])
        except ValueError:
            rank = None
        if m is None or rank is None:
            findings.append(Finding(
                rule="LCK002", file=path, line=base_line + i,
                symbol=f"docs:rank-row:{cells[1][:40]}",
                message=f"unparseable rank-table row {row!r} — expected "
                        "`| <int rank> | `lock.name` | prose |`"))
            continue
        out[m] = rank
    return out, findings


def check_lock_ranks(ranks: Optional[dict[str, int]] = None,
                     doc_path: Optional[str] = None) -> list[Finding]:
    """LCK002: unique ranks in code, and docs == code."""
    from repro.analysis.locktrace import LOCK_RANKS
    if ranks is None:
        ranks = LOCK_RANKS
    if doc_path is None:
        doc_path = _default_doc_path()
    out: list[Finding] = []
    code_file = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "locktrace.py")
    by_rank: dict[int, list[str]] = {}
    for name, rank in ranks.items():
        by_rank.setdefault(rank, []).append(name)
    for rank, names in sorted(by_rank.items()):
        if len(names) > 1:
            out.append(Finding(
                rule="LCK002", file=code_file, line=1,
                symbol=f"rank-dup:{rank}",
                message=f"locks {sorted(names)} share rank {rank} — "
                        "ranks must be unique so LOCK_RANKS is a total "
                        "order (equal-rank nesting is undetectable)"))
    try:
        with open(doc_path, "r") as f:
            text = f.read()
    except OSError:
        return out + [Finding(
            rule="LCK002", file=doc_path, line=1,
            symbol="docs:missing",
            message="docs/architecture.md not found — the documented "
                    "lock order cannot be checked")]
    documented, findings = _parse_rank_table(text, doc_path)
    out.extend(findings)
    if documented is None:
        return out
    for name in sorted(set(ranks) - set(documented)):
        out.append(Finding(
            rule="LCK002", file=doc_path, line=1,
            symbol=f"docs:undocumented:{name}",
            message=f"lock {name!r} (rank {ranks[name]}) is registered "
                    "in locktrace.LOCK_RANKS but missing from the "
                    "documented rank table"))
    for name in sorted(set(documented) - set(ranks)):
        out.append(Finding(
            rule="LCK002", file=doc_path, line=1,
            symbol=f"docs:stale:{name}",
            message=f"documented lock {name!r} is not registered in "
                    "locktrace.LOCK_RANKS — stale docs row"))
    for name in sorted(set(documented) & set(ranks)):
        if documented[name] != ranks[name]:
            out.append(Finding(
                rule="LCK002", file=doc_path, line=1,
                symbol=f"docs:rank-drift:{name}",
                message=f"documented rank {documented[name]} for "
                        f"{name!r} != code rank {ranks[name]}"))
    return out
