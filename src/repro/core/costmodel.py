"""Live accounting of the served path, and the QoS price.

The logs record what the engine, the scheduler and the bridges actually
did, each under its own lock: :class:`TransferLog` (every chunk that
crossed the bridge, its logical and framed bytes), :class:`WireLog`
(frames and bytes per socket endpoint), :class:`TaskLog` (queue wait and
execute time per task), :class:`CompileLog` (plan compiles and cache
hits), :class:`JitLog` (every executable JAX obtains in the process),
:class:`QosLog` (admission, backpressure and preemption) and
:class:`CacheLog` (memoized hits and upload dedup).

The one estimate here is the QoS price, :func:`routine_price_seconds`
with :data:`TASK_DISPATCH_S`: the fair-share policy charges it at
dispatch and reconciles it against the measured ``exec_s``.
"""
from __future__ import annotations

import collections
import dataclasses
import time

from repro.analysis import locktrace

# ---- task-dispatch constant (QoS price) ----
# Estimated fixed cost of dispatching ONE scheduler task: queue insertion,
# hazard-edge bookkeeping, worker wakeup, result encode, and the XLA
# dispatch itself. The QoS price adds it to every routine's estimate, and
# admission and fair share use it as the floor of any estimate.
TASK_DISPATCH_S = 2.0e-4


@dataclasses.dataclass
class TransferRecord:
    """One boundary crossing. With the streaming path (§3.2) a single
    logical matrix send produces one record per row-block chunk:
    ``chunk_index`` in ``[0, num_chunks)`` positions the chunk, ``session``
    names the client session that moved the bytes. ``chunk_index == -1``
    marks a whole-stream *aggregate* record (what ``transfer.to_engine``/
    ``to_client`` return to the caller; never appended to the log — with
    one exception: a content-dedup'd upload produces a single aggregate
    record with ``dedup=True`` and zero ``nbytes``, which IS logged,
    because that zero-byte crossing is the whole event;
    ``logical_nbytes`` records what the stream would have moved)."""
    nbytes: int
    direction: str                # "to_engine" | "to_client"
    session: int = 0
    chunk_index: int = 0
    num_chunks: int = 1
    dedup: bool = False           # upload short-circuited by content match
    logical_nbytes: int = 0       # bytes the dedup'd stream did NOT move
    # Bytes actually framed onto a TCP socket for this crossing (frame
    # headers + serialized payload). 0 on the in-memory bridge — there is
    # no wire — and measured on the socket bridge; ``nbytes`` always
    # keeps the logical payload size so the two bridges stay directly
    # comparable.
    wire_nbytes: int = 0


class TransferLog:
    """Accumulates every boundary crossing.

    Appends are lock-protected: with the async scheduler, transfers from
    several client threads interleave with engine-side task execution, and
    the log is the shared accounting surface they all write.
    """

    def __init__(self):
        self.records: list[TransferRecord] = []
        self._lock = locktrace.make_lock("costmodel.transfer")

    def record(self, nbytes: int, direction: str, session: int = 0,
               chunk_index: int = 0, num_chunks: int = 1,
               wire_nbytes: int = 0) -> TransferRecord:
        """Log one crossing (one chunk of a streamed send, or a whole
        single-shot send) and return its record."""
        rec = TransferRecord(
            nbytes=int(nbytes),
            direction=direction,
            session=session,
            chunk_index=chunk_index,
            num_chunks=num_chunks,
            wire_nbytes=int(wire_nbytes),
        )
        with self._lock:
            self.records.append(rec)
        return rec

    def record_dedup(self, logical_nbytes: int, direction: str,
                     session: int = 0, num_chunks: int = 1,
                     wire_nbytes: int = 0) -> TransferRecord:
        """Log a content-dedup'd upload: the stream short-circuited to a
        handle alias, so zero bytes actually crossed; ``logical_nbytes``
        is what the stream would have moved (over a socket,
        ``wire_nbytes`` is the tiny fingerprint-lookup frame — never the
        payload)."""
        rec = TransferRecord(
            nbytes=0, direction=direction, session=session, chunk_index=-1,
            num_chunks=num_chunks, dedup=True,
            logical_nbytes=int(logical_nbytes),
            wire_nbytes=int(wire_nbytes))
        with self._lock:
            self.records.append(rec)
        return rec

    @property
    def total_bytes(self) -> int:
        return sum(r.nbytes for r in self.records)

    def session_summary(self, session: int) -> dict:
        """Per-session transfer accounting: bytes and chunk counts by
        direction, and the uploads dedup short-circuited."""
        with self._lock:
            recs = [r for r in self.records if r.session == session]
        out = {"session": session}
        for direction in ("to_engine", "to_client"):
            sub = [r for r in recs if r.direction == direction]
            out[f"{direction}_bytes"] = sum(r.nbytes for r in sub)
            # a dedup pseudo-record (chunk_index=-1) moved nothing and is
            # counted under dedup_uploads, not as a stream chunk
            out[f"{direction}_chunks"] = sum(
                1 for r in sub if not r.dedup)
        out["dedup_uploads"] = sum(1 for r in recs if r.dedup)
        out["dedup_bytes_saved"] = sum(
            r.logical_nbytes for r in recs if r.dedup)
        return out


@dataclasses.dataclass
class WireStat:
    """Measured traffic of one wire endpoint: how many frames crossed in
    each direction and how many bytes they occupied on the socket, frame
    headers included."""
    frames_in: int = 0
    bytes_in: int = 0
    frames_out: int = 0
    bytes_out: int = 0


class WireLog:
    """Per-endpoint frame/byte accounting for the socket bridge.

    ``engine.endpoint_counts`` deliberately counts *logical* calls — one
    submit is one crossing however it is carried — and that stays true on
    every bridge. This log is the physical complement: the socket server
    (and the client bridge) record here how many frames each logical call
    actually cost and how many bytes they put on the wire.
    The in-memory bridge never writes one: no socket, no frames.
    """

    def __init__(self):
        self._stats: dict[str, WireStat] = {}
        self._lock = locktrace.make_lock("costmodel.wire")

    def record(self, endpoint: str, frames_in: int = 0, bytes_in: int = 0,
               frames_out: int = 0, bytes_out: int = 0) -> None:
        with self._lock:
            st = self._stats.setdefault(endpoint, WireStat())
            st.frames_in += frames_in
            st.bytes_in += bytes_in
            st.frames_out += frames_out
            st.bytes_out += bytes_out

    def stat(self, endpoint: str) -> WireStat:
        """The (possibly empty) accumulated stat for one endpoint."""
        with self._lock:
            return self._stats.get(endpoint, WireStat())


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (q in [0, 100]) without numpy — the latency
    quantile the logs' summaries report. Returns 0.0 on empty input."""
    if not values:
        return 0.0
    vals = sorted(values)
    rank = max(0, min(len(vals) - 1,
                      int(round(q / 100.0 * (len(vals) - 1)))))
    return float(vals[rank])


@dataclasses.dataclass
class TaskRecord:
    """Accounting for one scheduled command: which session ran what, how
    long it waited in the queue (dependencies + worker availability) vs
    how long its task body ran, and its terminal state.

    ``exec_s`` ends when the task body returns. JAX dispatches device
    work asynchronously, so a routine's last dispatched programs may
    still be running then (the ``U = X V`` product of a truncated SVD):
    ``exec_s`` is host time to the return, not time to a finished
    result.

    Backend-ABI fields: ``fused_ops`` is how many logical commands this
    task executed (1 normally; N for the lead task of a fused chain);
    ``absorbed`` marks a command that was *claimed into* another task's
    fused program instead of dispatching on its own (its row keeps the
    per-command accounting, but it cost no dispatch); ``relayouts``/
    ``relayout_bytes`` count the explicit layout redistributions the
    engine inserted because an operand arrived in a layout the backend
    implementation does not accept."""
    session: int
    label: str                    # "library.routine"
    state: str                    # DONE | FAILED
    wait_s: float
    exec_s: float
    fused_ops: int = 1
    absorbed: bool = False
    relayouts: int = 0
    relayout_bytes: int = 0

    @property
    def latency_s(self) -> float:
        return self.wait_s + self.exec_s


class TaskLog:
    """Per-task wait/execute accounting for the scheduler (the queueing
    side of the paper's overhead story: §4 separates transfer from
    compute; under concurrency a third term appears — time spent queued
    behind other tenants — and this log is where it becomes visible)."""

    def __init__(self):
        self.records: list[TaskRecord] = []
        self._lock = locktrace.make_lock("costmodel.task")

    def record(self, session: int, label: str, state: str,
               wait_s: float, exec_s: float, fused_ops: int = 1,
               absorbed: bool = False, relayouts: int = 0,
               relayout_bytes: int = 0) -> TaskRecord:
        rec = TaskRecord(session=session, label=label, state=state,
                         wait_s=wait_s, exec_s=exec_s,
                         fused_ops=int(fused_ops), absorbed=bool(absorbed),
                         relayouts=int(relayouts),
                         relayout_bytes=int(relayout_bytes))
        with self._lock:
            self.records.append(rec)
        return rec

    def stats(self) -> dict:
        """Engine-wide dispatch/fusion/relayout accounting — what the
        fusion tests assert on.

        ``commands`` counts logical routine invocations (every recorded
        row); ``dispatched`` counts tasks that actually ran on a worker
        (absorbed rows excluded); ``fused_tasks`` of those executed more
        than one command; ``ops_per_task`` is the amortization ratio
        (``commands / dispatched`` — 1.0 means fusion never engaged)."""
        with self._lock:
            recs = list(self.records)
        dispatched = [r for r in recs if not r.absorbed]
        fused = [r for r in dispatched if r.fused_ops > 1]
        return {
            "commands": len(recs),
            "dispatched": len(dispatched),
            "absorbed": len(recs) - len(dispatched),
            "fused_tasks": len(fused),
            "fused_ops": sum(r.fused_ops for r in fused),
            "ops_per_task": (len(recs) / len(dispatched))
            if dispatched else 0.0,
            "relayouts": sum(r.relayouts for r in recs),
            "relayout_bytes": sum(r.relayout_bytes for r in recs),
        }

    def session_summary(self, session: int) -> dict:
        """Latency summary for one session: task counts, total/mean
        wait and execute seconds, and p50/p99 end-to-end latency."""
        with self._lock:
            recs = [r for r in self.records if r.session == session]
        lat = [r.latency_s for r in recs]
        n = len(recs)
        return {
            "session": session,
            "tasks": n,
            "failed": sum(1 for r in recs if r.state == "FAILED"),
            "wait_s": sum(r.wait_s for r in recs),
            "exec_s": sum(r.exec_s for r in recs),
            "mean_wait_s": sum(r.wait_s for r in recs) / n if n else 0.0,
            "mean_exec_s": sum(r.exec_s for r in recs) / n if n else 0.0,
            "p50_latency_s": percentile(lat, 50),
            "p99_latency_s": percentile(lat, 99),
        }

    def sessions(self) -> list[int]:
        with self._lock:
            return sorted({r.session for r in self.records})


@dataclasses.dataclass
class CompileRecord:
    """One event on the XLA compile layer (see ``core/compilecache.py``).

    ``event`` is ``"compile"`` (a program was traced+compiled),
    ``"hit"`` (served from the backend's in-process program cache) or
    ``"evict"`` (LRU dropped programs). ``on_request_path`` separates
    the latency that a tenant's call actually absorbed from warmup
    compiles paid off-path; ``aot`` marks ahead-of-time
    ``lower().compile()`` compiles (vs a plain ``jax.jit`` that traces
    at first call); ``bucketed`` marks executions whose operands were
    padded to the bucket grid — the shapes that collapse onto shared
    executables. ``session`` is -1 for engine-initiated warmup."""
    session: int
    label: str                    # "lib.routine+lib.routine" chain label
    event: str                    # compile | hit | evict
    on_request_path: bool = True
    aot: bool = False
    bucketed: bool = False
    steps: int = 1
    compile_s: float = 0.0
    count: int = 1                # evicted-program count for "evict"


class CompileLog:
    """Compile-latency accounting for the engine's plans — the
    observability half of the compile cache. Where TaskLog shows
    queue-vs-execute time, this log shows the third hidden term the
    paper's overhead argument warns about: XLA trace+compile seconds of
    the programs the engine plans, and *where* they were paid (on a
    tenant's first call, or off-path during warmup). After warmup,
    :meth:`stats` reports zero ``request_compiles`` for bucketed shapes.

    ``compiles`` counts only those plan compiles. A ``jax.jit`` that a
    routine body builds or calls itself (the host loops' matvec and CG
    step) never passes through the plan, so it is not here: the
    process-wide :data:`JIT_LOG` counts every executable JAX obtains,
    every persistent-cache load and every trace, and
    ``engine.compile_stats()["jit"]`` reports it."""

    def __init__(self):
        self.records: list[CompileRecord] = []
        self._lock = locktrace.make_lock("costmodel.compile")

    def record(self, session: int, label: str, event: str,
               on_request_path: bool = True, aot: bool = False,
               bucketed: bool = False, steps: int = 1,
               compile_s: float = 0.0, count: int = 1) -> CompileRecord:
        rec = CompileRecord(session=session, label=label, event=event,
                            on_request_path=bool(on_request_path),
                            aot=bool(aot), bucketed=bool(bucketed),
                            steps=int(steps), compile_s=float(compile_s),
                            count=int(count))
        with self._lock:
            self.records.append(rec)
        return rec

    @staticmethod
    def _summarize(recs: list["CompileRecord"]) -> dict:
        compiles = [r for r in recs if r.event == "compile"]
        hits = [r for r in recs if r.event == "hit"]
        request = [r for r in compiles if r.on_request_path]
        lookups = len(compiles) + len(hits)
        bucketed = [r for r in recs if r.event in ("compile", "hit")
                    and r.bucketed]
        return {
            "compiles": len(compiles),
            "hits": len(hits),
            "hit_rate": len(hits) / lookups if lookups else 0.0,
            "aot_compiles": sum(1 for r in compiles if r.aot),
            "request_compiles": len(request),
            "warmup_compiles": len(compiles) - len(request),
            "request_compile_s": sum(r.compile_s for r in request),
            "warmup_compile_s": sum(r.compile_s for r in compiles
                                    if not r.on_request_path),
            "bucketed_executions": len(bucketed),
            "bucketed_request_compiles": sum(
                1 for r in request if r.bucketed),
            "evictions": sum(r.count for r in recs if r.event == "evict"),
        }

    def stats(self) -> dict:
        """Engine-wide compile accounting across every session."""
        with self._lock:
            recs = list(self.records)
        return self._summarize(recs)

    def session_summary(self, session: int) -> dict:
        """Compile seconds this session's calls actually absorbed vs
        cache hits it enjoyed — the p99 story per tenant."""
        with self._lock:
            recs = [r for r in self.records if r.session == session]
        return {"session": session, **self._summarize(recs)}

    def sessions(self) -> list[int]:
        with self._lock:
            return sorted({r.session for r in self.records})


#: JAX's own compile-path monitoring events, by the key the jit counter
#: counts each under: an executable obtained (compiled by XLA or supplied
#: by the persistent cache), a load from the persistent cache, a trace
#: of a Python function to a jaxpr (nested traces count too)
JIT_EVENTS = {
    "/jax/core/compile/backend_compile_duration": "executables",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_loads",
    "/jax/core/compile/jaxpr_trace_duration": "traces",
}


#: how many of the latest jit events keep their time, for windows
JIT_EVENTS_KEPT = 4096


class JitLog:
    """Every executable, persistent-cache load and trace in this process,
    counted by function name from JAX's own monitoring events — so it
    sees the jits a routine body builds per call, which
    :class:`CompileLog` does not.

    The listener is process-wide (JAX has one registry), so there is one
    log, :data:`JIT_LOG`, whatever the number of engines; ``install`` is
    idempotent. The latest :data:`JIT_EVENTS_KEPT` events also keep
    their ``time.perf_counter()`` time, so :meth:`events` can say what
    happened inside a window. A persistent-cache load carries no
    function name."""

    def __init__(self):
        self._counts: collections.Counter = collections.Counter()
        self._events: collections.deque = collections.deque(
            maxlen=JIT_EVENTS_KEPT)
        self._dropped_until: float | None = None   # the latest dropped
        self._installed = False
        self._lock = locktrace.make_lock("costmodel.jit")

    def install(self) -> None:
        with self._lock:
            if self._installed:
                return
            self._installed = True
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration_secs: float, **kwargs) -> None:
        key = JIT_EVENTS.get(event)
        if key is None:
            return
        fun = str(kwargs.get("fun_name", ""))
        t = time.perf_counter()
        with self._lock:
            self._counts[key, fun] += 1
            if len(self._events) == self._events.maxlen:
                self._dropped_until = self._events[0][0]
            self._events.append((t, key, fun))

    def stats(self) -> dict:
        """Totals per key, and per function name."""
        with self._lock:
            counts = dict(self._counts)
        out: dict = {key: 0 for key in JIT_EVENTS.values()}
        by_function: dict = {}
        for (key, fun), n in counts.items():
            out[key] += n
            by_function.setdefault(fun, {})[key] = n
        out["by_function"] = by_function
        return out

    def events(self, since: float = float("-inf"),
               until: float = float("inf")) -> list[tuple] | None:
        """``(time, key, function name)`` of the events whose
        ``perf_counter`` time lies in ``[since, until)``; ``None`` when an
        event dropped from the latest :data:`JIT_EVENTS_KEPT` may have
        lain there, so the list could not be whole."""
        with self._lock:
            if self._dropped_until is not None \
                    and since <= self._dropped_until:
                return None
            return [e for e in self._events if since <= e[0] < until]


JIT_LOG = JitLog()


# ---- QoS price model (fair-share virtual time, see core/qos/) ----
# Iterative solver-class routines (Lanczos SVD, CG, NMF) run tens of
# matvec passes over their operands per call; single linear kernels run
# one. The estimate's job is to *rank* tenants' work for fair-share
# charging at dispatch time, before the task has run — the scheduler
# reconciles each estimate against the measured ``exec_s`` on
# completion, so only the relative ordering needs to be right.
_QOS_ITERATIVE = frozenset({
    "truncated_svd", "svd", "cg_solve", "nmf", "lsqr",
})
_QOS_MODEL_PASSES = 30                # modeled solver iteration count
_QOS_BYTES_PER_S = 2e9                # modeled per-core streaming rate


def routine_price_seconds(library: str, routine: str,
                          arg_bytes: int = 0) -> float:
    """Estimated execute-seconds for one routine call: the fixed
    dispatch cost plus one modeled pass over the operand bytes — or
    :data:`_QOS_MODEL_PASSES` passes for the iterative solver class
    (the SVD/CG-class tasks the paper offloads). This is what the
    fair-share policy charges a session's virtual time at dispatch."""
    per_pass = max(int(arg_bytes), 0) / _QOS_BYTES_PER_S
    passes = _QOS_MODEL_PASSES if routine in _QOS_ITERATIVE else 1
    return TASK_DISPATCH_S + passes * per_pass


@dataclasses.dataclass
class QosRecord:
    """One event on the multi-tenant QoS layer (see ``core/qos/``).

    ``event`` is ``"admitted"`` (a submit passed admission control),
    ``"rejected"`` (a submit denied for a quota violation — ``reason``
    names the quota), ``"throttled"`` (an upload reservation denied:
    backpressure on the data plane), ``"preempted"`` (a long task
    yielded at an iteration boundary to a lagging lighter tenant), or
    ``"complete"`` (a task finished under fair share: ``wait_s`` is its
    queue wait, ``debt_s`` the reconciliation delta — measured minus
    estimated execute seconds — charged back to the session's virtual
    time). ``weight`` is the session's fair-share weight at event time,
    which is what groups the p50/p99 wait split by weight class."""
    session: int
    event: str        # admitted | rejected | throttled | preempted | complete
    weight: float = 1.0
    wait_s: float = 0.0
    debt_s: float = 0.0
    reason: str = ""


class QosLog:
    """Per-tenant QoS accounting — the observability half of admission
    control and fair-share dispatch. Where TaskLog shows what each task
    paid, this log shows what the QoS layer *did about it*: who was
    admitted, who was pushed back, who yielded, and whether the
    fair-share queue actually kept light tenants' waits flat under a
    heavy neighbor (the p50/p99 wait split by weight class)."""

    def __init__(self):
        self.records: list[QosRecord] = []
        self._lock = locktrace.make_lock("costmodel.qos")

    def record(self, session: int, event: str, weight: float = 1.0,
               wait_s: float = 0.0, debt_s: float = 0.0,
               reason: str = "") -> QosRecord:
        rec = QosRecord(session=session, event=event, weight=float(weight),
                        wait_s=float(wait_s), debt_s=float(debt_s),
                        reason=reason)
        with self._lock:
            self.records.append(rec)
        return rec

    @staticmethod
    def _summarize(recs: list["QosRecord"]) -> dict:
        waits = [r.wait_s for r in recs if r.event == "complete"]
        return {
            "admitted": sum(1 for r in recs if r.event == "admitted"),
            "rejected": sum(1 for r in recs if r.event == "rejected"),
            "throttled": sum(1 for r in recs if r.event == "throttled"),
            "preempted": sum(1 for r in recs if r.event == "preempted"),
            "completed": len(waits),
            "debt_s": sum(r.debt_s for r in recs),
            "p50_wait_s": percentile(waits, 50),
            "p99_wait_s": percentile(waits, 99),
        }

    def stats(self) -> dict:
        """Engine-wide QoS accounting, plus the same summary split by
        tenant weight class (every distinct weight seen) — how the
        fairness claim is checked: light classes' p99 wait must not
        inflate when a heavy class saturates."""
        with self._lock:
            recs = list(self.records)
        out = self._summarize(recs)
        out["weight_classes"] = {
            repr(w): self._summarize([r for r in recs if r.weight == w])
            for w in sorted({r.weight for r in recs})}
        return out

    def session_summary(self, session: int) -> dict:
        """One tenant's admission/backpressure/preemption history."""
        with self._lock:
            recs = [r for r in self.records if r.session == session]
        return {"session": session, **self._summarize(recs)}

    def sessions(self) -> list[int]:
        with self._lock:
            return sorted({r.session for r in self.records})


@dataclasses.dataclass
class CacheRecord:
    """One cache event on the bridge's amortization layer.

    ``event`` is ``"hit"`` (memoized result served), ``"miss"`` (computed
    and stored), ``"dedup"`` (upload short-circuited by content match) or
    ``"invalidate"`` (entry dropped by an overwrite/reclaim). ``saved_s``
    is the execute time a hit avoided (the original run's ``exec_s``);
    ``bytes_saved`` the payload a dedup never moved."""
    session: int
    label: str                    # "library.routine" | "transfer.to_engine"
    event: str                    # hit | miss | dedup | invalidate
    saved_s: float = 0.0
    bytes_saved: int = 0


class CacheLog:
    """Per-session cache accounting — the observability half of the
    content-addressed cache (see ``core/cache.py``). Where TaskLog shows
    what tenants *paid* (wait vs execute), this log shows what the cache
    let them *not pay*: avoided execute seconds and avoided bridge bytes,
    the two costs the paper's amortization argument (§3.2) is about."""

    def __init__(self):
        self.records: list[CacheRecord] = []
        self._lock = locktrace.make_lock("costmodel.cache")

    def record(self, session: int, label: str, event: str,
               saved_s: float = 0.0, bytes_saved: int = 0) -> CacheRecord:
        rec = CacheRecord(session=session, label=label, event=event,
                          saved_s=saved_s, bytes_saved=int(bytes_saved))
        with self._lock:
            self.records.append(rec)
        return rec

    @staticmethod
    def _summarize(recs: list[CacheRecord]) -> dict:
        hits = sum(1 for r in recs if r.event == "hit")
        misses = sum(1 for r in recs if r.event == "miss")
        return {
            "hits": hits,
            "misses": misses,
            "hit_rate": hits / (hits + misses) if hits + misses else 0.0,
            "dedup_uploads": sum(1 for r in recs if r.event == "dedup"),
            "invalidations": sum(1 for r in recs
                                 if r.event == "invalidate"),
            "saved_s": sum(r.saved_s for r in recs),
            "bytes_saved": sum(r.bytes_saved for r in recs),
        }

    def session_summary(self, session: int) -> dict:
        """Hit/miss/dedup counts, hit rate, and saved seconds/bytes for
        one client session."""
        with self._lock:
            recs = [r for r in self.records if r.session == session]
        return {"session": session, **self._summarize(recs)}

    def summary(self) -> dict:
        """Engine-wide totals across every session."""
        with self._lock:
            recs = list(self.records)
        return self._summarize(recs)

    def sessions(self) -> list[int]:
        with self._lock:
            return sorted({r.session for r in self.records})
