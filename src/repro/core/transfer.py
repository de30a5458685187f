"""The streaming transfer layer: client row-partitioned matrices <->
engine-resident distributed matrices (the paper's TCP-socket path, §3.2).

The paper never ships a matrix in one message: each Spark executor opens a
socket to each Alchemist worker and streams its rows in buffered sends,
which the workers scatter into the Elemental DistMatrix layout. This module
mirrors that: a matrix crosses the bridge as a sequence of row-block
*chunks*. A RowMatrix source is consumed partition-by-partition (peak
client memory is one partition plus one chunk, never the whole matrix),
each chunk is ``device_put`` directly onto the engine device that owns its
row range, and each chunk logs its own
:class:`~repro.core.costmodel.TransferRecord`, so the transfer log sees
the same per-message structure the real sockets have.

Chunk sizing uses the *source's actual dtype* (``RowMatrix.dtype`` is
tracked client-side exactly for this): a float32 matrix has half the
row-bytes of a float64 one, so assuming 8-byte elements — as this layer
once did — doubles chunk sizes.

**Upload dedup** (``dedup=True``, the default): the matrix's bytes are
digested in row-major order (chunk-boundary invariant — the same bytes
dedup whatever ``chunk_rows`` carried them) and the fingerprint is looked
up in the engine's store index. A re-upload of already-resident content —
the repeated-tenant case of the Cray deployment report — never streams:
the engine mints a handle *alias* over the existing store, and the log
records a zero-byte crossing (``TransferRecord.dedup``) with the avoided
payload in ``logical_nbytes``.

The pre-stream hash pass walks the source once more than a plain upload —
cheap for ndarrays (slices are views) and *cached* RowMatrix RDDs
(partitions memoized), which is when it runs. An **uncached** RDD source
(e.g. a bare ``map_rows``) is consumed exactly once: re-iterating it
would recompute every partition, and a nondeterministic lineage need not
even reproduce the bytes the fingerprint was built from — so such uploads
skip the pre-stream lookup and hash inline *during* streaming instead:
the registered fingerprint always matches the bytes that actually
crossed, and later uploads of equal content still dedup against it. Pass
``dedup=False`` to skip hashing entirely.
"""
from __future__ import annotations

import bisect
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import cache as caching, tracing
from repro.core.costmodel import TransferRecord
from repro.core.engine import SYSTEM_SESSION, AlchemistEngine
from repro.core.handles import MatrixHandle
from repro.frontend.rowmatrix import RowMatrix

# Default chunk size target, in bytes: roughly the socket-buffer ballpark
# the Cray deployment report tunes around. Row counts are derived from it
# per-matrix so a chunk is a whole number of rows.
DEFAULT_CHUNK_BYTES = 4 << 20


def chunk_rows_for(shape, itemsize: int,
                   chunk_bytes: int = DEFAULT_CHUNK_BYTES) -> int:
    """Rows per chunk so a chunk is ~``chunk_bytes`` (at least one row).
    ``itemsize`` must be the source's real element size — see the float32
    note in the module docstring."""
    row_bytes = max(1, int(np.prod(shape[1:])) * itemsize)
    return max(1, chunk_bytes // row_bytes)


def _row_plan(num_rows: int, chunk_rows: int,
              boundaries: list[int]) -> list[tuple[int, int]]:
    """Split ``[0, num_rows)`` into chunks of ``chunk_rows``, additionally
    cut at every device shard boundary so no chunk straddles two shards."""
    chunk_rows = max(1, int(chunk_rows))
    cuts = {0, num_rows}
    cuts.update(b for b in boundaries if 0 < b < num_rows)
    cuts.update(range(0, num_rows, chunk_rows))
    edges = sorted(cuts)
    return list(zip(edges, edges[1:]))


def _device_row_ranges(sharding, shape) -> list[tuple[int, int, Any]]:
    """Read the row range each device owns straight off the sharding
    (single source of truth — never re-derive the engine's layout rule).
    Returns [(lo, hi, device)] sorted by lo."""
    ranges = []
    for dev, idx in sharding.devices_indices_map(tuple(shape)).items():
        sl = idx[0] if idx else slice(None)
        lo = 0 if sl.start is None else int(sl.start)
        hi = shape[0] if sl.stop is None else int(sl.stop)
        ranges.append((lo, hi, dev))
    ranges.sort(key=lambda r: (r[0], r[1]))
    return ranges


def row_reader(arr):
    """``read(lo, hi)``: rows ``lo:hi`` of a device array, on the host.

    Where whole rows lie in blocks over several devices (the row-block
    layout, or a full copy on each), each device's block crosses to the
    host once, whole, when the first chunk reaches it; chunks are read in
    row order, so one block is held at a time. Sliced on the device
    instead, each chunk shape of a sharded array compiles a gather
    program of its own (10-15 s each on a v5e 2x2). One device, or
    blocks of columns, slice on the device."""
    blocks = _row_blocks(arr)
    if blocks is None:
        return lambda lo, hi: np.asarray(arr[lo:hi])
    held: dict[int, np.ndarray] = {}

    def read(lo: int, hi: int) -> np.ndarray:
        parts = []
        for first, last, shard in blocks:
            if first < hi and lo < last:
                if first not in held:
                    held.clear()
                    held[first] = np.asarray(shard.data)
                parts.append(held[first][max(lo, first) - first:
                                         min(hi, last) - first])
        return parts[0] if len(parts) == 1 else np.concatenate(parts)
    return read


def _row_blocks(arr) -> Optional[list[tuple[int, int, Any]]]:
    """``[(first row, end row, shard)]``, one shard per distinct block of
    whole rows, where ``arr`` spans several devices and no device holds
    part of a row; None otherwise."""
    sharding = getattr(arr, "sharding", None)
    if sharding is None or arr.ndim < 1 or len(sharding.device_set) < 2 \
            or sharding.shard_shape(arr.shape)[1:] != arr.shape[1:]:
        return None
    shards = {s.device: s for s in arr.addressable_shards}
    blocks = {}
    for lo, hi, device in _device_row_ranges(sharding, arr.shape):
        blocks.setdefault(lo, (lo, hi, shards[device]))
    return list(blocks.values())


def _aggregate_record(nbytes: int, direction: str, session: int,
                      num_chunks: int) -> TransferRecord:
    """Whole-stream summary record (returned to the caller, NOT logged —
    the log carries the per-chunk records). ``chunk_index=-1`` marks it as
    an aggregate."""
    return TransferRecord(
        nbytes=int(nbytes),
        direction=direction,
        session=session,
        chunk_index=-1,
        num_chunks=num_chunks,
    )


def to_engine(engine: AlchemistEngine, matrix, name: Optional[str] = None,
              session: int = SYSTEM_SESSION,
              chunk_rows: Optional[int] = None,
              dedup: bool = True
              ) -> tuple[MatrixHandle, TransferRecord]:
    """Stream a client matrix into the engine in row-block chunks (§3.2).

    Accepts a RowMatrix (the IndexedRowMatrix analogue; consumed
    partition-by-partition without collecting) or a plain array. The
    matrix crosses as ``ceil(rows / chunk_rows)`` chunks (plus cuts at
    shard boundaries); each is ``device_put`` onto the engine device
    owning its row range and logged as its own TransferRecord tagged with
    ``session`` and its chunk index. ``chunk_rows=None`` picks rows so a
    chunk is ~``DEFAULT_CHUNK_BYTES`` — sized by the source's actual
    dtype, never an assumed float64.

    With ``dedup`` (default), the chunks are content-hashed first and a
    re-upload of already-resident content short-circuits to a handle
    alias with a zero-byte logged crossing (see module docstring).

    Returns ``(handle, aggregate record)`` — the record summarizes the
    whole stream (total bytes, chunk count);
    the per-chunk records live in ``engine.transfer_log``.

    A ``jax.Array`` input is already device-resident (an engine-side
    service handing over data, not a socket crossing) and takes the
    direct re-layout path: one ``device_put``, one record, no host
    round trip (and no content hashing).

    ``engine`` may also be a :class:`~repro.core.wire.SocketBridge`: the
    same chunk plan then crosses as real frames to a remote engine
    server, and the returned record additionally carries the measured
    ``wire_nbytes``.
    """
    if not isinstance(engine, AlchemistEngine):
        with tracing.span(tracing.CLIENT_UPLOAD, session=session):
            return _to_engine_bridge(engine, matrix, name=name,
                                     session=session, chunk_rows=chunk_rows,
                                     dedup=dedup)
    if isinstance(matrix, jax.Array):
        arr = jax.device_put(matrix, engine.dist_sharding(matrix.shape))
        rec = engine.transfer_log.record(arr.nbytes, "to_engine",
                                         session=session)
        return engine.put(arr, name=name, session=session), rec

    is_rm = isinstance(matrix, RowMatrix)
    if is_rm:
        shape = matrix.shape
        dtype = matrix.dtype      # lazily derived from partition 0
        src = None
    else:
        src = np.asarray(matrix)
        shape = src.shape
        dtype = src.dtype
    itemsize = dtype.itemsize

    if len(shape) < 1 or shape[0] == 0:
        arr = jnp.asarray(matrix.collect() if is_rm else src)
        arr = jax.device_put(arr, engine.dist_sharding(arr.shape))
        rec = engine.transfer_log.record(arr.nbytes, "to_engine",
                                         session=session)
        return engine.put(arr, name=name, session=session), rec

    if chunk_rows is None:
        chunk_rows = chunk_rows_for(shape, itemsize)
    chunk_rows = max(1, int(chunk_rows))
    sharding = engine.dist_sharding(shape)

    # Read placement off the sharding itself: which device owns which
    # rows. Row-partitioned iff the per-device ranges tile [0, rows);
    # otherwise (replicated) stage every chunk on the first device and
    # let the final device_put broadcast.
    ranges = _device_row_ranges(sharding, shape)
    starts = [lo for lo, _, _ in ranges]
    partitioned = (starts[0] == 0 and ranges[-1][1] == shape[0]
                   and all(ranges[i][1] == ranges[i + 1][0]
                           for i in range(len(ranges) - 1)))
    boundaries = starts[1:] if partitioned else []
    plan = _row_plan(shape[0], chunk_rows, boundaries)
    num_chunks = len(plan)

    def chunk_stream():
        if is_rm:
            return matrix.iter_sized_row_blocks(
                [hi - lo for lo, hi in plan])
        return (src[lo:hi] for lo, hi in plan)

    # Pre-stream dedup lookup only for sources that are cheap AND safe to
    # iterate twice; uncached RDD lineages hash inline during streaming
    # (see module docstring).
    fingerprint = None
    inline_hasher = None
    if dedup and (not is_rm or matrix.rdd.cached):
        # hash pass: cheap client-side digest before paying the bridge.
        # The fingerprint is chunk-boundary invariant, so digest the raw
        # memoized partitions directly (no re-running the chunk plan's
        # cross-partition concatenation); an ndarray is digested in
        # row-slice pieces — views for C-order sources, and for strided
        # ones at most a chunk-sized copy at a time, never a whole-matrix
        # staging buffer.
        hasher = caching.ContentHasher(shape, dtype)
        logical = 0
        pieces = (matrix.rdd.partition(i)
                  for i in range(matrix.rdd.num_partitions)) \
            if is_rm else (src[lo:hi] for lo, hi in plan)
        for piece in pieces:
            piece = np.asarray(piece)
            hasher.update(piece)
            logical += piece.nbytes
        fingerprint = hasher.fingerprint()
        alias = engine.alias_by_fingerprint(fingerprint, shape,
                                           session=session, name=name)
        if alias is not None:
            rec = engine.transfer_log.record_dedup(
                logical, "to_engine", session=session,
                num_chunks=num_chunks)
            engine.cache_log.record(session, "transfer.to_engine",
                                    "dedup", bytes_saved=logical)
            return alias, rec
    elif dedup:
        inline_hasher = caching.ContentHasher(shape, dtype)

    per_range: list[list[jax.Array]] = [[] for _ in ranges]
    total = 0
    for idx, ((lo, hi), chunk) in enumerate(zip(plan, chunk_stream())):
        chunk = np.ascontiguousarray(chunk)
        if inline_hasher is not None:
            inline_hasher.update(chunk)
        total += chunk.nbytes
        engine.transfer_log.record(
            chunk.nbytes, "to_engine", session=session,
            chunk_index=idx, num_chunks=num_chunks)
        r = bisect.bisect_right(starts, lo) - 1 if partitioned else 0
        per_range[r].append(jax.device_put(chunk, ranges[r][2]))

    shards = [blocks[0] if len(blocks) == 1 else jnp.concatenate(blocks)
              for blocks in per_range if blocks]
    if partitioned and len(ranges) > 1:
        arr = jax.make_array_from_single_device_arrays(
            tuple(shape), sharding, shards)
    else:
        arr = jax.device_put(shards[0], sharding)
    if inline_hasher is not None:
        fingerprint = inline_hasher.fingerprint()
    rec = _aggregate_record(total, "to_engine", session, num_chunks)
    return engine.put(arr, name=name, session=session,
                      fingerprint=fingerprint), rec


def _to_engine_bridge(bridge, matrix, name: Optional[str],
                      session: int, chunk_rows: Optional[int],
                      dedup: bool) -> tuple[MatrixHandle, TransferRecord]:
    """``to_engine`` over a :class:`~repro.core.wire.SocketBridge`: the
    same chunk plan and the same dedup rules, carried by real frames.

    Differences from the in-process path are exactly the ones a socket
    forces: chunks are cut purely by ``chunk_rows`` (the client cannot
    see the remote mesh's shard boundaries — the server re-lays the
    assembled matrix out itself), and a device-resident ``jax.Array``
    cannot be handed over by reference, so it crosses as one whole-
    matrix frame (still a single logged record, like the in-memory
    direct path). Content fingerprints are computed client-side with the
    same chunk-boundary-invariant hash, so uploads dedup across bridges.
    """
    if isinstance(matrix, jax.Array):
        src = np.asarray(matrix)
        return bridge.upload(src.shape, src.dtype, [src],
                             session=session, name=name, single=True)

    is_rm = isinstance(matrix, RowMatrix)
    if is_rm:
        shape = matrix.shape
        dtype = matrix.dtype
        src = None
    else:
        src = np.asarray(matrix)
        shape = src.shape
        dtype = src.dtype

    if len(shape) < 1 or shape[0] == 0:
        arr = np.asarray(matrix.collect() if is_rm else src)
        return bridge.upload(arr.shape, arr.dtype, [arr],
                             session=session, name=name, single=True)

    if chunk_rows is None:
        chunk_rows = chunk_rows_for(shape, dtype.itemsize)
    plan = _row_plan(shape[0], chunk_rows, [])
    num_chunks = len(plan)

    def chunk_stream():
        if is_rm:
            return matrix.iter_sized_row_blocks([hi - lo for lo, hi in plan])
        return (src[lo:hi] for lo, hi in plan)

    fingerprint = None
    inline_hasher = None
    if dedup and (not is_rm or matrix.rdd.cached):
        hasher = caching.ContentHasher(shape, dtype)
        logical = 0
        pieces = (matrix.rdd.partition(i)
                  for i in range(matrix.rdd.num_partitions)) \
            if is_rm else (src[lo:hi] for lo, hi in plan)
        with tracing.span(tracing.CLIENT_HASH, session=session):
            for piece in pieces:
                piece = np.asarray(piece)
                hasher.update(piece)
                logical += piece.nbytes
            fingerprint = hasher.fingerprint()
        hit = bridge.alias_lookup(fingerprint, shape, session, name,
                                  logical, num_chunks)
        if hit is not None:
            return hit
    elif dedup:
        inline_hasher = caching.ContentHasher(shape, dtype)

    def hashed_chunks():
        for chunk in chunk_stream():
            chunk = np.ascontiguousarray(chunk)
            if inline_hasher is not None:
                inline_hasher.update(chunk)
            yield chunk

    fp = fingerprint if inline_hasher is None \
        else (lambda: inline_hasher.fingerprint())
    return bridge.upload(shape, dtype, hashed_chunks(), session=session,
                         name=name, num_chunks=num_chunks, fingerprint=fp)


def to_client(engine: AlchemistEngine, handle: MatrixHandle,
              num_partitions: int = 8, session: Optional[int] = None,
              chunk_rows: Optional[int] = None
              ) -> tuple[RowMatrix, TransferRecord]:
    """Stream an engine matrix back to the client as a RowMatrix (§3.2,
    reverse direction — the paper's ``toIndexedRowMatrix()``).

    The fetch crosses in row-block chunks, one TransferRecord per chunk
    plus an aggregate record returned to the caller; ``session`` applies
    the same namespace check as routine dispatch.

    Chunks land *directly in the per-partition blocks* backing the
    returned RowMatrix (the chunk plan is additionally cut at partition
    boundaries so no chunk straddles two blocks): beyond the result's own
    storage, peak host allocation is one chunk — never a whole-matrix
    staging buffer.

    Over a :class:`~repro.core.wire.SocketBridge` the same chunks arrive
    as FETCH frames and land in the same per-partition blocks.
    """
    if not isinstance(engine, AlchemistEngine):
        with tracing.span(tracing.CLIENT_FETCH,
                          session=SYSTEM_SESSION if session is None
                          else session):
            return _to_client_bridge(engine, handle, num_partitions,
                                     session=session, chunk_rows=chunk_rows)
    arr = engine.get(handle, session=session)
    sess = SYSTEM_SESSION if session is None else session
    if arr.ndim < 1 or arr.shape[0] == 0:
        rec = engine.transfer_log.record(arr.nbytes, "to_client",
                                         session=sess)
        return RowMatrix.from_array(np.asarray(arr), num_partitions), rec

    if chunk_rows is None:
        chunk_rows = chunk_rows_for(arr.shape, arr.dtype.itemsize)
    chunk_rows = max(1, int(chunk_rows))
    rows = arr.shape[0]
    num_partitions = max(1, min(num_partitions, rows))
    # partition bounds exactly as np.array_split (what from_array used):
    # the first rows % P partitions carry one extra row
    base, extra = divmod(rows, num_partitions)
    psizes = [base + (1 if i < extra else 0) for i in range(num_partitions)]
    pstarts = [0]
    for s in psizes:
        pstarts.append(pstarts[-1] + s)

    plan = _row_plan(rows, chunk_rows, pstarts[1:-1])
    blocks: list[Optional[np.ndarray]] = [None] * num_partitions
    total = 0
    read = row_reader(arr)
    for idx, (lo, hi) in enumerate(plan):
        block = read(lo, hi)
        p = bisect.bisect_right(pstarts, lo) - 1
        if blocks[p] is None:
            blocks[p] = np.empty((psizes[p],) + tuple(arr.shape[1:]),
                                 dtype=arr.dtype)
        blocks[p][lo - pstarts[p]: hi - pstarts[p]] = block
        total += block.nbytes
        engine.transfer_log.record(
            block.nbytes, "to_client", session=sess,
            chunk_index=idx, num_chunks=len(plan))
    rec = _aggregate_record(total, "to_client", sess, len(plan))
    return RowMatrix.from_blocks(blocks), rec


def _to_client_bridge(bridge, handle: MatrixHandle, num_partitions: int,
                      session: Optional[int], chunk_rows: Optional[int]
                      ) -> tuple[RowMatrix, TransferRecord]:
    """``to_client`` over a socket: one FETCH request, a stream of chunk
    frames written straight into the per-partition blocks (same
    peak-memory property as the in-process path), and the server's
    aggregate record — including measured wire bytes — from the END
    frame."""
    state: dict = {}

    def on_meta(meta):
        state["meta"] = meta
        if meta["whole"]:
            return
        psizes = meta["psizes"]
        pstarts = [0]
        for s in psizes:
            pstarts.append(pstarts[-1] + s)
        state["psizes"] = psizes
        state["pstarts"] = pstarts
        state["blocks"] = [None] * len(psizes)
        state["dtype"] = np.dtype(meta["dtype"])
        state["tail"] = tuple(meta["shape"][1:])

    def on_chunk(lo, hi, block):
        meta = state["meta"]
        if meta["whole"]:
            state["whole_array"] = block
            return
        pstarts = state["pstarts"]
        blocks = state["blocks"]
        p = bisect.bisect_right(pstarts, lo) - 1
        if blocks[p] is None:
            blocks[p] = np.empty(
                (state["psizes"][p],) + state["tail"],
                dtype=state["dtype"])
        blocks[p][lo - pstarts[p]: hi - pstarts[p]] = block

    # session passes through verbatim: None keeps its in-process meaning
    # (trusted global lookup) so both bridges resolve identically
    rec = bridge.fetch(handle, session=session, chunk_rows=chunk_rows,
                       num_partitions=num_partitions,
                       on_meta=on_meta, on_chunk=on_chunk)
    meta = state["meta"]
    if meta["whole"]:
        return RowMatrix.from_array(state["whole_array"],
                                    meta.get("num_partitions", 8)), rec
    return RowMatrix.from_blocks(state["blocks"]), rec
