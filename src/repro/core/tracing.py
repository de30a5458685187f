"""Host spans of the served path, on the profiler's clock.

Every span is a ``jax.profiler.TraceAnnotation`` named ``alchemist.<name>``:
with no profiler session it costs about a microsecond and records
nothing; under ``jax.profiler.trace`` it lands on the host plane beside
the device planes, so an idle gap on the chip can be laid against the
host work around it. Spans are kept nowhere else.

This module is the one place their names are spelled: the server, the
client, the scheduler and the backends take them from here, and so do
the trace readers and tests (``PREFIX + name`` is the name in a trace).
Spans wrap host code only, never the body of a jitted function or a
Pallas kernel (``repro.analysis`` rule TRC001). Ids are small ints
(``task``, ``session``, ``shards``) or short strings (``path``).
"""
from __future__ import annotations

import jax

PREFIX = "alchemist."

# ---- client (the caller's thread) ----------------------------------------
#: encode one command and its submit round trip (busy retries included)
CLIENT_SUBMIT = "client.submit"
#: block on a submitted task's result
CLIENT_WAIT = "client.wait"
#: one matrix back to the client
CLIENT_FETCH = "client.fetch"
#: one matrix to the engine over a socket; holds the four spans below
CLIENT_UPLOAD = "client.upload"
#: the content hash of the upload-dedup pass
CLIENT_HASH = "client.hash"
#: the dedup round trip
CLIENT_ALIAS_LOOKUP = "client.alias_lookup"
#: writing the chunk frames
CLIENT_STREAM = "client.stream"
#: the commit frame and its reply
CLIENT_COMMIT = "client.commit"

# ---- server (one thread per connection) ----------------------------------
#: one request frame: ``server.<frame>``, the frame's name in
#: ``wire.FRAME_SPECS`` in lower case (``server.command``,
#: ``server.upload_chunk``, ...); see :func:`server_frame`
SERVER = "server."
#: inside ``server.upload_commit``: the chunks joined into one host array
SERVER_ASSEMBLE = "server.assemble"
#: inside ``server.upload_commit``: ``jax.device_put`` and ``engine.put``
SERVER_DEVICE_PUT = "server.device_put"

# ---- scheduler worker and backends ----------------------------------------
#: one task body on a scheduler worker (ids ``task``, ``session``)
TASK = "task"
#: the Lanczos host loop of the jax backend's ``truncated_svd`` (ids
#: ``path``, the Gram matvec's, and ``shards``, the devices X's rows span)
LANCZOS = "lanczos"
#: one Gram matvec: the vector to the device, the program, the vector back
LANCZOS_MATVEC = "lanczos.matvec"
#: the jax backend's ``cg_solve``, whole; holds the four stages below
CG = "cg"
CG_RF_MAP = "cg.rf_map"
CG_RHS = "cg.rhs"
#: one iteration: its dispatch and the host sync on its residual
CG_STEP = "cg.step"
CG_RESIDUAL = "cg.residual"


def span(name: str, **ids: int) -> jax.profiler.TraceAnnotation:
    """The span ``alchemist.<name>``, as a context manager."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **ids)


def server_frame(frame: str) -> str:
    """The span name of request frame ``frame`` (``UPLOAD_COMMIT`` ->
    ``server.upload_commit``)."""
    return SERVER + frame.lower()
