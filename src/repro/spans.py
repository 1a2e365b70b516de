"""Program spans on the profiler's clock.

``span(name, **ids)`` marks a stretch of host work as a
``jax.profiler.TraceAnnotation``.  With a profile running
(``jax.profiler.trace``) the span lands on the host plane of the same trace
as the device's operations, on one clock, so an idle stretch of the device
can be read against what the host was doing in it; ``ids`` (a round or
commit number) are kept as the event's metadata.  With no profile running
a span costs about a microsecond, so spans are always on.

Names, outermost first:

* ``fl.round`` (``round=``) with ``fl.round.simulate``, ``.data``,
  ``.dispatch``, ``.fetch`` and ``.account``: one synchronous round
  (``orchestrator/server.py``);
* ``fl.async.dispatch``, ``.train``, ``.commit`` and ``.host_sync``: the
  phases of the asynchronous server, the same ones ``CommitLog.phase_wall``
  times (``orchestrator/async_server.py``).

The device side carries the matching ``jax.named_scope`` names in each
operation's ``op_name``: ``fl.local_train``, ``fl.commit``,
``fl.commit.pack``, ``fl.commit.unpack`` and ``fl.server_step``; Pallas
kernels are named ``fl_<kernel>``.
"""
from __future__ import annotations

import jax


def span(name: str, **ids):
    """A host span ``name`` on the profiler's clock, ``ids`` as metadata."""
    return jax.profiler.TraceAnnotation(name, **ids)
