"""Host spans of the program, on the profiler's clock.

Every span the program opens is a ``jax.profiler.TraceAnnotation`` named
``repro:<name>``: it lands in the trace of any ``jax.profiler`` session
(``jax.profiler.trace`` or ``start_server``) on the thread that opened
it, on the same clock as the device's operations, and records nothing
while no session is active.

Device work is named with ``jax.named_scope`` inside the traced bodies
(``knn``, ``graph``, ``apsp/update``, ``sparse_geodesics/gather``, and
on a mesh ``sparse_embed/replicate`` around the panel's all-gathers, ...):
the scope travels with each operation's HLO metadata (``op_name``), so a
device trace attributes every kernel and fusion to the stage that
launched it.  Span names:

* ``fit``, ``stage:<stage name>``, ``checkpoint`` - ``ManifoldPipeline.run``;
* ``landmarks:cover``, ``landmarks:fill`` - the two phases of
  ``hierarchical_landmarks`` (host work inside ``stage:landmarks``);
* ``serve:coalesce``, ``serve:pack``, ``serve:map``, ``serve:fetch``,
  ``serve:reply``, ``serve:absorb`` - ``BatchedMapperService``;
* ``map:put`` - the host-to-device copy in ``StreamingMapper.__call__``.
"""
from __future__ import annotations

import jax

#: prefix of every host span the program opens
PREFIX = "repro:"


def span(name: str) -> jax.profiler.TraceAnnotation:
    """Context manager opening the host span ``repro:<name>``."""
    return jax.profiler.TraceAnnotation(PREFIX + name)
