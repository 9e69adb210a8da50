"""Names for the profiler: host spans and device scopes.

Every span and scope of the program comes from here, rooted at
``repro.``, so a trace of any run (``jax.profiler.trace``) can be read
by name:

* :func:`span` marks host work.  It is a
  ``jax.profiler.TraceAnnotation``, so it lands in the profiler's own
  trace, on the clock of the device planes; its counts travel as the
  event's stats.  With no profiler session it costs one cheap call and
  keeps nothing.
* :func:`scope` names device work.  It is a ``jax.named_scope``, so it
  lands in the ``op_name`` metadata of every op traced inside it and
  costs nothing at run time.

The names in use:

==============================  ==========================================
``repro.comm.<engine>``         one collective, by the engine dispatch
                                picked (``core/comm.py``)
``.../intra``, ``.../inter``    its phases inside and across nodes
                                (``core/collectives.py``)
``repro.grad_sync.bucket<i>``   one gradient bucket (``core/grad_sync.py``)
``repro.train.fwd_bwd``,        the training step's parts
``.grad_sync``, ``.loss_sync``, (``launch/steps.py``)
``.optimizer``
``repro.serve.admit``,          host spans of one ``ServeEngine.step``
``.prefill``, ``.slice``,       (``serve/engine.py``)
``.wait``, ``.record``
==============================  ==========================================
"""

from __future__ import annotations

import jax

__all__ = ["span", "scope"]

ROOT = "repro."


def span(name: str, **counts: int | str):
    """Host span ``repro.<name>``; ``counts`` (numbers or labels) become
    the event's stats (``span("serve.prefill", bucket=256, prompt=100,
    mode="parallel")``).  More can be added once known with
    ``set_metadata(**counts)``."""
    return jax.profiler.TraceAnnotation(ROOT + name, **counts)


def scope(name: str, *, phase: bool = False):
    """Device scope ``repro.<name>``; a ``phase`` scope keeps its bare
    name and reads as a part of the scope around it
    (``repro.comm.nap/inter``)."""
    return jax.named_scope(name if phase else ROOT + name)
