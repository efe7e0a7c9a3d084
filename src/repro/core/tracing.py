"""Named spans of the live service, written into JAX's profiler trace.

``span(name, **args)`` is a ``jax.profiler.TraceAnnotation``. While a
profile is being taken (``jax.profiler.start_trace`` ...
``stop_trace``), each span becomes a host event, with its arguments, on
the same trace and clock as the device's operations, so an operator can
see what the service was doing while the chip sat idle. With no
profile running, entering a span costs about a microsecond and records
nothing. There is no switch: the spans are always in the code, and the
profiler decides whether they are kept.

Arguments are scalars the host already holds: query ids, counts,
level and pool names, and engine-clock seconds (``t``, the
``LiveEngine.now()`` value the program also stamps into ``Query`` and
``StageEvent``). A span never reads a device value, waits for the
device or takes a lock, so a traced engine schedules as an untraced
one does.

The span tree (docs/live.md, "Tracing"):

  repro.service.submit       LiveEngine.submit, the wait for the engine lock included
    repro.coordinator.route    placement of an IMMEDIATE query
  repro.service.poll         one scheduler poll, under the engine lock
    repro.coordinator.route    placement of each released query or fused batch
  repro.executor.wait        a reserved worker with nothing to run
  repro.executor.query       one placement of a query on a pool
    repro.executor.stage       the billed interval of one stage
      repro.stage.inputs         prompt tokens for a prefill
      repro.stage.checkpoint     load of the decode state
      repro.stage.dispatch       the calls into the compiled prefill/decode
      repro.stage.sync           wait for the device (block_until_ready)
      repro.stage.checkpoint     save of the decode state
    repro.executor.boundary    billing, heartbeat, calibration, preempt/rehome
  repro.model.compile        a compile of one (arch, batch) shape
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation


def span(name: str, **args) -> TraceAnnotation:
    """A host span named ``name`` with scalar ``args``; use it as a
    context manager. ``set_metadata(**args)`` on the entered span adds
    arguments known only at its end."""
    return TraceAnnotation(name, **args)
