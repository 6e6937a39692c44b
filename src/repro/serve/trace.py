"""Named host spans of the serving path, on the profiler's clock.

``span(name)`` marks one step of the host work around a launch.  While a
JAX profiler session records (``jax.profiler.start_trace`` or
``jax.profiler.trace``), a span is a ``jax.profiler.TraceAnnotation``:
an event on the host plane of the same ``.xplane.pb`` that holds the
device's operations, on one nanosecond clock, so a device-idle gap can be
put down to the step the host was in.  Otherwise a span costs one test of
the profiler's state: no annotation is built and no clock is read.

The profiler is both the switch and the exporter: there is no flag, no
environment variable and no host-side total.  ``LaunchRecord.measured``
still times each whole launch for the cost model and the watchdog; the
spans split that wall (and the mux's own work around it) into its steps.

The six spans are leaves: none contains another, so their totals add.

  serve.mux.admit     ``SolverMux.submit``: the arguments to arrays, the
                      finite admission scan, enqueue, the tuner's note
  serve.mux.stack     ``SolverMux._begin``: variant resolve, rider
                      embedding, one ``np.stack`` per argument, filler
                      padding (``pad_group``); on a mesh, the launch's
                      placement and the cost it holds on its shard
  serve.core.copy_in  ``EngineCore._timed_call``: each padded plane to a
                      device array (``jnp.asarray``; on a mesh,
                      ``jax.device_put`` straight from the host, to the
                      placed shard's device or, for a spanning launch,
                      to every shard's slab through the mesh's
                      ``NamedSharding``)
  serve.core.execute  the call of the jitted entry point until it
                      returns (dispatch, not the kernel's completion),
                      and the start of the answer's copy back to the host
  serve.core.copy_out ``EngineCore._gather``: ``np.asarray`` of the
                      answer, waiting for whatever of the kernel and the
                      copy back has not finished yet
  serve.mux.finish    ``SolverMux._supervise`` once an answer is on the
                      host: the per-lane finite check, ``record_launch``,
                      ``observe_launch``, scatter with ``record_job`` per
                      job, the watchdog and the ``flush`` event

A launch's three ``serve.core`` spans lie inside the wall that
``LaunchRecord.measured`` takes, so together they come to that wall.
They do not always run back to back.  In a bucket flush of several
launches (``SolverMux._launch_chunks``: lane groups, or on a mesh
spanning units and per-shard groups) launch k+1's stack, copy_in and
execute come before launch k's copy_out and finish, so k's copy back
lands while the host prepares k+1; ``measured`` leaves k+1's steps out.
A flush of one launch, a mux with a fault injector, and the overload
policy's rounds keep the order above, launch by launch.

To see them for a running server::

    jax.profiler.start_trace("/tmp/serve-trace")
    ...                                  # submit and poll as usual
    jax.profiler.stop_trace()

then open the directory in TensorBoard's profile plugin (xprof), or pass
``create_perfetto_trace=True`` to ``start_trace`` and load the
``perfetto_trace.json.gz`` it writes in Perfetto.
"""
from __future__ import annotations

import contextlib

from jax.profiler import TraceAnnotation

_OFF = contextlib.nullcontext()


def span(name: str):
    """A context manager marking ``name`` in the profiler's trace while
    a profiler session records, else a shared no-op."""
    if TraceAnnotation.is_enabled():
        return TraceAnnotation(name)
    return _OFF
