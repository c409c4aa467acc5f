"""Trace & profiling hooks: named scopes + the ``--trace N`` chunk capture.

``annotate(name)`` is a host-side ``jax.profiler.TraceAnnotation`` that
degrades to a no-op when the profiler is unavailable — it marks the
wall-clock extent of host work (chunk dispatch, checkpoint save, replay
callbacks) in a captured trace. Traced (in-program) scopes use
``jax.named_scope`` directly at the call sites.

``TraceCapture`` implements the ``ObsSpec.trace = N`` mode: the first
``begin()`` starts a ``jax.profiler`` trace into ``<log_dir>/trace/``, each
``end()`` counts one completed chunk, and the capture stops after ``N``
chunks (or at ``finish()``, whichever comes first). Profiler failures —
platforms without a profiler backend — are swallowed and reported through
``status`` instead of killing the run: tracing is a diagnostic, never a
correctness dependency.
"""
from __future__ import annotations

import contextlib
from pathlib import Path
from typing import Iterator, Optional

import jax


@contextlib.contextmanager
def annotate(name: str) -> Iterator[None]:
    """Host-side profiler annotation; no-op when the profiler is absent."""
    try:
        ctx = jax.profiler.TraceAnnotation(name)
    except Exception:                            # pragma: no cover
        yield
        return
    with ctx:
        yield


class TraceCapture:
    """Capture a ``jax.profiler`` trace of the first ``n_chunks`` chunks.

    status: "idle" (n_chunks == 0) | "active" | "done" | "failed: <err>".
    """

    def __init__(self, n_chunks: int, trace_dir: str):
        self.n_chunks = int(n_chunks)
        self.trace_dir = str(trace_dir)
        self.remaining = self.n_chunks
        self.active = False
        self.status = "idle" if self.n_chunks == 0 else "pending"
        self._error: Optional[str] = None

    def begin(self) -> None:
        """Start the trace at the first chunk; later calls are no-ops."""
        if self.status != "pending" or self.active:
            return
        try:
            Path(self.trace_dir).mkdir(parents=True, exist_ok=True)
            # the Python tracer's per-call events slow the host several-fold
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            self.active = True
            self.status = "active"
        except Exception as e:                   # pragma: no cover
            self.status = f"failed: {e}"

    def end(self) -> None:
        """Count one completed chunk; stop after ``n_chunks``."""
        if not self.active:
            return
        self.remaining -= 1
        if self.remaining <= 0:
            self._stop()

    def finish(self) -> None:
        """Force-stop (run ended before ``n_chunks`` chunks completed)."""
        if self.active:
            self._stop()

    def _stop(self) -> None:
        try:
            jax.profiler.stop_trace()
            self.status = "done"
        except Exception as e:                   # pragma: no cover
            self.status = f"failed: {e}"
        self.active = False
