"""Opt-in ``jax.profiler`` capture for a configured round window — the
one ``jax.profiler.start_trace`` site of the package.

``server_config.telemetry.profile_rounds`` names the window — an int
(``5``: profile the chunk containing round 5), a ``"lo:hi"`` string, or
a two-element list — and the server calls :meth:`RoundProfiler.observe`
at every chunk boundary.  The capture starts at the first chunk whose
round range reaches ``lo`` and stops at the first boundary at or past
``hi``, so a fused chunk spanning the window edge profiles whole (the
profiler cannot cut a compiled program in half).  The reference's
``do_profiling`` flag is an alias: the server turns it into the window
of the second chunk (or the only one) and drives the same object, with
or without a telemetry scope.

The capture runs with the Python call tracer off (it would slow the
host it observes) and carries one clock mark: a
``TraceAnnotation("flute_clock_sync")`` in the ``.xplane.pb`` and, when
a tracer is attached, an instant of the same name in ``events.jsonl``
whose ``epoch_s`` was taken just before the annotation — the offset
between the two puts host spans and device events on one clock.

A capture that cannot start (``jax.profiler`` allows one trace per
process; an outer harness may hold it) logs one warning and disables
the window instead of killing the run.
"""

from __future__ import annotations

import logging
import time
from typing import Any, Callable, Optional, Tuple

_LOGGER = logging.getLogger("msrflute_tpu")

#: the clock mark's name, in the profiler's trace and in events.jsonl
CLOCK_SYNC = "flute_clock_sync"


def parse_profile_rounds(spec: Any) -> Optional[Tuple[int, int]]:
    """``None`` | int | ``"lo:hi"`` | [lo, hi] -> half-open round window
    ``(lo, hi)`` or None.  Raises ValueError on garbage (the schema calls
    this too, so a bad spec fails at config load, not round ``lo``)."""
    if spec is None:
        return None
    if isinstance(spec, bool):
        raise ValueError("telemetry.profile_rounds: must be an int, "
                         "'lo:hi', or [lo, hi] — got a boolean")
    if isinstance(spec, int):
        return (spec, spec + 1)
    if isinstance(spec, str):
        if ":" not in spec:
            raise ValueError(
                f"telemetry.profile_rounds: {spec!r} is not 'lo:hi'")
        lo_s, hi_s = spec.split(":", 1)
        lo, hi = int(lo_s), int(hi_s)
    elif isinstance(spec, (list, tuple)) and len(spec) == 2:
        lo, hi = int(spec[0]), int(spec[1])
    else:
        raise ValueError(
            f"telemetry.profile_rounds: {spec!r} must be an int, "
            "'lo:hi', or [lo, hi]")
    if lo < 0 or hi <= lo:
        raise ValueError(
            f"telemetry.profile_rounds: window [{lo}, {hi}) is empty or "
            "negative")
    return (lo, hi)


class RoundProfiler:
    """Drives one ``jax.profiler`` trace over the configured window."""

    def __init__(self, spec: Any, out_dir: str, tracer=None):
        self.window = parse_profile_rounds(spec)
        self.out_dir = out_dir
        self.tracer = tracer
        self.active = False
        self.captured = False
        self.failed = False

    def observe(self, round_no: int, rounds: int = 1,
                fence: Optional[Callable[[], None]] = None) -> None:
        """Chunk-boundary hook: the chunk about to dispatch covers
        ``[round_no, round_no + rounds)``.  The capture starts when that
        range INTERSECTS the window — not only when it starts exactly at
        ``lo`` — so a window falling inside a fused chunk still fires
        (the chunk profiles whole; a compiled program cannot be cut).
        ``fence`` waits for the device work dispatched so far; it is
        called once, before the capture stops, so that the window's last
        chunk is in it."""
        if self.window is None or self.failed or self.captured:
            if self.active:
                self._stop(fence)
            return
        lo, hi = self.window
        if self.active and round_no >= hi:
            self._stop(fence)
        elif not self.active and round_no < hi and round_no + max(
                int(rounds), 1) > lo:
            self._start()

    def finish(self, fence: Optional[Callable[[], None]] = None) -> None:
        """Train-exit hook: a window still open (run ended inside it)
        stops here so the capture is flushed."""
        if self.active:
            self._stop(fence)

    # ------------------------------------------------------------------
    def _start(self) -> None:
        import jax
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        try:
            jax.profiler.start_trace(self.out_dir,
                                     profiler_options=options)
        except RuntimeError as exc:
            self.failed = True
            _LOGGER.warning(
                "flutescope: jax.profiler capture could not start (%s); "
                "telemetry.profile_rounds disabled for this run", exc)
            return
        self.active = True
        epoch_s = time.time()
        with jax.profiler.TraceAnnotation(CLOCK_SYNC):
            pass
        if self.tracer is not None:
            self.tracer.instant(CLOCK_SYNC, epoch_s=epoch_s)
        _LOGGER.info("flutescope: jax.profiler capture started -> %s",
                     self.out_dir)

    def _stop(self, fence: Optional[Callable[[], None]] = None) -> None:
        import jax
        self.active = False
        if fence is not None:
            fence()
        try:
            jax.profiler.stop_trace()
        except RuntimeError as exc:
            self.failed = True
            _LOGGER.warning(
                "flutescope: jax.profiler capture could not stop (%s)", exc)
            return
        self.captured = True
        _LOGGER.info("flutescope: jax.profiler capture written to %s",
                     self.out_dir)
