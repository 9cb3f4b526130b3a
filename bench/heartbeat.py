"""Heartbeat spool between a section child and the parent watchdog.

The failure mode that loses a whole round is a *wedge*, not a crash: a
kernel compile or H2D transfer that never returns. A wall-clock
timeout alone forces an impossible trade-off (short enough to catch
the wedge = short enough to kill a legitimately slow section).
Heartbeats resolve it: the child appends one line per unit
of real progress (section / kernel / batch currently running) to a
spool file, and the parent kills on *heartbeat silence* — progress
stalls are detected in BENCH_HEARTBEAT_TIMEOUT seconds no matter how
generous the wall-clock budget is.

Protocol: one line per beat, ``<unix_ts> <section> <detail>\\n``,
appended and flushed. The parent only ever needs the file *size* (any
growth = liveness) plus the last line for the kill diagnostic, so a
torn final line is harmless.

Startup is special-cased: a section child's first beat is written only
after its imports (for jax sections: after the backend came up), so
the watchdog applies ``TENDERMINT_TPU_PROBE_TIMEOUT`` as the
first-beat deadline, keeping a backend that wedges at start-up from
burning a whole section timeout.
"""

from __future__ import annotations

import os
import time
from typing import Callable, Optional

HEARTBEAT_FILE_ENV = "BENCH_HEARTBEAT_FILE"


class HeartbeatWriter:
    """Child side: append-and-flush progress lines to the spool file.

    Degrades to a no-op when the parent did not provide a spool path
    (section body invoked directly, e.g. from a test), so section code
    can beat unconditionally.
    """

    def __init__(self, section: str, path: Optional[str] = None):
        self.section = section
        self.path = path if path is not None else os.environ.get(HEARTBEAT_FILE_ENV)
        self.beats = 0

    def __call__(self, detail: str = "") -> None:
        self.beats += 1
        if not self.path:
            return
        try:
            with open(self.path, "a") as f:
                f.write(
                    "%.3f %s %s\n"
                    % (time.time(), self.section, detail.replace("\n", " "))
                )
                f.flush()
        except OSError:
            pass  # a full/odd tmpdir must never fail the measurement itself


class Watchdog:
    """Parent side: poll the spool file and decide when a child is dead.

    Liveness is file *growth*; the configured windows are
    ``startup_timeout`` (silence budget before the first beat — the
    probe budget for jax sections) and ``beat_timeout`` (silence budget
    between beats). ``wall_timeout`` caps the whole section regardless
    of progress. ``check()`` returns None while the child may live, or
    a one-line kill reason.
    """

    def __init__(
        self,
        path: str,
        beat_timeout: float,
        wall_timeout: float,
        startup_timeout: Optional[float] = None,
        clock: Callable[[], float] = time.monotonic,
    ):
        self.path = path
        self.beat_timeout = beat_timeout
        self.wall_timeout = wall_timeout
        self.startup_timeout = (
            startup_timeout if startup_timeout is not None else beat_timeout
        )
        self._clock = clock
        self._started = clock()
        self._last_size = self._size()
        self._last_progress = self._started
        self._seen_beat = False

    def _size(self) -> int:
        try:
            return os.stat(self.path).st_size
        except OSError:
            return 0

    def poll_interval(self) -> float:
        return max(0.05, min(0.5, self.beat_timeout / 10.0))

    def last_beat_line(self) -> str:
        """Last complete spool line — what the child was doing when it
        went silent (the kill diagnostic)."""
        try:
            with open(self.path, "rb") as f:
                data = f.read()
        except OSError:
            return ""
        lines = data.decode("utf-8", "replace").strip().splitlines()
        return lines[-1] if lines else ""

    def check(self) -> Optional[str]:
        now = self._clock()
        size = self._size()
        if size > self._last_size:
            self._last_size = size
            self._last_progress = now
            self._seen_beat = True
        silence = now - self._last_progress
        window = self.beat_timeout if self._seen_beat else self.startup_timeout
        if silence > window:
            if not self._seen_beat:
                return (
                    "no heartbeat within probe window (%.0fs): backend "
                    "import/init presumed wedged" % window
                )
            return "heartbeat silence %.0fs > %.0fs (last: %s)" % (
                silence,
                window,
                self.last_beat_line() or "<none>",
            )
        if now - self._started > self.wall_timeout:
            return "section wall timeout after %.0fs" % self.wall_timeout
        return None
