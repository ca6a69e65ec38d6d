"""Wave-cycle phases: one stopwatch that cuts a serving cycle into named,
contiguous, non-overlapping host phases.

The code that does the work opens a phase where the work starts
(``with clock.phase("h2d"):``) and the phase closes where it ends; a phase
opened inside another suspends the outer one, which resumes when the inner
one closes, so a phase's time is its SELF time and no instant is counted
twice. Every cut is one ``now_us()`` stamp, and the same stamp feeds the
three readers (docs/operations/tracing.md, "Wave phases"):

1. the clock's totals (``us``, plus byte counts in ``counts``) are flushed
   into the always-on counters by ``runtime/metrics.observe_phases``;
2. where the cycle was selected for the timeline, each cut also lands in
   ``slices`` as ``[name, t0_us, t1_us]`` — the very list the cycle's
   timeline event holds as ``phases``;
3. where a tracer is installed and jax is loaded, an open phase holds a
   ``jax.profiler.TraceAnnotation("zb:<phase>")``, so the phases sit in a
   profiler trace on the trace's own clock (inert while no session runs).

A clock belongs to one cycle and one thread (the broker actor's or the
raft actor's); nothing here locks.
"""

from __future__ import annotations

import sys
import threading
from typing import Dict, List, Optional

from zeebe_tpu.tracing.spans import now_us

# the phases of the contract, by track, in the order a cycle runs them (a
# phase that does not occur in a cycle has zero length and leaves no slice)
TRACKS = {
    "wave": ("pack", "route", "stage", "h2d", "credit_flush", "launch",
             "blocked", "readback", "decode", "apply", "push", "job_read"),
    "drain": ("drain_wait", "pump"),
    "tick": ("tick", "credit_flush", "backlog", "job_read"),
    "raft": ("log_append", "fsync", "commit"),
}
# The job path's phases are cut out of the phase they run in: ``push`` out
# of ``apply``, ``backlog`` out of ``tick``, and ``job_read`` (a device
# job's row read back and made a record) out of whichever cycle asks for
# it (a tick's ``backlog`` and deadline sweep; a subscription's backlog runs
# outside every cycle and flushes a clock of its own).
# what PendingWave.host_seconds / device_seconds sum: host work of the wave
# path, and host time waiting for the device (never a device time); the job
# path's phases are in neither
WAVE_HOST_PHASES = ("route", "stage", "h2d", "launch", "decode")
WAVE_BLOCKED_PHASES = ("blocked", "readback")

# The dispatcher of a shared wave knows whether the stride selected it; the
# engine that stamps most of its phases is two calls further down and takes
# no tracing argument. The dispatcher leaves the selected wave's slice list
# here for the calls it makes on ITS thread (several in-process brokers
# share one tracer, each on an actor thread of its own).
_SELECTED = threading.local()


def select_slices(slices: Optional[list]) -> None:
    """Route the slices of clocks made on this thread (``selected_slices``)
    into ``slices``; None ends the selection."""
    _SELECTED.slices = slices


def selected_slices() -> Optional[list]:
    return getattr(_SELECTED, "slices", None)


class PhaseClock:
    """One cycle's phases. ``slices`` is the timeline list to append to
    (None: totals only); ``annotate`` mirrors each open phase into the
    profiler trace."""

    __slots__ = ("us", "counts", "slices", "_open", "_t", "_annotate")

    def __init__(self, slices: Optional[list] = None, annotate: bool = False):
        self.us: Dict[str, int] = {}      # phase -> self time, microseconds
        self.counts: Dict[str, int] = {}  # e.g. bytes handed to the device
        self.slices = slices
        self._open: List[tuple] = []      # (name, annotation), innermost last
        self._t = 0
        self._annotate = annotate

    def phase(self, name: str) -> "PhaseClock":
        """Open ``name`` now (suspending the phase that is open, if any);
        use as ``with clock.phase(name):``."""
        now = now_us()
        if self._open:
            self._record(self._open[-1][0], self._t, now)
        annotation = None
        if self._annotate:
            annotation = sys.modules["jax"].profiler.TraceAnnotation(
                "zb:" + name
            )
            annotation.__enter__()
        self._open.append((name, annotation))
        self._t = now
        return self

    def __enter__(self) -> "PhaseClock":
        return self

    def __exit__(self, *exc) -> bool:
        now = now_us()
        name, annotation = self._open.pop()
        self._record(name, self._t, now)
        self._t = now  # the suspended outer phase resumes here
        if annotation is not None:
            annotation.__exit__(*exc)
        return False

    def waited(self, name: str, since_us: int) -> None:
        """A wait that ends now and began at ``since_us``, a ``now_us()``
        stamp taken where the cycle was scheduled — maybe on another
        thread, so it holds no annotation."""
        self._record(name, since_us, now_us())

    def _record(self, name: str, t0: int, t1: int) -> None:
        if t1 <= t0:
            return
        self.us[name] = self.us.get(name, 0) + t1 - t0
        if self.slices is not None:
            self.slices.append([name, t0, t1])

    def count(self, name: str, n) -> None:
        """Add ``n`` to a total that is no phase: bytes, jobs, or seconds
        of a wait that spans cycles (a parked job's)."""
        self.counts[name] = self.counts.get(name, 0) + n

    def seconds(self, *names: str) -> float:
        us = self.us
        return sum(us.get(n, 0) for n in names) / 1e6

    def add(self, other: "PhaseClock") -> None:
        """Take over another clock's totals (a shared wave sums its
        segments' engine clocks); slices are not copied — clocks of one
        selected wave already share one list."""
        for name, v in other.us.items():
            self.us[name] = self.us.get(name, 0) + v
        for name, n in other.counts.items():
            self.counts[name] = self.counts.get(name, 0) + n
