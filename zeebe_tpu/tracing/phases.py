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

Around the cycles runs the actor's own timeline (track ``actor``): the loop
that runs an actor's mailbox (``runtime/actors.ActorScheduler._run_job``)
stamps every job of a measured actor with the same ``now_us()``, and a job's
SELF time is its wall time less what the clocks on its thread recorded while
it ran (``thread_phase_us``), so a job and the phases inside it never count
an instant twice either.
"""

from __future__ import annotations

import sys
import threading
from typing import Dict, List, Optional

from zeebe_tpu.tracing.spans import now_us

# the roles an actor can name to have its jobs timed: those the counters
# table (runtime/metrics._phase_handles) has totals for
ROLES = ("broker", "raft")
# the kinds of job the broker actor names where it enqueues one
# (runtime/cluster_broker.py); anything else is ``other``
JOB_KINDS = ("command", "job_subscription", "topic_subscription", "drain",
             "tick", "other")
# The thread's CPU clock is a system call (6 us alone, several times that
# under load on the benchmark's host, where a reading around EVERY job cost 4
# % of the rate and one around every drain, tick and raft job 2 %: PERF.md,
# PR 36). So only the kind that runs the waves, the one that can wait for
# the device, is put on it, and of its jobs one in CPU_CLOCK_STRIDE: the
# counters hold the sampled jobs' seconds beside their count.
CPU_CLOCK_KINDS = ("drain",)
CPU_CLOCK_STRIDE = 8
# the phases of the contract, by track, in the order a cycle runs them (a
# phase that does not occur in a cycle has zero length and leaves no slice);
# track ``actor`` holds the broker actor's jobs, inside which the others run
TRACKS = {
    "actor": ("actor_idle",) + tuple("job:" + kind for kind in JOB_KINDS),
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

# What a thread keeps for the clocks that run on it. ``slices``: the
# dispatcher of a shared wave knows whether the stride selected it; the
# engine that stamps most of its phases is two calls further down and takes
# no tracing argument, so the dispatcher leaves the selected wave's slice
# list here for the calls it makes on ITS thread (several in-process brokers
# share one tracer, each on an actor thread of its own). ``phase_us``: the
# running total of every phase cut on this thread, which the loop that runs
# an actor's jobs reads before and after a job.
_THREAD = threading.local()


def select_slices(slices: Optional[list]) -> None:
    """Route the slices of clocks made on this thread (``selected_slices``)
    into ``slices``; None ends the selection."""
    _THREAD.slices = slices


def selected_slices() -> Optional[list]:
    return getattr(_THREAD, "slices", None)


def thread_phase_us() -> int:
    """Microseconds of phases recorded on the calling thread so far, by
    every clock (``waited`` intervals, which begin on other threads, are
    not in it)."""
    try:
        return _THREAD.phase_us
    except AttributeError:  # this thread's first reading
        _THREAD.phase_us = 0
        return 0


class JobNames:
    """What one kind of job of one measured actor is called: the keys of
    its totals in the counters table (``runtime/metrics._phase_handles``)
    and its slices on track ``actor``. Per-kind totals are the broker
    actor's; another role has its busy, CPU and idle time only, and every
    kind of its jobs is one the CPU clock samples (``cpu_clock``)."""

    __slots__ = ("busy", "cpu", "offcpu", "cpu_jobs", "idle", "cpu_clock",
                 "self_time", "jobs", "mailbox_wait", "idle_before",
                 "job_slice", "idle_slice")

    def __init__(self, role: str, kind: str):
        self.busy = role + "_actor_busy"
        self.cpu = role + "_actor_cpu"
        self.offcpu = role + "_actor_offcpu"
        self.cpu_jobs = role + "_actor_cpu_clock_jobs"
        self.idle = role + "_actor_idle"
        if kind not in JOB_KINDS:
            kind = "other"
        if role == "broker":
            self.cpu_clock = kind in CPU_CLOCK_KINDS
            self.self_time = f"broker_actor_{kind}"
            self.jobs = f"broker_actor_{kind}_jobs"
            self.mailbox_wait = f"broker_actor_{kind}_mailbox_wait"
            self.idle_before = f"broker_actor_idle_before_{kind}"
            # the bare names the readers match (``idle_gap_share``'s ``phase``)
            self.job_slice = "job:" + kind
            self.idle_slice = "actor_idle"
        else:
            self.cpu_clock = True
            self.self_time = self.jobs = None
            self.mailbox_wait = self.idle_before = None
            self.job_slice = f"{role}_job:{kind}"
            self.idle_slice = role + "_idle"


_JOB_NAMES: Dict[tuple, JobNames] = {}


def job_names(role: str, kind: str) -> JobNames:
    names = _JOB_NAMES.get((role, kind))
    if names is None:
        names = _JOB_NAMES[(role, kind)] = JobNames(role, kind)
    return names


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
            self._cut(self._open[-1][0], self._t, now)
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
        self._cut(name, self._t, now)
        self._t = now  # the suspended outer phase resumes here
        if annotation is not None:
            annotation.__exit__(*exc)
        return False

    def waited(self, name: str, since_us: int) -> None:
        """A wait that ends now and began at ``since_us``, a ``now_us()``
        stamp taken where the cycle was scheduled — maybe on another
        thread, so it holds no annotation."""
        self._record(name, since_us, now_us())

    def _cut(self, name: str, t0: int, t1: int) -> None:
        """A phase of this thread ended (or was suspended) at ``t1``."""
        if t1 > t0:
            _THREAD.phase_us = thread_phase_us() + t1 - t0
            self._record(name, t0, t1)

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
