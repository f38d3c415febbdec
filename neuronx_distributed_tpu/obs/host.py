"""What the operating system says of this process and of one thread of it.

Two readers, both of cumulative counters, so that a caller keeps one
reading and subtracts it from a later one:

* :class:`HostCounters` — who stops a *process*: the CPU controller's
  throttling (``cpu.stat`` of the process's cgroup), the machine's CPU
  pressure (``/proc/pressure/cpu``) and the time a hypervisor gave to
  other guests (``/proc/stat``'s steal). Each is left out where the host
  does not offer the file: a sandbox's kernel offers none of the three.
* :class:`ThreadMeter` — what one *thread* did: its CPU time
  (``time.thread_time_ns``), its context switches and page faults
  (``getrusage(RUSAGE_THREAD)``) and the time it was runnable and not on a
  core (``/proc/thread-self/schedstat``'s second field, through a
  descriptor kept open for the thread).

Neither is a tracer: :class:`~.tracing.SpanTracer` owns one of each while
it watches the host (``watch_host``) and nothing exists before that.
"""

from __future__ import annotations

import ctypes
import functools
import os
import resource
import threading
import time
from typing import Dict, Optional, Tuple

CPU_PRESSURE = "/proc/pressure/cpu"
PROC_STAT = "/proc/stat"
SCHEDSTAT = "/proc/thread-self/schedstat"


def _cgroup_cpu_stat() -> Optional[str]:
    """The ``cpu.stat`` of this process's cgroup: the unified hierarchy's
    (``0::<path>``) or the version 1 ``cpu`` controller's."""
    try:
        with open("/proc/self/cgroup") as f:
            lines = [ln.strip().split(":", 2) for ln in f]
    except OSError:
        lines = []
    tried = []
    for _, controllers, path in (ln for ln in lines if len(ln) == 3):
        if controllers == "":
            tried.insert(0, "/sys/fs/cgroup" + path)
        elif "cpu" in controllers.split(","):
            tried.append("/sys/fs/cgroup/cpu" + path)
    # inside a cgroup namespace the path above is the host's, not ours
    tried += ["/sys/fs/cgroup", "/sys/fs/cgroup/cpu"]
    for directory in tried:
        path = os.path.join(directory, "cpu.stat")
        if os.path.isfile(path):
            return path
    return None


class HostCounters:
    """Cumulative counters of the host, ``read()`` as flat numbers:
    ``throttled`` (periods in which the cgroup's CPU quota ran out) and
    ``throttled_us`` (what its threads waited for the next period),
    ``pressure_us`` (time in which some runnable task of the machine had no
    core: ``some total``), ``steal_ms`` (time the hypervisor ran something
    else on this guest's cores). The sources are looked for once."""

    def __init__(self):
        self._cpu_stat = _cgroup_cpu_stat()
        self._pressure = CPU_PRESSURE if os.path.isfile(CPU_PRESSURE) else None
        self._stat = PROC_STAT if os.path.isfile(PROC_STAT) else None
        self._tick_ms = 1000.0 / os.sysconf("SC_CLK_TCK")

    def read(self) -> Dict[str, float]:
        out: Dict[str, float] = {}
        if self._cpu_stat is not None:
            try:
                with open(self._cpu_stat) as f:
                    stat = dict(ln.split()[:2] for ln in f if ln.strip())
            except OSError:
                stat = {}
            if "nr_throttled" in stat:
                out["throttled"] = float(stat["nr_throttled"])
            if "throttled_usec" in stat:
                out["throttled_us"] = float(stat["throttled_usec"])
            elif "throttled_time" in stat:      # version 1: nanoseconds
                out["throttled_us"] = float(stat["throttled_time"]) * 1e-3
        if self._pressure is not None:
            try:
                with open(self._pressure) as f:
                    some = f.readline().split()
                out["pressure_us"] = float(next(
                    kv[6:] for kv in some if kv.startswith("total=")))
            except (OSError, StopIteration, ValueError):
                pass
        if self._stat is not None:
            try:
                with open(self._stat) as f:
                    cpu = f.readline().split()
                out["steal_ms"] = float(cpu[8]) * self._tick_ms
            except (OSError, IndexError, ValueError):
                pass
        return out


def deltas(before: Dict[str, float], after: Dict[str, float]
           ) -> Dict[str, float]:
    """``after - before`` of the counters both readings hold."""
    return {k: after[k] - before[k] for k in after if k in before}


#: a :class:`ThreadMeter` reading: CPU time in ns, voluntary and involuntary
#: context switches, minor and major page faults, run-queue delay in ns
#: (``None`` where the host has no ``schedstat``)
Reading = Tuple[int, int, int, int, int, Optional[int]]


class ThreadMeter:
    """Cumulative readings of the *calling* thread. ``read()`` costs one
    clock read, one ``getrusage`` and one ``pread`` (2-4 us together on a
    Linux host); ``close()`` gives the descriptors back. A sandbox's
    kernel may count no switch at all (the chip's host: a thread that has
    imported the package and has never given a core up), and a
    ``getrusage`` there is 5.7 us for five zeros: the thread that makes
    the meter looks once, and where its own count is 0 no reading asks."""

    def __init__(self):
        self._fds: Dict[int, Optional[int]] = {}    # by thread
        ru = resource.getrusage(resource.RUSAGE_THREAD)
        self._counts_switches = ru.ru_nvcsw + ru.ru_nivcsw > 0

    def _schedstat_fd(self) -> Optional[int]:
        ident = threading.get_ident()
        try:
            return self._fds[ident]
        except KeyError:
            pass
        try:
            # thread-self resolves when the file is opened: one a thread
            fd = os.open(SCHEDSTAT, os.O_RDONLY)
        except OSError:
            fd = None
        self._fds[ident] = fd
        return fd

    def read(self) -> Reading:
        cpu = time.thread_time_ns()
        switches = (0, 0, 0, 0)
        if self._counts_switches:
            ru = resource.getrusage(resource.RUSAGE_THREAD)
            switches = (ru.ru_nvcsw, ru.ru_nivcsw, ru.ru_minflt, ru.ru_majflt)
        waited = None
        fd = self._schedstat_fd()
        if fd is not None:
            try:
                waited = int(os.pread(fd, 96, 0).split()[1])
            except (OSError, IndexError, ValueError):
                pass
        return (cpu, *switches, waited)

    def close(self) -> None:
        fds, self._fds = self._fds, {}
        for fd in fds.values():
            if fd is not None:
                os.close(fd)


def since(began: Reading, now: Reading) -> Dict[str, float]:
    """What the thread did between two of its readings: ``cpu_us``,
    ``switches_voluntary`` / ``_involuntary``, ``faults_minor`` /
    ``_major``, and ``runq_us`` where both readings have it."""
    out = {"cpu_us": (now[0] - began[0]) * 1e-3,
           "switches_voluntary": now[1] - began[1],
           "switches_involuntary": now[2] - began[2],
           "faults_minor": now[3] - began[3],
           "faults_major": now[4] - began[4]}
    if now[5] is not None and began[5] is not None:
        out["runq_us"] = (now[5] - began[5]) * 1e-3
    return out


@functools.lru_cache(maxsize=1)
def _libc_sched_getcpu():
    try:
        fn = ctypes.CDLL(None).sched_getcpu
    except (OSError, AttributeError):
        return None
    fn.argtypes, fn.restype = (), ctypes.c_int
    return fn


def current_core() -> Optional[int]:
    """The core the calling thread is on (``sched_getcpu``), or ``None``."""
    # the interpreter has its own from 3.13 on
    fn = getattr(os, "sched_getcpu", None) or _libc_sched_getcpu()
    core = fn() if fn is not None else -1
    return core if core >= 0 else None
