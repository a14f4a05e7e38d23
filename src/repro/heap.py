"""Handing the process's freed heap back to the system.

A host-class slot computes with XLA:CPU, whose outputs and temporaries come
from the C library's heap and are freed when the run ends.  glibc keeps
freed chunks resident for reuse, which normally costs nothing: the next
run's buffers take the same chunks.  While ``jax.profiler`` traces, the
profiler's event buffers, small and kept until the trace stops, land in
those freed chunks and split them; the next run's buffers no longer fit,
take fresh pages, and the freed ones stay resident.  A traced 4096² px
filter request (819 rows on the host) leaves about 128 MiB behind so, and
a long traced window of a fast program fills the host's memory.
``malloc_trim`` gives those pages back.

:class:`HeapGuard` calls it once the resident set has grown by
``SLACK_BYTES`` over its lowest reading since the last release, on a
thread of its own, so that no request waits for it.  Untraced, a serving
process's resident set stays level and the guard never fires.  Where the C
library has no ``malloc_trim`` (it is not glibc) or ``/proc/self/statm``
is missing, the guard does nothing.
"""
from __future__ import annotations

import ctypes
import os
import threading
from typing import Callable, Optional

#: resident growth, in bytes, after which the freed heap is handed back
SLACK_BYTES = 1 << 30

_PAGE = os.sysconf("SC_PAGE_SIZE") if hasattr(os, "sysconf") else 4096


def resident_bytes() -> Optional[int]:
    """The process's resident set in bytes, or None where unknown."""
    try:
        with open("/proc/self/statm", "rb") as f:
            return int(f.read().split()[1]) * _PAGE
    except (OSError, ValueError, IndexError):
        return None


def _malloc_trim() -> Optional[Callable[[int], int]]:
    try:
        return ctypes.CDLL(None).malloc_trim
    except (OSError, AttributeError):
        return None


class HeapGuard:
    """Releases freed heap when the resident set has grown by ``slack``
    bytes since the lowest reading after the last release (see the
    module docstring)."""

    def __init__(self, slack: int = SLACK_BYTES):
        self.slack = slack
        self.releases = 0
        self._trim = _malloc_trim()
        self._floor: Optional[int] = None
        self._busy = False
        self._lock = threading.Lock()

    def check(self) -> bool:
        """Read the resident set; start a release on its own thread when
        it has grown by the slack.  Returns whether one started."""
        if self._trim is None:
            return False
        rss = resident_bytes()
        if rss is None:
            return False
        with self._lock:
            if self._busy:
                return False
            if self._floor is None or rss < self._floor:
                self._floor = rss
                return False
            if rss - self._floor < self.slack:
                return False
            self._busy = True
        threading.Thread(target=self._release, name="repro-heap-release",
                         daemon=True).start()
        return True

    def _release(self) -> None:
        try:
            self._trim(0)
        finally:
            with self._lock:
                self._floor = resident_bytes()
                self._busy = False
                self.releases += 1


#: the process's guard: the heap is one per process
GUARD = HeapGuard()
