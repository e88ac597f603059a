"""Kernel executor (paper §5.2.2): receives kernel calls from the taxon
shim, verifies with the memory daemon that all operand data is resident on
device, then launches. This is the correctness barrier that makes the
parallelized cold setup safe.

The executor also owns the node's compute lock (:class:`LaunchGate`). A
serialized launch resolves its operands first, outside the lock, and holds
the lock only to enqueue its program: the device runs one program at a
time, and one more may wait on it, queued while the host fetches the
previous result.

Failure contract: if a daemon loader failed (or was cancelled), resolving
the operand raises :class:`DataLoadError` out of ``launch`` — the launch
never blocks on an entry whose loader is already dead. ``wait_timeout``
additionally bounds waits on *live* loads (None = unbounded, the daemon's
own load deadline is the backstop)."""
from __future__ import annotations

import threading
import time
from collections import deque
from typing import Any, Deque, Dict, NamedTuple, Optional, Tuple

import jax
from jax.profiler import TraceAnnotation

from repro.core.daemon import Handle

#: programs of a node that may wait on the device behind the running one
QUEUED_BEHIND_RUNNING = 1


def _unfinished(out) -> bool:
    """Whether any array of a launch's output is still being computed."""
    return any(not leaf.is_ready() for leaf in jax.tree_util.tree_leaves(out)
               if hasattr(leaf, "is_ready"))


class LaunchWaits(NamedTuple):
    """One launch's own waits, in seconds, and whether its program was
    enqueued while an earlier program of the node was unfinished (None
    for a launch that did not go through the node's gate)."""
    data_s: float
    queue_s: float
    overlapped: Optional[bool]


class LaunchGate:
    """The node's compute lock: held only to enqueue a program, after at
    most ``QUEUED_BEHIND_RUNNING`` earlier programs are left unfinished.
    Programs still run one at a time, in launch order, on the device."""

    def __init__(self):
        self._lock = threading.Lock()
        self._inflight: Deque[Any] = deque()  # outputs not yet seen ready

    def _wait_for_room(self) -> None:
        # caller holds self._lock
        self._inflight = deque(o for o in self._inflight if _unfinished(o))
        while len(self._inflight) > QUEUED_BEHIND_RUNNING:
            try:
                jax.block_until_ready(self._inflight.popleft())
            except RuntimeError:  # the program failed: its own caller
                pass              # gets the error when it fetches

    def launch(self, fn, args, kwargs) -> Tuple[Any, float, bool]:
        """Enqueue ``fn`` under the lock; returns (output, seconds waited
        for the lock and the room, whether an earlier program was still
        unfinished at the enqueue)."""
        t0 = time.monotonic()
        with TraceAnnotation("sage.wait.compute_lock"):
            self._lock.acquire()
            try:
                self._wait_for_room()
            except BaseException:
                self._lock.release()
                raise
        queued = time.monotonic() - t0
        try:
            overlapped = bool(self._inflight)
            with TraceAnnotation("sage.launch"):
                out = fn(*args, **kwargs)
            if _unfinished(out):
                self._inflight.append(out)
        finally:
            self._lock.release()
        return out, queued, overlapped


class KernelExecutor:
    def __init__(self, clock=None, wait_timeout: Optional[float] = None,
                 serialize: bool = True):
        self.clock = clock
        self.wait_timeout = wait_timeout
        self.gate = LaunchGate() if serialize else None

    def _resolve(self, x):
        if isinstance(x, Handle):
            return x.wait(self.wait_timeout)
        return x

    def launch(self, fn, args: Tuple, kwargs: Dict, *,
               serialize: bool = False) -> Tuple[Any, LaunchWaits]:
        """Resolve the operand handles, then call ``fn``, through the
        node's gate when ``serialize`` and the node has one; returns the
        result and this launch's own waits, which the shim charges to its
        invocation."""
        t0 = time.monotonic()
        with TraceAnnotation("sage.wait.data"):
            rargs = [self._resolve(a) for a in args]
            rkwargs = {k: self._resolve(v) for k, v in kwargs.items()}
        waited = time.monotonic() - t0
        if serialize and self.gate is not None:
            out, queued, overlapped = self.gate.launch(fn, rargs, rkwargs)
            return out, LaunchWaits(waited, queued, overlapped)
        with TraceAnnotation("sage.launch"):
            return fn(*rargs, **rkwargs), LaunchWaits(waited, 0.0, None)
