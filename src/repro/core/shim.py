"""Taxon shim (paper §5.2): intercepts the function's GPU calls and
re-dispatches them by category — memory calls to the unified memory daemon,
kernel calls to the kernel executor.

TPU adaptation: the interception point is the runtime API the handler is
written against (SageLoadToGPU / SageDumpToDB / alloc / launch) rather than
the CUDA driver ABI; classification and forwarding semantics are the paper's.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

from repro.core.daemon import Handle, MemoryDaemon
from repro.core.request import Request


class TaxonShim:
    def __init__(self, daemon: MemoryDaemon, executor, request: Request,
                 handles: Dict[str, Handle]):
        self.daemon = daemon
        self.executor = executor
        self.request = request
        self._handles = handles  # pre-loaded by the engine's prepare()
        # set by the runtime's handler wrapper where the node's launches
        # go through its compute lock (not under a shared compute plane)
        self.serialize = False
        # this invocation's own waits, in seconds, summed over its kernel
        # launches: for operand data, and for the node's compute lock and
        # room on the device; and whether a launch was enqueued behind an
        # unfinished program of the node (None: no launch went through
        # the lock)
        self.data_wait_s = 0.0
        self.compute_queue_s = 0.0
        self.overlapped: Optional[bool] = None
        # the expert counters the handler read from its program's outputs
        # (None: the model has no dropless experts)
        self.expert_rows: Optional[int] = None
        self.expert_rows_max: Optional[int] = None

    # ---- memory calls (-> daemon) -------------------------------------
    def sage_load_to_gpu(self, key: str) -> Handle:
        """Async: returns immediately with a handle; the daemon may still be
        loading (§5.2.1: 'SageLoadToGPU is an asynchronous operation')."""
        h = self._handles.get(key)
        if h is None:
            # datum not declared in the request: load on demand (no overlap
            # benefit — this is the slow path the programming model avoids)
            for d in self.request.in_data:
                if d.key == key:
                    h = self.daemon.prepare(
                        type(self.request)(
                            function_name=self.request.function_name, in_data=[d]
                        )
                    )[key]
                    break
            else:
                raise KeyError(f"{key} not in request.in_data")
            self._handles[key] = h
        return h

    def cuda_malloc(self, key: str, nbytes: int) -> Handle:
        h = self.daemon.alloc(self.request, key, nbytes)
        self._handles[key] = h
        return h

    def sage_dump_to_db(self, key: str, value: Any, size: int = 0) -> None:
        self.daemon.db.put(key, value, size=size)

    # ---- kernel calls (-> executor) ------------------------------------
    def launch_kernel(self, fn, *args, **kwargs):
        """Forwarded to the kernel executor, which verifies with the daemon
        that every operand handle is ready before launching (§5.2.2)."""
        result, waits = self.executor.launch(fn, args, kwargs,
                                             serialize=self.serialize)
        self.data_wait_s += waits.data_s
        self.compute_queue_s += waits.queue_s
        if waits.overlapped is not None:
            self.overlapped = bool(self.overlapped) or waits.overlapped
        return result
