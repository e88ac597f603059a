"""In-memory 'database' (the paper uses MongoDB) with brokered fetch timing.

Values are host objects: numpy pytrees (model weights, request inputs)
or small numpy stand-ins for modeled payloads. Nothing here lives on a
device; the memory daemon's load is what moves a value into HBM. Fetch
latency is modeled through the shared db bandwidth broker using the
*declared* size, so contention behaves like the paper's Fig 4.
"""
from __future__ import annotations

import threading
from typing import Any, Dict


class Database:
    def __init__(self):
        self._lock = threading.Lock()
        self._kv: Dict[str, Any] = {}
        self._sizes: Dict[str, int] = {}

    def put(self, key: str, value: Any, size: int = 0) -> None:
        with self._lock:
            self._kv[key] = value
            self._sizes[key] = size

    def size_of(self, key: str) -> int:
        return self._sizes.get(key, 0)

    def fetch(self, key: str, broker=None, *, scale: float = 1.0) -> Any:
        if broker is not None:
            broker.transfer(self._sizes.get(key, 0), scale=scale)
        with self._lock:
            return self._kv.get(key)
