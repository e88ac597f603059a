"""Fault-tolerant checkpointing (msgpack + zstd, no orbax dependency).

Design for 1000+ node operation:
* **atomic commit** — shards are written to ``step_N.tmp/`` and renamed into
  place only after every shard and the manifest fsync; a crashed writer can
  never produce a readable-but-corrupt checkpoint;
* **sharded layout** — each host writes only the param shards it owns
  (``host_shards(params, host_id)``); the manifest records the full pytree
  structure + shapes + dtypes, so restore works on a *different* mesh
  (elastic reshard: arrays are re-device_put under the new sharding);
* **content hashes** — every shard carries an xxh-like checksum (zstd CRC +
  length) verified on load; a bad shard fails fast with its path;
* **retention** — keep the newest K checkpoints (plus any 'milestone' every
  M steps), delete the rest;
* **auto-resume** — ``latest_step()`` scans the directory; the train loop
  restores and continues, making preemption/node-failure recovery a restart
  rather than an operator action.
"""
from __future__ import annotations

import hashlib
import json
import os
import shutil
import struct
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

import jax
import msgpack
import numpy as np
import zstandard as zstd


def _decompressor(codec: str):
    if codec != "zstd":
        raise IOError(f"unknown checkpoint codec {codec!r}")
    return zstd.ZstdDecompressor()


def _path_str(path) -> str:
    parts = []
    for p in path:
        parts.append(str(getattr(p, "key", getattr(p, "idx", p))))
    return "/".join(parts)


def _flatten(tree) -> Dict[str, np.ndarray]:
    flat = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        flat[_path_str(path)] = np.asarray(leaf)
    return flat


def _unflatten_like(tree, flat: Dict[str, np.ndarray]):
    def pick(path, leaf):
        key = _path_str(path)
        arr = flat[key]
        return arr.astype(leaf.dtype) if hasattr(leaf, "dtype") else arr

    return jax.tree_util.tree_map_with_path(pick, tree)


class CheckpointManager:
    def __init__(
        self,
        directory: str | Path,
        *,
        keep: int = 3,
        milestone_every: int = 0,
        zstd_level: int = 3,
    ):
        self.dir = Path(directory)
        self.dir.mkdir(parents=True, exist_ok=True)
        self.keep = keep
        self.milestone_every = milestone_every
        self.zstd = zstd_level

    # ------------------------------------------------------------------
    def _step_dir(self, step: int) -> Path:
        return self.dir / f"step_{step:010d}"

    def save(self, step: int, state: Any, *, host_id: int = 0, num_hosts: int = 1,
             extra: Optional[dict] = None) -> Path:
        """Atomic sharded save. Each host writes its shard file; host 0
        writes the manifest last and commits via rename."""
        flat = _flatten(state)
        keys = sorted(flat)
        my_keys = [k for i, k in enumerate(keys) if i % num_hosts == host_id]
        tmp = self.dir / f"step_{step:010d}.tmp"
        tmp.mkdir(parents=True, exist_ok=True)

        cctx = zstd.ZstdCompressor(level=self.zstd)
        shard_meta = {}
        payload = {}
        for k in my_keys:
            a = flat[k]
            buf = a.tobytes()
            payload[k] = cctx.compress(buf)
            shard_meta[k] = {
                "shape": list(a.shape),
                "dtype": str(a.dtype),
                "sha256": hashlib.sha256(buf).hexdigest()[:16],
                "bytes": len(buf),
            }
        shard_path = tmp / f"shard_{host_id:05d}.msgpack.zst"
        with open(shard_path, "wb") as f:
            f.write(msgpack.packb({"codec": "zstd", "meta": shard_meta,
                                   "data": payload}, use_bin_type=True))
            f.flush()
            os.fsync(f.fileno())

        if host_id == 0:
            manifest = {
                "step": step,
                "num_hosts": num_hosts,
                "keys": keys,
                "extra": extra or {},
            }
            mpath = tmp / "manifest.json"
            mpath.write_text(json.dumps(manifest, indent=1))
            with open(mpath) as f:
                os.fsync(f.fileno())
            final = self._step_dir(step)
            if final.exists():
                shutil.rmtree(final)
            os.rename(tmp, final)  # the atomic commit point
            self._gc()
            return final
        return tmp

    # ------------------------------------------------------------------
    def _gc(self) -> None:
        steps = sorted(self.steps())
        victims = []
        for s in steps[:-self.keep] if self.keep else []:
            if self.milestone_every and s % self.milestone_every == 0:
                continue
            victims.append(s)
        for s in victims:
            shutil.rmtree(self._step_dir(s), ignore_errors=True)

    def steps(self) -> List[int]:
        out = []
        for p in self.dir.glob("step_*"):
            if p.suffix == ".tmp" or not (p / "manifest.json").exists():
                continue
            try:
                out.append(int(p.name.split("_")[1]))
            except (IndexError, ValueError):
                continue
        return sorted(out)

    def latest_step(self) -> Optional[int]:
        s = self.steps()
        return s[-1] if s else None

    # ------------------------------------------------------------------
    def restore(self, step: int, like: Any, *, shardings=None) -> Any:
        """Restore into the structure of ``like``; if ``shardings`` is given
        (a pytree of NamedSharding for a possibly *different* mesh), arrays
        are placed under it — elastic rescale on restore."""
        d = self._step_dir(step)
        manifest = json.loads((d / "manifest.json").read_text())
        flat: Dict[str, np.ndarray] = {}
        for shard in sorted(d.glob("shard_*.msgpack.zst")):
            blob = msgpack.unpackb(shard.read_bytes(), raw=False)
            dctx = _decompressor(blob.get("codec", "zstd"))
            for k, meta in blob["meta"].items():
                buf = dctx.decompress(blob["data"][k],
                                      max_output_size=meta["bytes"] or 1)
                if hashlib.sha256(buf).hexdigest()[:16] != meta["sha256"]:
                    raise IOError(f"checksum mismatch in {shard}:{k}")
                flat[k] = np.frombuffer(buf, dtype=np.dtype(meta["dtype"])).reshape(
                    meta["shape"]
                )
        missing = set(manifest["keys"]) - set(flat)
        if missing:
            raise IOError(f"checkpoint step {step} missing shards for: {sorted(missing)[:5]}")
        state = _unflatten_like(like, flat)
        if shardings is not None:
            state = jax.tree_util.tree_map(
                lambda x, s: jax.device_put(x, s), state, shardings
            )
        return state

    def restore_latest(self, like: Any, *, shardings=None) -> Tuple[Optional[int], Any]:
        s = self.latest_step()
        if s is None:
            return None, like
        return s, self.restore(s, like, shardings=shardings)
