"""Sharded, async, crash-safe checkpointing.

Layout: <dir>/step_<N>/ with one .npy per leaf + manifest.json
(tree structure, step, data-pipeline cursor, mesh shape). Writes go to a
temp dir then os.rename — a crash mid-write never corrupts the latest
checkpoint. ``restore_latest`` re-shards to whatever mesh the restart is
running on (elastic scaling): leaves are loaded as full arrays and
``jax.device_put`` against the new shardings.
"""

from __future__ import annotations

import json
import os
import shutil
import threading
import time
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

PyTree = Any


def _flatten_with_paths(tree: PyTree) -> Tuple[List[Tuple[str, Any]], Any]:
    flat, treedef = jax.tree.flatten_with_path(tree)
    out = []
    for path, leaf in flat:
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                       for p in path)
        out.append((key or "leaf", leaf))
    return out, treedef


class Checkpointer:
    def __init__(self, directory: str, keep: int = 3) -> None:
        self.dir = Path(directory)
        self.dir.mkdir(parents=True, exist_ok=True)
        self.keep = keep
        self._thread: Optional[threading.Thread] = None
        self._error: Optional[BaseException] = None   # the writer's failure

    # ---- save ------------------------------------------------------------
    def save(self, step: int, state: PyTree, extra: Optional[Dict] = None,
             blocking: bool = True) -> None:
        """Snapshot state (device→host gather happens in the caller thread;
        disk I/O can run async)."""
        leaves, _ = _flatten_with_paths(state)
        host = [(k, np.asarray(v)) for k, v in leaves]

        def write():
            tmp = self.dir / f".tmp_step_{step}_{os.getpid()}"
            tmp.mkdir(parents=True, exist_ok=True)
            names, dtypes = [], []
            for i, (k, v) in enumerate(host):
                dtypes.append(str(v.dtype))
                if v.dtype.name == "bfloat16":   # numpy can't save bf16
                    v = v.view(np.uint16)
                np.save(tmp / f"{i}.npy", v)
                names.append(k)
            manifest = {"step": step, "leaves": names, "dtypes": dtypes,
                        "time": time.time(), "extra": extra or {}}
            (tmp / "manifest.json").write_text(json.dumps(manifest))
            final = self.dir / f"step_{step}"
            if final.exists():
                shutil.rmtree(final)
            os.rename(tmp, final)                      # atomic publish
            self._gc()

        def write_async():
            try:
                write()
            except BaseException as e:  # noqa: BLE001 — handed to wait()
                self._error = e

        if blocking:
            write()
        else:
            self.wait()
            self._thread = threading.Thread(target=write_async, daemon=True)
            self._thread.start()

    def wait(self) -> None:
        """Join the async writer; re-raise what made its write fail."""
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        error, self._error = self._error, None
        if error is not None:
            raise error

    def _gc(self) -> None:
        steps = sorted(self.steps())
        for s in steps[: -self.keep]:
            shutil.rmtree(self.dir / f"step_{s}", ignore_errors=True)

    # ---- restore ---------------------------------------------------------
    def steps(self) -> List[int]:
        out = []
        for p in self.dir.glob("step_*"):
            if (p / "manifest.json").exists():
                out.append(int(p.name.split("_")[1]))
        return sorted(out)

    def restore(self, step: int, like: PyTree,
                shardings: Optional[PyTree] = None) -> Tuple[PyTree, Dict]:
        """Load a checkpoint into the structure of ``like``; re-shard to
        ``shardings`` (elastic: the mesh may differ from save time)."""
        path = self.dir / f"step_{step}"
        manifest = json.loads((path / "manifest.json").read_text())
        flat_like, treedef = jax.tree.flatten(like)
        n = len(manifest["leaves"])
        assert n == len(flat_like), (
            f"checkpoint has {n} leaves, expected {len(flat_like)}")
        import ml_dtypes
        loaded = []
        for i in range(n):
            a = np.load(path / f"{i}.npy")
            if manifest.get("dtypes", [None] * n)[i] == "bfloat16":
                a = a.view(ml_dtypes.bfloat16)
            loaded.append(a)
        for a, b in zip(loaded, flat_like):
            assert tuple(a.shape) == tuple(b.shape), (
                f"shape mismatch {a.shape} vs {b.shape}")

        def cast(a, dtype):
            return a if a.dtype == dtype else a.astype(dtype)

        if shardings is not None:
            shard_flat = jax.tree.leaves(
                shardings, is_leaf=lambda x: hasattr(x, "spec"))
            loaded = [jax.device_put(cast(a, b.dtype), s)
                      for a, b, s in zip(loaded, flat_like, shard_flat)]
        else:
            loaded = [jax.numpy.asarray(cast(a, b.dtype))
                      for a, b in zip(loaded, flat_like)]
        return jax.tree.unflatten(treedef, loaded), manifest["extra"]

    def restore_latest(self, like: PyTree,
                       shardings: Optional[PyTree] = None
                       ) -> Optional[Tuple[int, PyTree, Dict]]:
        steps = self.steps()
        if not steps:
            return None
        step = steps[-1]
        state, extra = self.restore(step, like, shardings)
        return step, state, extra
