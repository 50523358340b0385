"""Sharded, asynchronous, elastic checkpointing.

Paper §Resilience point 3: checkpoint/restore is how long-running
synchronous jobs survive failures. Design points implemented here:

  * **Leaf-per-file layout** with a JSON manifest (tree structure, shapes,
    dtypes, step). No framework-opaque blobs: a checkpoint written at one
    mesh shape restores at any other (the arrays are saved unsharded and
    re-sharded by the caller's shardings on load) — this is what the
    elastic re-mesh driver relies upon after the OCS scheduler shrinks or
    regrows a slice.
  * **Async writes**: ``save`` snapshots to host (device_get) and hands the
    file I/O to a background thread — training resumes immediately, the
    goodput ledger only pays the snapshot, not the write.
  * **Atomicity**: writes go to ``<dir>.tmp`` then rename; a crash during
    write never corrupts the latest complete checkpoint. ``latest_step``
    only sees complete manifests.
  * **Integrity**: each leaf records a CRC32; restore verifies (detects the
    paper's silent-corruption concern at the storage layer).

With an enabled tracer, a save is four spans on the ``ckpt`` process
row: ``gather`` (device_get and flatten), ``write`` (the ``.npy``
files), ``crc`` (every leaf's checksum) and ``commit`` (manifest,
rename, gc). The writer thread of an async save has its own lane;
``train_ckpt_bytes`` counts the bytes gathered.
"""

from __future__ import annotations

import dataclasses
import json
import os
import shutil
import threading
import zlib
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import SpanTracer

PyTree = Any

_SEP = "__"
_WRITER_TID = 1  # trace lane of the async writer thread


def _flatten(tree: PyTree) -> List[Tuple[str, np.ndarray]]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    out = []
    for path, leaf in flat:
        key = _SEP.join(_path_str(p) for p in path)
        out.append((key, np.asarray(leaf)))
    return out


def _path_str(entry) -> str:
    if hasattr(entry, "key"):
        return str(entry.key)
    if hasattr(entry, "idx"):
        return str(entry.idx)
    return str(entry)


@dataclasses.dataclass
class CheckpointManager:
    directory: str
    keep: int = 3
    tracer: Optional[SpanTracer] = None  # None -> disabled
    metrics: Optional[MetricsRegistry] = None  # None -> fresh enabled one

    def __post_init__(self) -> None:
        os.makedirs(self.directory, exist_ok=True)
        if self.tracer is None:
            self.tracer = SpanTracer(enabled=False)
        if self.metrics is None:
            self.metrics = MetricsRegistry()
        self._bytes = self.metrics.counter("train_ckpt_bytes")
        self._trace_pid = self.tracer.process("ckpt")
        self.tracer.thread(self._trace_pid, 0, "save")
        self.tracer.thread(self._trace_pid, _WRITER_TID, "writer")
        self._thread: Optional[threading.Thread] = None
        self._error: Optional[BaseException] = None
        # the step restore() actually loaded — older than the requested
        # one when a corrupt checkpoint was quarantined and the previous
        # complete manifest used instead. Callers computing a replay
        # range must anchor on this, not on the step they asked for.
        self.last_restored_step: Optional[int] = None

    # ------------------------------------------------------------------ save

    def save(self, step: int, state: PyTree, *, blocking: bool = False
             ) -> None:
        """Snapshot to host and write asynchronously (unless blocking)."""
        self.wait()  # one outstanding write at a time
        tr, pid = self.tracer, self._trace_pid
        with tr.span("gather", pid=pid, cat="ckpt", args={"step": step}):
            host_state = jax.device_get(state)
            leaves = _flatten(host_state)
            treedef = jax.tree_util.tree_structure(host_state)
            self._bytes.inc(sum(arr.nbytes for _, arr in leaves))
        tid = 0 if blocking else _WRITER_TID

        def write() -> None:
            try:
                final = os.path.join(self.directory, f"step_{step:08d}")
                tmp = final + ".tmp"
                with tr.span("write", pid=pid, tid=tid, cat="ckpt"):
                    if os.path.exists(tmp):
                        shutil.rmtree(tmp)
                    os.makedirs(tmp)
                    for key, arr in leaves:
                        np.save(os.path.join(tmp, key + ".npy"), arr)
                with tr.span("crc", pid=pid, tid=tid, cat="ckpt"):
                    crcs = [zlib.crc32(arr.tobytes()) for _, arr in leaves]
                with tr.span("commit", pid=pid, tid=tid, cat="ckpt"):
                    manifest = {"step": step, "treedef": str(treedef),
                                "leaves": {
                                    key: {"file": key + ".npy",
                                          "shape": list(arr.shape),
                                          "dtype": str(arr.dtype),
                                          "crc32": crc}
                                    for (key, arr), crc in zip(leaves, crcs)}}
                    with open(os.path.join(tmp, "manifest.json"), "w") as fh:
                        json.dump(manifest, fh)
                    if os.path.exists(final):
                        shutil.rmtree(final)
                    os.rename(tmp, final)
                    self._gc()
            except BaseException as exc:  # surfaced on next wait()
                self._error = exc

        if blocking:
            write()
            self._raise_pending()
        else:
            self._thread = threading.Thread(target=write, daemon=True)
            self._thread.start()

    def wait(self) -> None:
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        self._raise_pending()

    def _raise_pending(self) -> None:
        if self._error is not None:
            err, self._error = self._error, None
            raise err

    def _gc(self) -> None:
        steps = self.all_steps()
        for step in steps[:-self.keep]:
            shutil.rmtree(os.path.join(self.directory,
                                       f"step_{step:08d}"))

    # --------------------------------------------------------------- restore

    def all_steps(self) -> List[int]:
        out = []
        for name in os.listdir(self.directory):
            # strict step_<digits> parse: skips ".tmp" partials AND
            # ".corrupt" quarantined dirs
            if not name.startswith("step_") or not name[5:].isdigit():
                continue
            if os.path.exists(os.path.join(self.directory, name,
                                           "manifest.json")):
                out.append(int(name[5:]))
        return sorted(out)

    def latest_step(self) -> Optional[int]:
        steps = self.all_steps()
        return steps[-1] if steps else None

    def restore(self, step: int, like: PyTree,
                shard_fn: Optional[Callable[[str, np.ndarray], Any]] = None
                ) -> PyTree:
        """Restore into the structure of ``like``. ``shard_fn(key, array)``
        may device_put each leaf with new shardings (elastic re-mesh).

        A corrupt checkpoint (CRC mismatch or unreadable leaf) is
        *quarantined* — renamed to ``<dir>.corrupt``, invisible to
        ``all_steps``/``latest_step`` — and the previous complete
        checkpoint restored instead, falling back as far as needed.
        Only when no complete checkpoint survives does the original
        ``IOError`` propagate. ``last_restored_step`` records the step
        actually loaded, so replay ranges stay correct after fallback."""
        while True:
            try:
                out = self._restore_step(step, like, shard_fn)
                self.last_restored_step = step
                return out
            except IOError:
                self._quarantine(step)
                earlier = [s for s in self.all_steps() if s < step]
                if not earlier:
                    raise
                step = earlier[-1]

    def _quarantine(self, step: int) -> None:
        path = os.path.join(self.directory, f"step_{step:08d}")
        if not os.path.isdir(path):
            return
        target = path + ".corrupt"
        if os.path.exists(target):
            shutil.rmtree(target)
        os.rename(path, target)

    def _restore_step(self, step: int, like: PyTree,
                      shard_fn: Optional[Callable[[str, np.ndarray], Any]]
                      ) -> PyTree:
        path = os.path.join(self.directory, f"step_{step:08d}")
        with open(os.path.join(path, "manifest.json")) as fh:
            manifest = json.load(fh)
        flat, treedef = jax.tree_util.tree_flatten_with_path(like)
        leaves = []
        for keypath, leaf in flat:
            key = _SEP.join(_path_str(p) for p in keypath)
            meta = manifest["leaves"].get(key)
            if meta is None:
                raise KeyError(f"checkpoint missing leaf {key!r}")
            try:
                arr = np.load(os.path.join(path, meta["file"]))
            except (OSError, ValueError, EOFError) as exc:
                # truncated/unreadable leaf: same corruption class as a
                # checksum mismatch (and handled by the same quarantine)
                raise IOError(f"checksum mismatch restoring {key!r} "
                              f"(corrupt checkpoint: {exc})") from exc
            if zlib.crc32(arr.tobytes()) != meta["crc32"]:
                raise IOError(f"checksum mismatch restoring {key!r} "
                              "(corrupt checkpoint)")
            want_shape = tuple(np.shape(leaf))
            if tuple(arr.shape) != want_shape:
                raise ValueError(
                    f"shape mismatch for {key!r}: ckpt {arr.shape} vs "
                    f"model {want_shape}")
            if shard_fn is not None:
                leaves.append(shard_fn(key, arr))
            else:
                leaves.append(jax.numpy.asarray(arr))
        return jax.tree_util.tree_unflatten(treedef, leaves)
