"""Sharded, async, elastic checkpointing.

Design points for 1000+ node scale (the restart path is the fault-
tolerance unit of the pipeline's stage boundaries and the APSP panel
loop):

* **Logical-array checkpoints**: leaves are saved as full logical arrays
  (device shards gathered per host), with the pytree structure flattened
  to ``/``-joined keys in an .npz + a JSON manifest.  Restoring resharded
  onto a *different* mesh shape is therefore trivial - elastic restart is
  "load + device_put with the new rules" (test-covered).  On a multi-host
  deployment the same manifest format shards per-host (each host saves the
  shards it owns); this process-local build saves whole arrays since all
  devices are addressable.
* **Async**: `save` snapshots to host memory synchronously (cheap) and
  writes to disk on a daemon thread so the training loop never blocks on
  I/O; `wait()` joins outstanding writes (called before exit / between
  APSP segments when a consistent cut is required).
* **Atomicity**: write to ``<dir>.tmp`` then ``os.replace`` - a crash
  mid-write never corrupts the newest complete checkpoint.
* **Retention**: keep the latest `keep` checkpoints.
"""
from __future__ import annotations

import json
import os
import shutil
import threading
from typing import Any

import jax
import numpy as np

Tree = Any

_SEP = "/"


def _flatten(tree: Tree) -> dict[str, np.ndarray]:
    flat = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = _SEP.join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        flat[key] = np.asarray(leaf)
    return flat


class CheckpointManager:
    def __init__(self, directory: str, *, keep: int = 3):
        self.directory = directory
        self.keep = keep
        os.makedirs(directory, exist_ok=True)
        self._threads: list[threading.Thread] = []

    # ------------------------------------------------------------- save --

    def save(
        self,
        step: int,
        tree: Tree,
        *,
        blocking: bool = False,
        manifest_extra: dict | None = None,
    ) -> str:
        """Snapshot `tree` under `step`.

        manifest_extra: JSON-serializable metadata merged into the manifest
        (the pipeline engine records its name + completed stage here so a
        restart can locate the right resume point without a prototype).
        """
        flat = _flatten(tree)  # synchronous host snapshot
        path = os.path.join(self.directory, f"step_{step:010d}")

        def write():
            tmp = f"{path}.tmp.{os.getpid()}.{threading.get_ident()}"
            os.makedirs(tmp, exist_ok=True)
            np.savez(os.path.join(tmp, "arrays.npz"), **flat)
            manifest = {
                "step": step,
                "keys": sorted(flat.keys()),
                "shapes": {k: list(v.shape) for k, v in flat.items()},
                "dtypes": {k: str(v.dtype) for k, v in flat.items()},
            }
            if manifest_extra:
                manifest.update(manifest_extra)
            with open(os.path.join(tmp, "manifest.json"), "w") as f:
                json.dump(manifest, f)
            # racing writers of the same step: last os.replace wins; retry
            # once if another thread re-created `path` between rmtree and
            # replace (both candidates are complete checkpoints).  A second
            # failure is a real error: clean up the tmp dir and raise
            # rather than report a checkpoint that does not exist.
            for attempt in range(2):
                if os.path.exists(path):
                    shutil.rmtree(path, ignore_errors=True)
                try:
                    os.replace(tmp, path)
                    break
                except OSError:
                    if attempt == 1:
                        shutil.rmtree(tmp, ignore_errors=True)
                        raise
            self._gc()

        if blocking:
            write()
        else:
            t = threading.Thread(target=write, daemon=True)
            t.start()
            self._threads.append(t)
        return path

    def wait(self):
        for t in self._threads:
            t.join()
        self._threads.clear()

    def _gc(self):
        steps = self.all_steps()
        for s in steps[: -self.keep]:
            shutil.rmtree(
                os.path.join(self.directory, f"step_{s:010d}"),
                ignore_errors=True,
            )

    # ---------------------------------------------------------- restore --

    def all_steps(self) -> list[int]:
        steps = []
        for name in os.listdir(self.directory):
            if not name.startswith("step_") or ".tmp" in name:
                continue
            # the FULL suffix must be numeric: a stray "step_foo" or a
            # manual "step_0000000003_backup" copy must neither kill the
            # resume scan (ValueError) nor alias a real step number
            suffix = name[len("step_"):]
            if not suffix.isdigit():
                continue
            steps.append(int(suffix))
        return sorted(steps)

    def latest_step(self) -> int | None:
        steps = self.all_steps()
        return steps[-1] if steps else None

    def read_manifest(self, step: int) -> dict:
        path = os.path.join(self.directory, f"step_{step:010d}")
        with open(os.path.join(path, "manifest.json")) as f:
            return json.load(f)

    def restore_flat(self, step: int) -> dict[str, np.ndarray]:
        """Restore a checkpoint as a flat {key: array} dict, prototype-free
        (shapes/dtypes come from the manifest).  This is the pipeline
        stage-boundary restore path: artifacts are a flat namespace, so no
        pytree prototype is required to resume."""
        path = os.path.join(self.directory, f"step_{step:010d}")
        with np.load(os.path.join(path, "arrays.npz")) as data:
            return {k: data[k] for k in data.files}

    def restore(self, step: int, target: Tree, *, shardings: Tree | None = None):
        """target: pytree prototype (structure + dtypes).  shardings: optional
        matching tree of Shardings - this is the elastic-resharding hook."""
        path = os.path.join(self.directory, f"step_{step:010d}")
        flat_proto, treedef = jax.tree_util.tree_flatten_with_path(target)
        flat_shard = (
            [s for _, s in jax.tree_util.tree_flatten_with_path(shardings)[0]]
            if shardings is not None
            else [None] * len(flat_proto)
        )
        leaves = []
        with np.load(os.path.join(path, "arrays.npz")) as data:
            for (path_, proto), shard in zip(flat_proto, flat_shard):
                key = _SEP.join(
                    str(getattr(p, "key", getattr(p, "idx", p))) for p in path_
                )
                arr = data[key]
                if shard is not None:
                    leaves.append(jax.device_put(arr, shard))
                else:
                    leaves.append(jax.numpy.asarray(arr, dtype=proto.dtype))
        return treedef.unflatten(leaves)
