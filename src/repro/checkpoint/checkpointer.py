"""Fault-tolerant checkpointing: async save, atomic publish, retention,
mesh-agnostic restore (resharding on load).

Layout:  <dir>/step_<N>/
            manifest.json          {step, leaf paths, shapes, dtypes}
            <leaf-path>.npy        one file per pytree leaf

Save is atomic (write to ``step_<N>.tmp`` then rename) so a crash mid-save
never corrupts the latest checkpoint; ``latest_step`` only sees published
directories. Async mode hands the host copy to a worker thread so the train
loop continues. Restore takes a *target* sharding tree and device_puts each
leaf accordingly — checkpoints carry no mesh information, which is what
makes elastic re-scaling (restore onto a different mesh) work.
"""
from __future__ import annotations

import json
import os
import re
import shutil
import threading
from typing import Any, Optional

import jax
import numpy as np

_SEP = "::"


def _flatten(tree) -> dict[str, Any]:
    flat = {}
    for path, leaf in jax.tree.flatten_with_path(tree)[0]:
        key = _SEP.join(
            str(getattr(p, "key", getattr(p, "idx", getattr(p, "name", p))))
            for p in path
        )
        flat[key] = leaf
    return flat


class Checkpointer:
    def __init__(self, directory: str, keep: int = 3):
        self.directory = directory
        self.keep = keep
        os.makedirs(directory, exist_ok=True)
        self._thread: Optional[threading.Thread] = None

    # -- save ---------------------------------------------------------------
    def save(self, step: int, tree, blocking: bool = True) -> None:
        host_tree = jax.tree.map(np.asarray, jax.device_get(tree))
        if blocking:
            self._write(step, host_tree)
        else:
            self.wait()  # one in-flight save at a time
            self._thread = threading.Thread(
                target=self._write, args=(step, host_tree), daemon=True
            )
            self._thread.start()

    def wait(self) -> None:
        if self._thread is not None:
            self._thread.join()
            self._thread = None

    def _write(self, step: int, host_tree) -> None:
        final = os.path.join(self.directory, f"step_{step:08d}")
        tmp = final + ".tmp"
        if os.path.exists(tmp):
            shutil.rmtree(tmp)
        os.makedirs(tmp)
        flat = _flatten(host_tree)
        manifest = {"step": step, "leaves": {}}
        for key, leaf in flat.items():
            fname = re.sub(r"[^A-Za-z0-9_.:-]", "_", key) + ".npy"
            np.save(os.path.join(tmp, fname), leaf)
            manifest["leaves"][key] = {
                "file": fname,
                "shape": list(np.shape(leaf)),
                "dtype": str(np.asarray(leaf).dtype),
            }
        with open(os.path.join(tmp, "manifest.json"), "w") as f:
            json.dump(manifest, f)
        if os.path.exists(final):
            shutil.rmtree(final)
        os.rename(tmp, final)  # atomic publish
        self._gc()

    def _gc(self) -> None:
        steps = self.all_steps()
        for s in steps[: -self.keep] if self.keep else []:
            shutil.rmtree(os.path.join(self.directory, f"step_{s:08d}"))

    # -- restore ------------------------------------------------------------
    def all_steps(self) -> list[int]:
        out = []
        for name in os.listdir(self.directory):
            m = re.fullmatch(r"step_(\d+)", name)
            if m:
                out.append(int(m.group(1)))
        return sorted(out)

    def latest_step(self) -> Optional[int]:
        steps = self.all_steps()
        return steps[-1] if steps else None

    def restore(self, step: int, target_tree, sharding_tree=None):
        """Restore into the structure of ``target_tree``; if a sharding tree
        is given, leaves are placed with those shardings (any mesh)."""
        d = os.path.join(self.directory, f"step_{step:08d}")
        with open(os.path.join(d, "manifest.json")) as f:
            manifest = json.load(f)
        flat_target = _flatten(target_tree)
        flat_shard = _flatten(sharding_tree) if sharding_tree is not None else {}
        restored = {}
        for key in flat_target:
            entry = manifest["leaves"][key]
            arr = np.load(os.path.join(d, entry["file"]))
            if key in flat_shard:
                restored[key] = jax.device_put(arr, flat_shard[key])
            else:
                restored[key] = jax.numpy.asarray(arr)
        # Rebuild the pytree in target order.
        leaves_in_order = [restored[k] for k in flat_target]
        treedef = jax.tree.structure(target_tree)
        return jax.tree.unflatten(treedef, leaves_in_order)
