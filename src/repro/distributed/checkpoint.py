"""Fault-tolerant checkpointing.

Design (multi-host ready, exercised single-host in tests):
  * atomic: write to ``step_<N>.tmp/``, fsync, rename to ``step_<N>/`` —
    a crash mid-write never corrupts the restore set
  * async: a background thread serializes device_get'd arrays so the train
    loop only blocks for the host copy, not the disk write
  * integrity: every array file carries a crc32 in the manifest; restore
    validates and falls back to the previous step on mismatch
  * aux payload: ``save(..., aux=...)`` pickles an arbitrary host-side
    object (training cursor, sampler draw count, PlanCache state) next to
    the array tree with its own crc — the recovery contract for the
    mini-batch loop (train/gnn_steps.py) is that params + aux together
    reproduce the uninterrupted run bit-identically from the cursor
"""
from __future__ import annotations

import json
import os
import pickle
import re
import shutil
import threading
import time
import zlib
from typing import Any

import jax
import numpy as np

from repro.obs import Telemetry


def _flatten_with_paths(tree):
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    out = []
    for path, leaf in flat:
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                       for p in path)
        out.append((key, leaf))
    return out, treedef


class CheckpointManager:
    def __init__(self, directory: str, keep: int = 3, async_write: bool = True,
                 telemetry: Telemetry | None = None):
        self.dir = directory
        self.keep = keep
        self.async_write = async_write
        self.tele = telemetry if telemetry is not None else Telemetry()
        self._saves = self.tele.metrics.counter("checkpoint.saves")
        self._write_s = self.tele.metrics.histogram("checkpoint.write_s")
        self._thread: threading.Thread | None = None
        os.makedirs(directory, exist_ok=True)
        # a crash mid-write leaves a step_<N>.tmp/ behind; it was never
        # renamed, so it is not a restore candidate — GC it up front (no
        # writer can be live in __init__, so this never races a save)
        for name in os.listdir(directory):
            if re.fullmatch(r"step_\d+\.tmp", name):
                shutil.rmtree(os.path.join(directory, name),
                              ignore_errors=True)

    # -- save -----------------------------------------------------------------

    def save(self, step: int, tree: Any, aux: Any = None,
             blocking: bool = False) -> None:
        host_tree = jax.tree.map(lambda a: np.asarray(jax.device_get(a)), tree)
        self.wait()   # never two writers
        if self.async_write and not blocking:
            self._thread = threading.Thread(
                target=self._write, args=(step, host_tree, aux), daemon=True,
                name="ckpt-writer")
            self._thread.start()
        else:
            self._write(step, host_tree, aux)

    def wait(self) -> None:
        if self._thread is not None:
            self._thread.join()
            self._thread = None

    def _write(self, step: int, host_tree, aux: Any = None) -> None:
        t0 = time.perf_counter()
        with self.tele.tracer.span("checkpoint.write", cat="io", step=step):
            self._write_inner(step, host_tree, aux)
        self._saves.inc()
        self._write_s.observe(time.perf_counter() - t0)

    def _write_inner(self, step: int, host_tree, aux: Any = None) -> None:
        tmp = os.path.join(self.dir, f"step_{step:012d}.tmp")
        final = os.path.join(self.dir, f"step_{step:012d}")
        if os.path.exists(tmp):
            shutil.rmtree(tmp)
        os.makedirs(tmp)
        flat, _ = _flatten_with_paths(host_tree)
        manifest = {"step": step, "arrays": {}}
        with open(os.path.join(tmp, "arrays.npz"), "wb") as f:
            np.savez(f, **{k: v for k, v in flat})
        with open(os.path.join(tmp, "arrays.npz"), "rb") as f:
            crc = zlib.crc32(f.read())
        manifest["npz_crc32"] = crc
        manifest["keys"] = [k for k, _ in flat]
        if aux is not None:
            blob = pickle.dumps(aux, protocol=pickle.HIGHEST_PROTOCOL)
            with open(os.path.join(tmp, "aux.pkl"), "wb") as f:
                f.write(blob)
            manifest["aux_crc32"] = zlib.crc32(blob)
        with open(os.path.join(tmp, "manifest.json"), "w") as f:
            json.dump(manifest, f)
            f.flush()
            os.fsync(f.fileno())
        os.rename(tmp, final)
        self._gc()

    def _gc(self) -> None:
        steps = self.all_steps()
        for s in steps[: -self.keep]:
            shutil.rmtree(os.path.join(self.dir, f"step_{s:012d}"),
                          ignore_errors=True)

    # -- restore ----------------------------------------------------------------

    def all_steps(self) -> list[int]:
        out = []
        for name in os.listdir(self.dir):
            m = re.fullmatch(r"step_(\d+)", name)
            if m:
                out.append(int(m.group(1)))
        return sorted(out)

    def _valid(self, step: int) -> bool:
        d = os.path.join(self.dir, f"step_{step:012d}")
        try:
            with open(os.path.join(d, "manifest.json")) as f:
                manifest = json.load(f)
            with open(os.path.join(d, "arrays.npz"), "rb") as f:
                crc = zlib.crc32(f.read())
            if crc != manifest["npz_crc32"]:
                return False
            if "aux_crc32" in manifest:
                with open(os.path.join(d, "aux.pkl"), "rb") as f:
                    if zlib.crc32(f.read()) != manifest["aux_crc32"]:
                        return False
            return True
        except (OSError, KeyError, json.JSONDecodeError):
            return False

    def latest_valid_step(self) -> int | None:
        for s in reversed(self.all_steps()):
            if self._valid(s):
                return s
        return None

    def load_aux(self, step: int | None = None) -> Any:
        """Unpickle the aux payload saved with ``step`` (latest valid step
        when None); None when the checkpoint carries no aux."""
        if step is None:
            step = self.latest_valid_step()
            if step is None:
                raise FileNotFoundError(f"no valid checkpoint in {self.dir}")
        path = os.path.join(self.dir, f"step_{step:012d}", "aux.pkl")
        if not os.path.exists(path):
            return None
        with open(path, "rb") as f:
            return pickle.load(f)

    def restore(self, tree_like: Any,
                step: int | None = None) -> tuple[Any, int]:
        """Restore into the structure and dtypes of ``tree_like``."""
        if step is None:
            step = self.latest_valid_step()
            if step is None:
                raise FileNotFoundError(f"no valid checkpoint in {self.dir}")
        d = os.path.join(self.dir, f"step_{step:012d}")
        data = np.load(os.path.join(d, "arrays.npz"))
        flat, treedef = _flatten_with_paths(tree_like)
        leaves = []
        for key, like in flat:
            arr = data[key]
            assert arr.shape == tuple(like.shape), (key, arr.shape, like.shape)
            leaves.append(jax.numpy.asarray(arr, dtype=like.dtype))
        return jax.tree.unflatten(treedef, leaves), step
