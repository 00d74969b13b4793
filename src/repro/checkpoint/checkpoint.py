"""Checkpointing: atomic, resumable, async, reshardable.

Layout:  <dir>/step_<n>/  manifest.json  +  one .npy per leaf (flattened key path).
Writes go to a temp dir and are renamed atomically; a ``latest`` marker file is
updated last, so a crash mid-write can never corrupt the restore point — the
fault-tolerance contract (a killed run restarts from the last complete step).
``runtime.chaos`` sites (``ckpt:leaf``, ``ckpt:commit``) let tests kill a save
at any point and assert exactly that.

Arrays are saved *unsharded* (gathered), so a restore may target a different mesh
or rule set than the save (elastic scaling): restore() device_puts each leaf with
the target sharding.  Object-dtype leaves (pickled Python values — the serve
recovery path's token streams and actor states, which need exact scalar-type
round-trips for bit-identity) pass through np.save's pickle path and are never
coerced.  AsyncCheckpointer runs saves on a background thread — the paper's
non-blocking PLink discipline applied to the checkpoint writer.
"""

from __future__ import annotations

import hashlib
import json
import os
import queue
import shutil
import threading
from pathlib import Path
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np

from repro.runtime import chaos as chaos_mod

PyTree = Any
_SEP = "/"
_NATIVE_DTYPES = (
    "float64", "float32", "float16", "int64", "int32", "int16",
    "int8", "uint8", "uint16", "uint32", "uint64", "bool",
)


def _flatten(tree: PyTree) -> Dict[str, Any]:
    flat: Dict[str, Any] = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = _SEP.join(_part_name(p) for p in path)
        flat[key] = leaf
    return flat


def _part_name(p) -> str:
    if hasattr(p, "key"):
        return str(p.key)
    if hasattr(p, "idx"):
        return str(p.idx)
    return str(p)


def save(
    ckpt_dir, step: int, tree: PyTree, *, extra: Optional[Dict] = None,
    keep: int = 3,
) -> Path:
    ckpt_dir = Path(ckpt_dir)
    ckpt_dir.mkdir(parents=True, exist_ok=True)
    tmp = ckpt_dir / f".tmp_step_{step}_{os.getpid()}"
    if tmp.exists():
        shutil.rmtree(tmp)
    tmp.mkdir()
    try:
        flat = _flatten(tree)
        manifest: Dict[str, Any] = {
            "step": step, "leaves": {}, "extra": extra or {},
        }
        for key, leaf in flat.items():
            chaos_mod.poke("ckpt:leaf")
            arr = np.asarray(jax.device_get(leaf))
            logical_dtype = str(arr.dtype)
            if arr.dtype == object:
                # pickled Python payloads: np.save handles them natively;
                # load_flat/restore re-enable allow_pickle for exactly
                # these leaves
                logical_dtype = "object"
            elif arr.dtype.kind == "V" or logical_dtype not in _NATIVE_DTYPES:
                arr = arr.astype(np.float32)  # exotic dtypes (bf16, fp8) via f32
            fname = hashlib.md5(key.encode()).hexdigest()[:16] + ".npy"
            np.save(tmp / fname, arr)
            manifest["leaves"][key] = {
                "file": fname,
                "shape": list(arr.shape),
                "dtype": logical_dtype,
            }
        (tmp / "manifest.json").write_text(json.dumps(manifest))
        chaos_mod.poke("ckpt:commit")
        final = ckpt_dir / f"step_{step}"
        if final.exists():
            shutil.rmtree(final)
        tmp.rename(final)
    except BaseException:
        # torn write: leave no temp litter, and — critically — leave
        # ``latest`` untouched, still naming the previous complete step
        shutil.rmtree(tmp, ignore_errors=True)
        raise
    # updated last: the commit point.  Written aside and renamed over the old
    # marker, so a concurrent reader never sees it empty or half-written.
    marker_tmp = ckpt_dir / "latest.tmp"
    marker_tmp.write_text(str(step))
    os.replace(marker_tmp, ckpt_dir / "latest")
    _gc(ckpt_dir, keep)
    return final


def _gc(ckpt_dir: Path, keep: int) -> None:
    steps = sorted(
        int(p.name.split("_")[1])
        for p in ckpt_dir.glob("step_*")
        if p.name.split("_")[1].isdigit()
    )
    for s in steps[:-keep] if keep else []:
        shutil.rmtree(ckpt_dir / f"step_{s}", ignore_errors=True)


def latest_step(ckpt_dir) -> Optional[int]:
    marker = Path(ckpt_dir) / "latest"
    if not marker.exists():
        return None
    step = int(marker.read_text().strip())
    if not (Path(ckpt_dir) / f"step_{step}" / "manifest.json").exists():
        return None
    return step


def load_flat(ckpt_dir, step: int) -> Tuple[Dict[str, np.ndarray], Dict]:
    """Raw flattened view of one step: ``{key path: stored array}`` plus the
    manifest ``extra`` dict.  No ``like`` tree needed — the serve recovery
    path reconstructs structure from its own metadata.  Arrays come back
    exactly as stored (the manifest records the logical dtype when an
    exotic one was widened to float32)."""
    d = Path(ckpt_dir) / f"step_{step}"
    manifest = json.loads((d / "manifest.json").read_text())
    flat = {
        key: np.load(
            d / info["file"], allow_pickle=info["dtype"] == "object"
        )
        for key, info in manifest["leaves"].items()
    }
    return flat, manifest["extra"]


def restore(
    ckpt_dir, step: int, like: PyTree, *, shardings: Optional[PyTree] = None,
) -> Tuple[PyTree, Dict]:
    """Restore into the structure of ``like`` (abstract or concrete), resharding
    onto ``shardings`` when given (elastic restore onto a different mesh)."""
    d = Path(ckpt_dir) / f"step_{step}"
    manifest = json.loads((d / "manifest.json").read_text())
    flat_like = _flatten(like)
    flat_sh = _flatten(shardings) if shardings is not None else {}
    out_flat = {}
    for key, want in flat_like.items():
        info = manifest["leaves"].get(key)
        assert info is not None, f"checkpoint missing leaf {key}"
        arr = np.load(d / info["file"], allow_pickle=info["dtype"] == "object")
        assert tuple(arr.shape) == tuple(want.shape), (key, arr.shape, want.shape)
        if info["dtype"] == "object":
            out_flat[key] = arr  # pickled host payload: no device placement
            continue
        arr = jax.numpy.asarray(arr).astype(want.dtype)
        sh = flat_sh.get(key)
        out_flat[key] = jax.device_put(arr, sh) if sh is not None else jax.device_put(arr)
    # rebuild the tree
    treedef = jax.tree_util.tree_structure(like)
    keys = [
        _SEP.join(_part_name(p) for p in path)
        for path, _ in jax.tree_util.tree_flatten_with_path(like)[0]
    ]
    leaves = [out_flat[k] for k in keys]
    return jax.tree_util.tree_unflatten(treedef, leaves), manifest["extra"]


class AsyncCheckpointer:
    """Background checkpoint writer: save() returns immediately; the training
    loop never blocks on IO.  wait() drains pending saves (call before exit).

    A background save's failure is never silent: the error is re-raised on
    the *next* ``save()`` or ``wait()`` call (whichever comes first), and
    the torn step it produced is invisible — ``latest`` still names the
    previous complete step (the atomic-rename contract above)."""

    def __init__(self, ckpt_dir, keep: int = 3):
        self.ckpt_dir = Path(ckpt_dir)
        self.keep = keep
        self._q: "queue.Queue" = queue.Queue()
        self._err: Optional[BaseException] = None
        self._thread = threading.Thread(target=self._worker, daemon=True)
        self._thread.start()

    def _worker(self) -> None:
        while True:
            item = self._q.get()
            if item is None:
                return
            step, tree, extra = item
            try:
                save(self.ckpt_dir, step, tree, extra=extra, keep=self.keep)
            except BaseException as e:  # noqa: BLE001 — re-raised on save/wait
                self._err = e
            finally:
                self._q.task_done()

    def _raise_pending(self) -> None:
        if self._err is not None:
            err, self._err = self._err, None
            raise err

    def save(
        self, step: int, tree: PyTree, extra: Optional[Dict] = None
    ) -> None:
        self._raise_pending()  # a swallowed background failure surfaces HERE
        # device_get now so the step's arrays are snapshot before donation reuse
        host_tree = jax.tree.map(lambda a: np.asarray(jax.device_get(a)), tree)
        self._q.put((step, host_tree, extra))

    def wait(self) -> None:
        self._q.join()
        self._raise_pending()

    def close(self) -> None:
        self.wait()
        self._q.put(None)
        self._thread.join(timeout=5)
