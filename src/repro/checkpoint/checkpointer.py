"""Sharded, step-atomic checkpointing with async save and reshard-on-restore.

Layout:  <dir>/step_<N>/
           manifest.json     — tree structure, shapes, dtypes, step, mesh axes
           arr_<i>.npy       — one file per leaf (host-gathered)
         <dir>/LATEST        — committed step pointer (written LAST = atomic)

Restore accepts a *different* mesh/shardings than the save (elastic re-mesh:
leaves are device_put with the new shardings).  Async mode runs the host
gather synchronously (cheap) and the file writes on a background thread;
``wait()`` joins before the next save (step-atomicity preserved by LATEST).
"""

from __future__ import annotations

import json
import os
import shutil
import threading

import jax
import ml_dtypes
import numpy as np

_VIEW_AS = {"bfloat16": np.uint16, "float8_e4m3fn": np.uint8,
            "float8_e5m2": np.uint8}


def _to_savable(a: np.ndarray) -> np.ndarray:
    v = _VIEW_AS.get(str(a.dtype))
    return a.view(v) if v is not None else a


def _from_saved(a: np.ndarray, dtype_name: str) -> np.ndarray:
    if dtype_name in _VIEW_AS:
        return a.view(getattr(ml_dtypes, dtype_name))
    return a


def _flatten(tree):
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return leaves, treedef


def save(path: str, tree, step: int, async_: bool = True):
    leaves, treedef = _flatten(tree)
    host = [_to_savable(np.asarray(jax.device_get(x))) for x in leaves]
    tdir = os.path.join(path, f"step_{step}")
    manifest = {
        "step": step,
        "treedef": str(treedef),
        "leaves": [{"shape": list(np.shape(x)), "dtype": str(x.dtype)}
                   for x in leaves],
    }

    def _write():
        tmp = tdir + ".tmp"
        os.makedirs(tmp, exist_ok=True)
        for i, a in enumerate(host):
            np.save(os.path.join(tmp, f"arr_{i}.npy"), a)
        with open(os.path.join(tmp, "manifest.json"), "w") as f:
            json.dump(manifest, f)
        if os.path.exists(tdir):
            shutil.rmtree(tdir)
        os.replace(tmp, tdir)
        with open(os.path.join(path, "LATEST.tmp"), "w") as f:
            f.write(str(step))
        os.replace(os.path.join(path, "LATEST.tmp"),
                   os.path.join(path, "LATEST"))

    if async_:
        t = threading.Thread(target=_write, daemon=True)
        t.start()
        return t
    _write()
    return None


def wait(handle):
    if handle is not None:
        handle.join()


def latest_step(path: str) -> int | None:
    p = os.path.join(path, "LATEST")
    if not os.path.exists(p):
        return None
    with open(p) as f:
        return int(f.read().strip())


def restore(path: str, like_tree, shardings=None, step: int | None = None):
    """Restore into the structure of ``like_tree`` with optional reshard.

    ``shardings``: pytree of (Named)Shardings matching ``like_tree`` — pass
    the NEW mesh's shardings to elastically reshard a checkpoint.
    """
    if step is None:
        step = latest_step(path)
        if step is None:
            raise FileNotFoundError(f"no committed checkpoint under {path}")
    tdir = os.path.join(path, f"step_{step}")
    leaves, treedef = _flatten(like_tree)
    sh_leaves = (jax.tree_util.tree_flatten(shardings)[0]
                 if shardings is not None else [None] * len(leaves))
    with open(os.path.join(tdir, "manifest.json")) as f:
        manifest = json.load(f)
    out = []
    for i, (ref, sh) in enumerate(zip(leaves, sh_leaves)):
        a = np.load(os.path.join(tdir, f"arr_{i}.npy"))
        a = _from_saved(a, manifest["leaves"][i]["dtype"])
        if tuple(a.shape) != tuple(ref.shape):
            raise ValueError(f"leaf {i}: checkpoint shape {a.shape} != {ref.shape}")
        a = a.astype(ref.dtype)
        out.append(jax.device_put(a, sh) if sh is not None else jax.device_put(a))
    return jax.tree_util.tree_unflatten(treedef, out), step
