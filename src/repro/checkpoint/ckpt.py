"""Sharded checkpointing with integrity manifests.

Layout of a checkpoint directory::

    step-000123/
      tree.json          # pytree structure + per-leaf dtype/shape/chunking
      leaf-00000.c00.npy # leaf payload, chunked on the leading axis so a
      leaf-00000.c01.npy #   large cluster restores in parallel reads
      ...
      data_state.npz     # data-pipeline iterator state
      MANIFEST.json      # per-file (size, checksum) — verified on restore
      COMMITTED          # written last: crash-safe atomicity marker

Save is atomic (tmp dir + rename + COMMITTED marker); restore refuses
uncommitted or corrupt checkpoints and falls back to the previous step —
the checkpoint/restart half of fault tolerance.  Checksums use the same
hash as the replication integrity layer (kernels/checksum).
"""
from __future__ import annotations

import json
import os
import re
import shutil
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.core.integrity import Manifest
from repro.obs import spans

PyTree = Any
_LEAF_RE = re.compile(r"leaf-(\d{5})\.c(\d{2})\.npy$")
_WRITE_BYTES = 4 * 1024 * 1024   # one write call; the transport's _CHUNK_BYTES


def _flatten(tree: PyTree):
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return leaves, treedef


def _write_npy(f, arr: np.ndarray) -> None:
    """Write ``arr`` to the binary file ``f`` as ``np.save`` would, byte for
    byte (an ``.npy`` v1.0 file), with the data in one C-ordered buffer
    written in ``_WRITE_BYTES`` slices.  ``np.save`` hands a real file to
    ``ndarray.tofile``, which writes an array that is not C-contiguous one
    element at a time; a TPU's ``device_get`` returns several leaves in the
    device's layout (the embedding in Fortran order), and their chunks are
    not contiguous."""
    arr = np.asarray(arr)
    header = np.lib.format.header_data_from_array_1_0(arr)
    np.lib.format.write_array_header_1_0(f, header)
    # np.save writes a Fortran-ordered array's memory as it lies (its
    # header says so), any other array in C order.
    data = np.asarray(arr.T if header["fortran_order"] else arr, order="C")
    data = memoryview(data.reshape(-1).view(np.uint8))
    for start in range(0, len(data), _WRITE_BYTES):
        f.write(data[start:start + _WRITE_BYTES])


def save_checkpoint(ckpt_root: str, step: int, tree: PyTree,
                    data_state_path: Optional[str] = None,
                    n_chunks: int = 4, keep: int = 3) -> str:
    """Write checkpoint for ``step``; returns the committed directory."""
    with spans.span("ckpt.save", step=step) as save:
        final = os.path.join(ckpt_root, f"step-{step:06d}")
        tmp = final + ".tmp"
        if os.path.exists(tmp):
            shutil.rmtree(tmp)
        os.makedirs(tmp, exist_ok=True)

        leaves, treedef = _flatten(tree)
        meta: List[Dict] = []
        for i, leaf in enumerate(leaves):
            with spans.span("ckpt.device_get") as s:
                arr = np.asarray(jax.device_get(leaf))
                s.set(bytes=arr.nbytes)
            # bf16 has no numpy dtype; persist as uint16 view + dtype tag
            dtype_tag = str(leaf.dtype) if hasattr(leaf, "dtype") else str(arr.dtype)
            if dtype_tag == "bfloat16":
                arr = arr.view(np.uint16)
            chunks = max(1, min(n_chunks, arr.shape[0] if arr.ndim else 1))
            bounds = np.linspace(0, arr.shape[0] if arr.ndim else 1,
                                 chunks + 1).astype(int) if arr.ndim else [0, 1]
            files = []
            with spans.span("ckpt.write", files=chunks) as s:
                nbytes = 0
                for c in range(chunks):
                    name = f"leaf-{i:05d}.c{c:02d}.npy"
                    with open(os.path.join(tmp, name), "wb") as f:
                        _write_npy(f, arr[bounds[c]:bounds[c + 1]] if arr.ndim else arr)
                        nbytes += f.tell()
                    files.append(name)
                s.set(bytes=nbytes)
            meta.append({"dtype": dtype_tag, "shape": list(arr.shape),
                         "files": files})
        with spans.span("ckpt.write") as s:
            with open(os.path.join(tmp, "tree.json"), "w") as f:
                json.dump({"treedef": _treedef_token(treedef), "step": step,
                           "leaves": meta}, f)
                nbytes, nfiles = f.tell(), 1
            if data_state_path and os.path.exists(data_state_path):
                shutil.copy(data_state_path, os.path.join(tmp, "data_state.npz"))
                nbytes, nfiles = nbytes + os.path.getsize(data_state_path), 2
            s.set(bytes=nbytes, files=nfiles)

        with spans.span("ckpt.manifest") as s:
            manifest = Manifest.scan(tmp)
            manifest.save(os.path.join(tmp, "MANIFEST.json"))
            s.set(bytes=manifest.total_bytes)
        save.set(bytes=manifest.total_bytes, files=len(manifest.entries))
        with open(os.path.join(tmp, "COMMITTED"), "w") as f:
            f.write("ok")
        if os.path.exists(final):
            shutil.rmtree(final)
        os.rename(tmp, final)
        _gc(ckpt_root, keep)
        return final


def restore_checkpoint(ckpt_root: str, example_tree: PyTree,
                       step: Optional[int] = None,
                       ) -> Optional[Tuple[int, PyTree, str]]:
    """Restore the latest committed+verified checkpoint (or a given step).

    Returns (step, tree, dir) or None.  Corrupt/uncommitted candidates are
    skipped with a warning — restart never loads bad state.
    """
    with spans.span("ckpt.restore", dir=ckpt_root) as restore:
        for cand_step, d in _candidates(ckpt_root, step):
            manifest_path = os.path.join(d, "MANIFEST.json")
            if not (os.path.exists(os.path.join(d, "COMMITTED"))
                    and os.path.exists(manifest_path)):
                continue
            manifest = Manifest.load(manifest_path)
            with spans.span("ckpt.verify", bytes=manifest.total_bytes):
                problems = {k: v for k, v in manifest.verify(d).items()
                            if k not in ("MANIFEST.json", "COMMITTED")}
            if problems:
                print(f"[ckpt] skipping corrupt {d}: {problems}")
                continue
            with open(os.path.join(d, "tree.json")) as f:
                info = json.load(f)
            leaves = []
            with spans.span("ckpt.read") as s:
                for m in info["leaves"]:
                    parts = [np.load(os.path.join(d, fn)) for fn in m["files"]]
                    arr = parts[0] if len(parts) == 1 else np.concatenate(parts, 0)
                    if m["dtype"] == "bfloat16":
                        import jax.numpy as jnp
                        arr = arr.view(np.uint16)
                        leaves.append(jnp.asarray(arr).view(jnp.bfloat16))
                    else:
                        leaves.append(arr.astype(m["dtype"]))
                s.set(bytes=sum(leaf.nbytes for leaf in leaves))
            _, treedef = _flatten(example_tree)
            tree = jax.tree_util.tree_unflatten(treedef, leaves)
            restore.set(step=info["step"])
            return info["step"], tree, d
        return None


def latest_step(ckpt_root: str) -> Optional[int]:
    cands = _candidates(ckpt_root, None)
    return cands[0][0] if cands else None


# ---------------------------------------------------------------------- util
def _candidates(root: str, step: Optional[int]):
    if not os.path.isdir(root):
        return []
    out = []
    for name in os.listdir(root):
        m = re.match(r"step-(\d+)$", name)
        if not m:
            continue
        s = int(m.group(1))
        if step is not None and s != step:
            continue
        out.append((s, os.path.join(root, name)))
    return sorted(out, reverse=True)


def _gc(root: str, keep: int) -> None:
    cands = _candidates(root, None)
    for s, d in cands[keep:]:
        shutil.rmtree(d, ignore_errors=True)


def _treedef_token(treedef) -> str:
    return str(treedef)
