"""Atomic npz checkpointing with keep-k retention and auto-resume.

Layout: <dir>/step_<n>.npz written as .tmp then os.replace (atomic on POSIX),
so a crash mid-write never corrupts the latest checkpoint — the restart path
(runtime/driver.py) always finds either the previous or the new complete file.

Pytrees are flattened to dict[str_path] = leaf; structure round-trips through
jax.tree flatten/unflatten against a template pytree with identical structure.
"""
from __future__ import annotations

import os
import re
from typing import Any

import jax
import numpy as np

Array = jax.Array


def _is_key(x) -> bool:
    return hasattr(x, "dtype") and jax.dtypes.issubdtype(x.dtype, jax.dtypes.prng_key)


def _flatten(tree: Any) -> dict[str, np.ndarray]:
    leaves, treedef = jax.tree.flatten(tree)
    out = {}
    for i, x in enumerate(leaves):
        if _is_key(x):
            x = jax.random.key_data(x)
        out[f"leaf_{i:05d}"] = np.asarray(x)
    return out


def save_pytree(path: str, tree: Any, step: int, keep: int = 3) -> str:
    os.makedirs(path, exist_ok=True)
    fname = os.path.join(path, f"step_{step:09d}.npz")
    tmp = fname + ".tmp"
    with open(tmp, "wb") as fh:  # file handle avoids numpy's suffix appending
        np.savez(fh, **_flatten(tree))
    os.replace(tmp, fname)
    # retention
    steps = sorted(all_steps(path))
    for s in steps[:-keep]:
        try:
            os.remove(os.path.join(path, f"step_{s:09d}.npz"))
        except OSError:
            pass
    return fname


def save_arrays(path: str, arrays: dict[str, np.ndarray]) -> str:
    """Atomic SELF-DESCRIBING npz: named arrays, loadable with no
    template pytree. The persistence layer of artifacts that must be
    restorable independently of sampler state — the posterior
    ``SampleBank`` in particular (DESIGN.md §15). Same tmp + os.replace
    crash-safety as ``save_pytree``."""
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "wb") as fh:
        np.savez(fh, **{k: np.asarray(v) for k, v in arrays.items()})
    os.replace(tmp, path)
    return path


def load_arrays(path: str) -> dict[str, np.ndarray]:
    """Load a ``save_arrays`` npz back into a name -> array dict."""
    with np.load(path) as data:
        return {k: data[k] for k in data.files}


def update_json(path: str, update) -> str:
    """Tolerant read-modify-write of a small JSON artifact (the durable
    BENCH_<date>.json perf trajectory, which has two writers:
    ``benchmarks/run.py`` and ``repro.launch.serve_ibp``). A corrupt or
    half-written file reads as {} instead of crashing the caller, and
    the write is tmp + os.replace — the same crash contract as the npz
    checkpoints. ``update`` maps the current dict to the new one."""
    import json

    data: dict = {}
    if os.path.exists(path):
        try:
            with open(path) as fh:
                data = json.load(fh)
        except (OSError, json.JSONDecodeError):
            data = {}
    data = update(data)
    tmp = path + ".tmp"
    with open(tmp, "w") as fh:
        json.dump(data, fh, indent=1)
    os.replace(tmp, path)
    return path


def all_steps(path: str) -> list[int]:
    if not os.path.isdir(path):
        return []
    out = []
    for f in os.listdir(path):
        m = re.fullmatch(r"step_(\d+)\.npz", f)
        if m:
            out.append(int(m.group(1)))
    return sorted(out)


def latest_step(path: str) -> int | None:
    steps = all_steps(path)
    return steps[-1] if steps else None


def load_pytree(path: str, template: Any, step: int,
                older: tuple[Any, ...] = ()) -> Any:
    """Load step ``step`` against ``template``. ``older`` are templates
    of earlier state layouts: when the file's leaf count does not match
    ``template``, the first of them whose count matches is used instead,
    and the caller fills in what that layout lacks."""
    fname = os.path.join(path, f"step_{step:09d}.npz")
    with np.load(fname) as data:
        leaves = [data[f"leaf_{i:05d}"] for i in range(len(data.files))]
    for t in (template, *older):
        if len(jax.tree.leaves(t)) == len(leaves):
            template = t
            break
    _, treedef = jax.tree.flatten(template)
    t_leaves = jax.tree.leaves(template)
    if len(leaves) != len(t_leaves):
        raise ValueError(
            f"checkpoint {fname} has {len(leaves)} leaves but the template "
            f"has {len(t_leaves)} — the checkpoint predates a state-layout "
            f"change; clear or rename the checkpoint directory to start fresh"
        )
    cast = []
    for l, t in zip(leaves, t_leaves):
        if _is_key(t):
            cast.append(jax.random.wrap_key_data(jax.numpy.asarray(l)))
        elif hasattr(t, "dtype"):
            if l.dtype.kind == "V":  # npz loads ml_dtypes (bf16 etc.) as void
                l = l.view(np.dtype(t.dtype))
            cast.append(jax.numpy.asarray(l, t.dtype))
        else:
            cast.append(l)
    return jax.tree.unflatten(treedef, cast)


def restore(path: str, template: Any,
            older: tuple[Any, ...] = ()) -> tuple[Any, int] | None:
    """Load the newest complete checkpoint, or None if none exists
    (``older`` as in ``load_pytree``)."""
    step = latest_step(path)
    if step is None:
        return None
    return load_pytree(path, template, step, older), step
