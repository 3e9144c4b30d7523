"""Miscellaneous helpers (parity: reference utils/other.py).

`extract_model_from_parallel` and `save` keep their reference semantics
(other.py:56,176); environment context managers live in utils/environment.py.
"""

from __future__ import annotations

import os
import socket
from typing import Any

import numpy as np


def extract_model_from_parallel(model, keep_fp32_wrapper: bool = True):
    """Unwrap a prepared model back to the user's module (parity: reference
    utils/other.py:56 which unwraps DDP/FSDP/DeepSpeed/compiled wrappers).

    Under GSPMD there is exactly one wrapper type: `PreparedModel`."""
    from ..modeling import PreparedModel

    if isinstance(model, PreparedModel):
        return model.module if model.module is not None else model
    return model


def save(obj: Any, f, save_on_each_node: bool = False, safe_serialization: bool = True):
    """Save `obj` on the main process only (parity: reference utils/other.py:176).

    Arrays are saved via numpy `.npz`/msgpack-style flat dict when `obj` is a pytree of
    arrays; arbitrary picklables fall back to pickle.
    """
    import pickle

    from ..state import PartialState

    state = PartialState()
    if state.is_main_process or save_on_each_node:
        f = str(f)
        os.makedirs(os.path.dirname(f) or ".", exist_ok=True)
        import jax

        leaves, _ = jax.tree_util.tree_flatten(obj)
        if leaves and all(isinstance(x, (jax.Array, np.ndarray, np.generic, int, float)) for x in leaves):
            from ..checkpointing import save_pytree

            save_pytree(obj, f)
            return
        with open(f, "wb") as fh:
            pickle.dump(obj, fh)


def is_port_in_use(port: int = 29500) -> bool:
    """(parity: reference utils/other.py:313)"""
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        return s.connect_ex(("localhost", port)) == 0


def convert_bytes(size: float) -> str:
    """Human-readable byte size (parity: reference utils/other.py:324)."""
    for unit in ["bytes", "KB", "MB", "GB", "TB"]:
        if size < 1024.0:
            return f"{round(size, 2)} {unit}"
        size /= 1024.0
    return f"{round(size, 2)} PB"


def check_os_kernel():
    """Warn on Linux kernels with poor multiprocess host performance (parity:
    reference utils/other.py:334 warns on <5.5)."""
    import platform
    import warnings

    info = platform.uname()
    if info.system != "Linux":
        return
    try:
        version = tuple(int(v) for v in info.release.split(".")[:2])
    except ValueError:
        return
    if version < (5, 5):
        warnings.warn(
            f"Detected kernel version {info.release}, which is below the recommended minimum of 5.5.0; "
            "this can cause the process to hang.",
            UserWarning,
        )


def merge_dicts(source: dict, destination: dict) -> dict:
    """Recursive dict merge; `source` wins (used by config layering)."""
    for key, value in source.items():
        if isinstance(value, dict):
            node = destination.setdefault(key, {})
            merge_dicts(value, node)
        else:
            destination[key] = value
    return destination
