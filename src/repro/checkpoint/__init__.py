"""Checkpointing: pytree archives and resumable training state.

Names load on first use, so importing ``repro.checkpoint.train_state`` for
:func:`latest_checkpoint_step` pulls in no JAX.
"""
import importlib

_HOMES = {
    "save_pytree": "store",
    "load_pytree": "store",
    "save_train_checkpoint": "train_state",
    "load_train_checkpoint": "train_state",
    "latest_checkpoint_step": "train_state",
}

__all__ = list(_HOMES)


def __getattr__(name):
    if name not in _HOMES:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    return getattr(importlib.import_module(f"{__name__}.{_HOMES[name]}"), name)
