"""Preemption tolerance: durable checkpoints, bit-identical resume, faults.

The reference artifact has NO checkpointing (SURVEY §5.4); this package is
the layer that makes long runs survivable on preemptible hardware
(``docs/resilience.md``):

  * ``atomic``      — temp + fsync + rename write discipline (checkpoints
    AND the obs run manifest ride it);
  * ``checkpoint``  — ``CheckpointManager``: step-stamped directory,
    keep-last-K rotation, newest-INTACT discovery with corruption fallback;
  * ``runner``      — ``run_resumable``: the per-step training loop behind
    ``--checkpoint-every`` / ``--resume auto``, with the kill point where
    fault injection lands;
  * ``faults``      — deterministic env-driven fault injection
    (kill-after-save, corrupt-after-save).

Attribute access is lazy (PEP 562) so importing ``sgcn_tpu.resilience``
never drags in the trainer stack — ``utils/checkpoint.py`` imports
``resilience.atomic`` from inside the package and an eager ``__init__``
would cycle.
"""

from __future__ import annotations

_EXPORTS = {
    "atomic_write": ".atomic",
    "atomic_write_json": ".atomic",
    "CheckpointManager": ".checkpoint",
    "run_resumable": ".runner",
    "FaultSpec": ".faults",
    "FAULT_EXIT_CODE": ".faults",
    "parse_fault": ".faults",
    "active_fault": ".faults",
    "after_checkpoint_save": ".faults",
    "corrupt_file": ".faults",
}

__all__ = sorted(_EXPORTS)


def __getattr__(name: str):
    if name in _EXPORTS:
        import importlib

        mod = importlib.import_module(_EXPORTS[name], __name__)
        return getattr(mod, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
