"""What a model with a configuration of its own hands the shared code.

A ``MODELS`` entry (``train/fullbatch.py``) may carry a fifth element, a
setup hook ``hook(plan, fin, widths, model_args, *, comm_schedule,
compute_dtype, serve_subgraph) -> ModelSetup``.  The hook validates
``model_args``, refuses the modes the model has no form for, and returns
everything the trainer, the memory model and ``CommStats`` would otherwise
have to know the model's name for; they read the fields below and never
compare names.  Models without a hook (``gcn``, ``gat``) take no
``model_args``.
"""

from __future__ import annotations

import dataclasses
from typing import Callable


@dataclasses.dataclass(frozen=True)
class ModelSetup:
    fwd_static: dict            # statics of the forward, beside the registry's
    init_static: dict           # keyword arguments bound into the init function
    extra_arrays: dict          # arrays derived from the plan, shipped per chip
    #                             beside the registry's plan fields
    mask_fields: tuple          # shipped plan fields narrowed to an int8 0/1 mask
    lane_widths: tuple          # f32 lanes of each layer's exchange, forward
    lane_widths_bwd: tuple      # ... and backward (CommStats books each)
    param_count: int            # parameters of the whole model
    estimate_memory: Callable   # (train: bool) -> itemised per-chip HBM bytes,
    #                             with "total", "rows_kept", "rows_transient",
    #                             "slot_temps" (obs/memory.py's workspace)
    #                             and, where leaves are row-owned,
    #                             "param_bytes": the tree's bytes on ONE chip
    counters: dict              # program counters the trainer leaves
    #                             (obs.tracing.set_counter), name -> value
    allow_pallas: bool          # whether a Pallas aggregator may be selected
    checkpointed: bool = False  # the forward checkpoints its own layers: the
    #                             trainer's whole-forward ``remat`` is refused
    row_owned: dict = dataclasses.field(default_factory=dict)
    #                             parameters owned WITH the rows: top-level
    #                             key of the parameter tree -> a tree, shaped
    #                             like that subtree, of (k, height) int
    #                             arrays: the row of the leaf in global row
    #                             order that each per-chip row holds (-1:
    #                             padding).  Such a leaf is stacked per chip
    #                             and sharded like ``h0``; its gradient is not
    #                             ``psum``med; optimiser state follows it;
    #                             checkpoints hold it in global row order
    out_rows: tuple | None = None  # (rows, valid): names of two shipped
    #                             arrays — the plan row of every row the
    #                             forward returns, and a 0/1 mask of the real
    #                             ones — where the forward returns other rows
    #                             than the chip's ``plan.b``


def check_memory(device, estimate: dict) -> None:
    """Raise where ``estimate["total"]`` passes 97 % of what ``device``
    reports as ``memory_stats()["bytes_limit"]``; a backend that reports none
    (CPU) has no capacity to guard.  No environment variable opens this
    fence: the levers are more chips (every term shrinks ~k-fold) or fewer
    rows."""
    limit = (device.memory_stats() or {}).get("bytes_limit")
    if limit and estimate["total"] > 0.97 * limit:
        gb = {name: round(v / 1024**3, 2) for name, v in estimate.items()}
        raise RuntimeError(
            f"the model at this shape needs an estimated {gb['total']} GB of "
            f"per-chip HBM against {limit / 1024**3:.1f} GB ({gb}); shard "
            f"over more chips or train fewer rows per chip")
