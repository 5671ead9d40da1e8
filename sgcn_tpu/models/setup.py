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

from ..obs.tracing import form_token


@dataclasses.dataclass(frozen=True)
class Hoist:
    """Work of a model's forward that reads data alone (the trainer's
    ``h0`` and the shipped arrays), made once per ``h0`` object outside the
    step: the trainer runs ``build(pa, h0)`` per chip under the step's
    ``shard_map`` — compiled, called once and dropped, in the program span
    ``span`` — keeps the tuple of arrays it returns (per chip ``shapes``) on
    the device, and hands it to every exact program beside ``h0``, whose
    forward gets it as ``hoisted=``; after each build or use it sets the
    program counter ``counter`` to ``report(builds, steps_served)``."""
    build: Callable
    shapes: tuple
    span: str
    counter: str
    report: Callable


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
    hoist: Hoist | None = None  # loop-invariant work over data, made once
    #                             per ``h0`` outside the step (``Hoist``)
    label_input: str | None = None  # name of a shipped (k, B) 0/1 array,
    #                             the rows whose label the forward reads: the
    #                             trainer hands the forward ``label_ids`` (the
    #                             label there, -1 elsewhere) and leaves those
    #                             rows out of the loss


def slot_pass(layer: int, way: str, lanes: int, stores: dict, *, tags=(),
              times_per_epoch: int = 1, true_edges=()) -> dict:
    """One aggregation pass of the step's pass list, as the counter
    ``slots.work`` holds it.  ``stores`` is ``{"ell" | "tail" | "halo":
    [((rows, width), unroll), ...]}`` — the buckets and width classes the
    pass hands ``bucketed_slot_reduce`` with the form ``bucket_forms`` gives
    each (``ops.pspmm.pass_store_forms``) — so ``rows × width ×
    times_per_epoch`` is what EVERY chip executes and ``form`` is the tail of
    the bucket's token in the compiled step.  ``way`` is ``"fwd"`` or
    ``"bwd"`` (an op is backward where its name holds ``transpose(``);
    ``tags`` are the tokens that tell the pass from others of its layer and
    way (``att_max``, ``pair_<s>_<d>``); ``times_per_epoch`` counts the runs
    ONE token covers (a scanned body of 13 layers: 13); ``true_edges`` the
    pass's real edges per chip, over all its stores."""
    return {"layer": int(layer), "way": way, "tags": list(tags),
            "lanes": int(lanes), "times_per_epoch": int(times_per_epoch),
            "stores": {name: [{"rows": int(nb), "width": int(wb),
                               "form": form_token(unroll)}
                              for (nb, wb), unroll in forms]
                       for name, forms in stores.items()},
            "true_edges": [int(x) for x in true_edges]}


def plan_true_edges(plan) -> list:
    """Per chip, the real edges one pass over a plan's three stores visits
    (ELL slots + hub tail, and the halo-source edges)."""
    return [int(a) + int(b) for a, b in zip(plan.lnnz, plan.hnnz)]


def slot_work(passes: list, relations: dict | None = None) -> dict:
    """The program counter ``slots.work`` — ONE schema for every model:
    the step's pass list (``slot_pass``) and its sums an epoch.  ``ell_slots``
    are the slots of the ELL buckets, ``fold_slots`` those of the tail's and
    the halo store's virtual rows, ``virtual_rows`` the rows a sorted scatter
    folds (``sgcn.fold_rows``), ``scanned_slots`` the slots of every bucket
    or class that runs as a ``lax.scan``.  ``relations`` names the pairs of
    the typed aggregation's ``pair_<s>_<d>`` tags."""
    per = {"ell_slots": 0, "fold_slots": 0, "virtual_rows": 0,
           "scanned_slots": 0}
    true = None
    for p in passes:
        times = p["times_per_epoch"]
        for store, entries in p["stores"].items():
            for e in entries:
                slots = e["rows"] * e["width"] * times
                per["ell_slots" if store == "ell" else "fold_slots"] += slots
                per["scanned_slots"] += slots * (e["form"] != "u")
                per["virtual_rows"] += e["rows"] * times * (store != "ell")
        if p["true_edges"]:
            add = [x * times for x in p["true_edges"]]
            true = add if true is None else [a + b for a, b in zip(true, add)]
    out = {"passes": list(passes), "per_epoch": {**per, "true_edges": true}}
    if relations:
        out["relations"] = dict(relations)
    return out


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
