"""What every model hands the shared code, and the record that names it.

A ``MODELS`` entry (``train/fullbatch.py``) is a ``Model`` record: the
model's init and per-chip forward, a few facts the callers read before any
plan exists, and its setup hook ``hook(plan, fin, widths, model_args,
request) -> ModelSetup``.  Every model has one.  The hook validates
``model_args``, refuses the ``Request``s the model has no form for, picks
the plan arrays and statics its forward reads, and returns everything the
trainer, the memory model and ``CommStats`` would otherwise have to know the
model's name for; they read the fields below and never compare names.  A new
model adds its module (forward, init, hook) and its ``MODELS`` record.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

from ..obs.tracing import form_token


@dataclasses.dataclass(frozen=True)
class Model:
    """One ``MODELS`` entry."""
    init: Callable              # (rng, dims, **ModelSetup.init_static) -> params
    forward: Callable           # the per-chip forward
    hook: Callable              # -> ModelSetup (module docstring)
    ragged: bool = False        # has a ragged transport (resolve_comm_schedule)
    combined_edges: bool = False  # reads the combined-edge layout
    #                             (``CommPlan.ensure_cell``): a caller that pads
    #                             many plans to one envelope builds it first,
    #                             with buckets they share
    full_batch_only: bool = False  # no mini-batch trainer, no serve engine
    activation: str = "relu"    # the train CLI's default ``--activation``


@dataclasses.dataclass(frozen=True)
class Request:
    """What the caller asks of a hook, beside the plan and ``model_args``."""
    comm_schedule: str          # resolved: 'a2a' or 'ragged'
    compute_dtype: str | None = None
    halo_dtype: str | None = None
    halo_staleness: int = 0
    halo_delta: bool = False
    replica_budget: int = 0     # resolved: 'auto' -> the knee B
    refresh_band: float | None = None
    remat: bool = False
    serve_subgraph: bool = False
    allow_pallas: bool = True   # whether a Pallas aggregator may be selected
    shared_envelope: bool = False  # one program for many padded plans
    decision: dict = dataclasses.field(default_factory=dict)
    #                             the resolver's selection log (the Pallas
    #                             dispatch adds to it)


@dataclasses.dataclass(frozen=True)
class Hoist:
    """Work of a model's forward that reads data alone (the trainer's
    ``h0`` and the shipped arrays), made once per ``h0`` object outside the
    step: the trainer runs ``build(pa, h0)`` per chip under the step's
    ``shard_map`` (after the ``compute_dtype`` cast) — compiled, called once
    and dropped, in the program span ``span`` — keeps the tuple of arrays it
    returns (per chip ``shapes``) on the device, and hands it to every exact
    program beside ``h0`` or, where ``replaces``, in its place; the forward
    gets it as ``hoisted=`` (and ``h0=None`` where it replaces).  After each
    build or use the trainer sets the program counter ``counter`` to
    ``report(builds, steps_served)``."""
    build: Callable
    shapes: tuple
    span: str
    counter: str
    report: Callable
    replaces: bool = False      # what it builds takes h0's place (gcn: Â·h0)


@dataclasses.dataclass(frozen=True)
class ModelSetup:
    plan_fields: tuple          # CommPlan array fields the forward reads
    fwd_static: dict            # statics of the forward
    init_static: dict           # keyword arguments bound into the init function
    extra_arrays: dict          # arrays derived from the plan, shipped per chip
    #                             beside the plan fields
    mask_fields: tuple          # shipped plan fields narrowed to an int8 0/1 mask
    lane_widths: tuple          # f32 lanes of each layer's exchange, forward
    lane_widths_bwd: tuple      # ... and backward (CommStats books each;
    #                             empty: the forward's)
    param_count: int            # parameters of the whole model
    counters: dict              # program counters the trainer leaves
    #                             (obs.tracing.set_counter), name -> value
    wire_itemsize: tuple = (4, 4)  # bytes a lane takes on the wire, forward
    #                             and backward
    estimate_memory: Callable | None = None  # (train: bool) -> itemised
    #                             per-chip HBM bytes, with "total",
    #                             "rows_kept", "rows_transient", "slot_temps"
    #                             (obs/memory.py's workspace) and, where
    #                             leaves are row-owned, "param_bytes": the
    #                             tree's bytes on ONE chip; the trainer fences
    #                             it (check_memory).  None: obs/memory.py's
    #                             rule of rows
    preflight: Callable | None = None  # (device) -> None: the model's own
    #                             capacity fence, before any array ships
    slot_bytes: int = 0         # bytes one pass materialises per chip over
    #                             its slots beside the rows (GAT's per-slot
    #                             scores; obs/memory.py's workspace)
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


def refuse_args(name: str, model_args: dict | None) -> None:
    """A model with no configuration of its own refuses any."""
    if model_args:
        raise ValueError(f"model {name!r} takes no model_args "
                         f"(got {sorted(model_args)})")


def refuse_carried_halo(request: Request) -> None:
    """Refuse what only ``gcn`` has a form for: the narrowed wire and the
    carried-halo families (stale halo, halo-delta, replicas)."""
    if request.halo_dtype is not None:
        raise ValueError(
            "halo_dtype is a GCN-trainer lever; for GAT use "
            "compute_dtype='bfloat16' (the packed exchange already "
            "ships half-width rows)")
    if request.replica_budget:
        raise ValueError(
            "replica_budget replicates rows of the GCN feature "
            "exchange; the GAT exchange ships per-layer attention "
            "tables whose replication is not supported")
    if request.halo_staleness:
        raise ValueError(
            "halo_staleness=1 pipelines the GCN hot path; the GAT "
            "exchange ships per-layer attention tables whose "
            "staleness is not supported (models/gat.py)")


def refuse_beyond_full_f32(name: str, request: Request) -> None:
    """What the models of one full-batch f32 form refuse: the carried
    halos, the ragged ring, sub-graph serving and a narrowed compute
    dtype."""
    refuse_carried_halo(request)
    if request.comm_schedule != "a2a" or request.serve_subgraph:
        raise ValueError(
            f"{name} runs the dense a2a schedule and the full forward only "
            f"(comm_schedule={request.comm_schedule!r}, "
            f"serve_subgraph={request.serve_subgraph})")
    if request.compute_dtype is not None:
        raise ValueError(f"{name} is float32 only "
                         f"(compute_dtype={request.compute_dtype!r})")


def select_pallas(plan, fin: int, widths, model: str,
                  request: Request) -> dict | None:
    """The Pallas VMEM aggregator's statics where the plan-driven rule
    (``ops.pallas_spmm.use_pallas_spmm``: per-chip tables in the VMEM
    regime, either transport) selects it for ``model`` and the request
    allows it, the per-class dispatch logged into ``request.decision``;
    ``None`` otherwise.  Sub-graph serving reproduces the ELL fold's
    per-row chains (``serve/subgraph.py``), which the tile fold does not,
    so it refuses a plan the rule would put on the kernel."""
    if not request.allow_pallas:
        return None
    from ..ops.pallas_spmm import choose_pallas_dispatch, use_pallas_spmm
    if not use_pallas_spmm(plan, fin, widths, model=model,
                           compute_dtype=request.compute_dtype,
                           schedule=request.comm_schedule):
        return None
    if request.serve_subgraph:
        raise ValueError(
            "sub-graph serving reproduces the ELL fold; this plan "
            "resolved to the Pallas VMEM aggregator — serve with "
            "mode='full' or set SGCN_PALLAS_SPMM=0")
    return choose_pallas_dispatch(plan, model=model,
                                  schedule=request.comm_schedule,
                                  decision=request.decision)


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
