"""Full-batch training of a TYPED graph by whatever model the configuration
names, on the path ``python -m sgcn_tpu.train`` takes: ``build_comm_plan →
FullBatchTrainer → make_train_data → shard_stacked``, then one ``step()`` per
epoch with the loss read back.

This kind is ``runners/fullbatch_model.py`` plus two things.  The training
mask: the configuration's ``split`` names a node type and how many of its
first ids train (node types are id ranges, in the order of the model block's
``types``), handed to ``make_train_data(..., train_mask=)``.  And the logits
comparison runs over that type's rows only: the others have no label and a
program need not form their logits.  It knows types and splits, no model: the
configuration's ``model`` block goes to the trainer as ``name`` + ``model_args``
and to the reference whole; parameters a program keeps sharded with its rows
are read through its ``host_state()`` (global row order), where it has one.

Besides what ``correct`` compares, every run reads once what the logits
limit must REFUSE: the reference with the table its aggregation gathers held
in bfloat16, against the program's logits, on a ``bench:`` line.

Series: ``step`` — wall seconds per epoch.
"""

from __future__ import annotations

import json
import os

import numpy as np

import manifest
import runlib

_cfg = manifest.load_module(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "fullbatch_cfg.py"))
warm, sample, traced = _cfg.warm, _cfg.sample, _cfg.traced
first_updates = _cfg.first_updates


def labelled_rows(cfg) -> slice:
    """The id range of the split's node type."""
    lo = 0
    for t in cfg["model"]["types"]:
        if t["name"] == cfg["split"]["type"]:
            return slice(lo, lo + int(t["count"]))
        lo += int(t["count"])
    raise SystemExit(f"benchmark: split type {cfg['split']['type']!r} is not "
                     "one of the model block's types")


def train_mask(cfg) -> np.ndarray:
    rows = labelled_rows(cfg)
    mask = np.zeros(cfg["n"], np.float32)
    mask[rows.start:rows.start + int(cfg["split"]["train_first"])] = 1.0
    return mask


def _host_params(trainer):
    if hasattr(trainer, "host_state"):
        return trainer.host_state()[0]
    return _cfg._to_host(trainer.params)


def build(cell, ctx) -> runlib.State:
    import jax

    from sgcn_tpu.train import fullbatch as program

    cfg, traffic = cell.config, cell.traffic
    model = dict(cfg["model"])
    name = model.pop("name")
    if name not in program.MODELS:
        # a program without the model (a parent commit): out, before any
        # plan is built
        raise SystemExit(f"benchmark: the program has no model {name!r} "
                         f"(has {sorted(program.MODELS)})")
    from sgcn_tpu.parallel import build_comm_plan, make_mesh_1d, shard_stacked
    from sgcn_tpu.train import FullBatchTrainer, TrainData, make_train_data

    k = int(traffic["k"])
    with ctx.span("partition"):
        pv = runlib.partition(ctx, k, traffic.get("partition", {}))
    with ctx.span("plan"):
        plan = build_comm_plan(ctx.ahat, pv, k)
    with ctx.span("placement"):
        mesh = make_mesh_1d(k, devices=ctx.devices[:k])
        trainer = FullBatchTrainer(
            plan, fin=cfg["f_in"], widths=cfg["widths"], mesh=mesh,
            lr=cfg["lr"], seed=ctx.seed, model=name, model_args=model,
            activation=cfg["activation"])
        data = make_train_data(plan, ctx.feats, ctx.labels,
                               train_mask=train_mask(cfg))
        data = TrainData(**shard_stacked(mesh, vars(data)))
    params0 = _host_params(trainer)
    nparams = sum(int(np.size(x)) for x in jax.tree.leaves(params0))
    if nparams != cfg["params"]:
        raise SystemExit(f"benchmark: the program's model has {nparams} "
                         f"parameters, the configuration {cfg['params']}")
    ctx.notes["trainer"] = {
        "model": name, "comm_schedule": trainer.comm_schedule,
        "b_per_chip": int(plan.b), "params": nparams,
        "memory_estimate": getattr(trainer, "model_memory", None)}
    return runlib.State(
        trainer=trainer, data=data,
        halo_counts=[int(x) for x in plan.halo_counts], params0=params0,
        extra={"rows": labelled_rows(cfg)})


def release(state) -> None:
    """Before the device is emptied for the reference: the trained weights
    and the logits the program's own ``predict()`` gives with them, the
    labelled type's rows."""
    tr = state.trainer
    rows = state.extra["rows"]
    state.extra["final"] = (_host_params(tr), tr.predict(state.data)[rows])
    state.trainer = state.data = None


def _on_device(state, ctx, ref):
    """Â's pattern as per-type row blocks of edges, features, labels and
    the training mask on the first chip, put there once for both of the
    reference's uses."""
    import jax

    if "ref_inputs" not in state.extra:
        a, cfg = ctx.ahat, ctx.cell.config
        state.extra["ref_inputs"] = jax.device_put(
            (ref.coo_chunks(a.indptr, a.indices, a.data, model=cfg["model"]),
             ctx.feats, ctx.labels, train_mask(cfg)), ctx.devices[0])
    return state.extra["ref_inputs"]


def reference_losses(state, ctx, ref, k: int) -> list:
    cfg = ctx.cell.config
    return ref.training_losses(state.params0, [_on_device(state, ctx, ref)] * k,
                               cfg["lr"], cfg["model"], cfg["activation"])


def logits_pair(state, ctx, ref, precisions) -> tuple:
    """The trainer's logits at the trained weights and the reference's at
    each of ``precisions``, over the labelled type's rows — and, on a
    ``bench:`` line, how far the program stands from the reference of the
    LAST precision with its gathered table in bfloat16: the reading that
    check's limit has to refuse."""
    params, got = state.extra["final"]
    edges, h0, _, _ = _on_device(state, ctx, ref)
    cfg = ctx.cell.config
    # (two checks may name one precision: computed once)
    theirs = {p: ref.logits(params, edges, h0, p, cfg["model"],
                            cfg["activation"])
              for p in dict.fromkeys(precisions)}
    narrow = ref.logits(params, edges, h0, precisions[-1], cfg["model"],
                        cfg["activation"], table_dtype="bfloat16")
    diff = (got - narrow).astype("float64")
    rms = float((narrow.astype("float64") ** 2).mean()) ** 0.5
    _, norm, limit = ref.LOGITS_CHECKS[-1]
    gaps = {"max": float(abs(diff).max()) / rms,
            "rms": float((diff ** 2).mean()) ** 0.5 / rms}
    print("bench: " + json.dumps({"bf16_table_reference": {
        "precision": precisions[-1], **gaps, "limit": [norm, limit],
        "refused_by": gaps[norm] / limit}}), flush=True)
    return got, theirs
