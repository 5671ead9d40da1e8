"""Full-batch training of whatever model the configuration names, on the path
``python -m sgcn_tpu.train`` takes: ``build_comm_plan → FullBatchTrainer →
make_train_data → shard_stacked``, then one ``step()`` per epoch with the loss
read back.

``runners/fullbatch_cfg.py`` knows its model's block (heads, channels); this
kind knows none: the configuration's ``model`` block goes to the trainer as
``name`` (the program's registry entry) and the rest as its ``model_args``,
and to the reference whole.  A program without the model exits at once,
before any plan is built.  Warm-up, the windows, the release and the
reference's inputs are the older runners' own functions, loaded from their
files.

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
first_updates, release = _cfg.first_updates, _cfg.release
reference_losses = _cfg.reference_losses


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
        data = make_train_data(plan, ctx.feats, ctx.labels)
        data = TrainData(**shard_stacked(mesh, vars(data)))
    nparams = sum(int(np.size(x)) for x in jax.tree.leaves(trainer.params))
    if nparams != cfg["params"]:
        raise SystemExit(f"benchmark: the program's model has {nparams} "
                         f"parameters, the configuration {cfg['params']}")
    ctx.notes["trainer"] = {
        "model": name, "comm_schedule": trainer.comm_schedule,
        "b_per_chip": int(plan.b), "params": nparams,
        "memory_estimate": getattr(trainer, "model_memory", None)}
    return runlib.State(
        trainer=trainer, data=data,
        halo_counts=[int(x) for x in plan.halo_counts],
        params0=_cfg._to_host(trainer.params))


def logits_pair(state, ctx, ref, precisions) -> tuple:
    """The trainer's logits at the trained weights and the reference's at
    each of ``precisions`` — and, on a ``bench:`` line, how far the program
    stands from the reference of the LAST precision with its gathered table
    in bfloat16: the reading that check's limit has to refuse."""
    # (two checks may name one precision: computed once)
    got, theirs = _cfg.logits_pair(state, ctx, ref,
                                   list(dict.fromkeys(precisions)))
    params, _ = state.extra["final"]
    edges, h0, _ = _cfg._on_device(state, ctx, ref)
    cfg = ctx.cell.config
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
