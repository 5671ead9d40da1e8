"""Full-batch training of the model the configuration names, on the path
``python -m sgcn_tpu.train`` takes: ``build_comm_plan → FullBatchTrainer →
make_train_data → shard_stacked``, then one ``step()`` per epoch with the loss
read back.

``runners/fullbatch.py`` builds the trainer with its default model; this kind
builds it from the configuration's ``model`` block (``name`` is the
program's registry entry, the rest its ``model_args``) and hands the
reference that block.  Warm-up, the timed window and the traced window are
the old runner's own functions, loaded from its file.

Series: ``step`` — wall seconds per epoch.
"""

from __future__ import annotations

import os

import numpy as np

import manifest
import runlib

_base = manifest.load_module(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "fullbatch.py"))
warm, sample, traced = _base.warm, _base.sample, _base.traced
first_updates = _base.first_updates


def _to_host(tree):
    import jax

    return jax.tree.map(np.asarray, tree)


def build(cell, ctx) -> runlib.State:
    import jax

    from sgcn_tpu.train import fullbatch as program

    cfg, traffic = cell.config, cell.traffic
    model = dict(cfg["model"])
    name = model.pop("name")
    model.pop("channels")           # stated by widths ÷ heads; checked below
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
    want = [w // h if c else w for w, h, c in zip(
        cfg["widths"], cfg["model"]["heads"], cfg["model"]["concat"])]
    if want != cfg["model"]["channels"]:
        raise SystemExit(f"benchmark: widths {cfg['widths']} and the model "
                         f"block's channels {cfg['model']['channels']} differ")
    nparams = sum(int(np.size(x)) for x in jax.tree.leaves(trainer.params))
    if "params" in cfg and nparams != cfg["params"]:
        raise SystemExit(f"benchmark: the program's model has {nparams} "
                         f"parameters, the configuration {cfg['params']}")
    ctx.notes["trainer"] = {
        "model": name, "comm_schedule": trainer.comm_schedule,
        "b_per_chip": int(plan.b), "params": nparams,
        "memory_estimate": getattr(trainer, "model_memory", None)}
    return runlib.State(
        trainer=trainer, data=data,
        halo_counts=[int(x) for x in plan.halo_counts],
        params0=_to_host(trainer.params))


def release(state) -> None:
    """Before the device is emptied for the reference: the trained weights
    and the logits the program's own ``predict()`` gives with them."""
    tr = state.trainer
    state.extra["final"] = (_to_host(tr.params), tr.predict(state.data))
    state.trainer = state.data = None


def _on_device(state, ctx, ref):
    """Â's pattern as row blocks of edges, features and labels on the first
    chip, put there once for both of the reference's uses."""
    import jax

    if "ref_inputs" not in state.extra:
        a = ctx.ahat
        state.extra["ref_inputs"] = jax.device_put(
            (ref.coo_chunks(a.indptr, a.indices, a.data), ctx.feats,
             ctx.labels), ctx.devices[0])
    return state.extra["ref_inputs"]


def reference_losses(state, ctx, ref, k: int) -> list:
    edges, h0, labels = _on_device(state, ctx, ref)
    cfg = ctx.cell.config
    return ref.training_losses(state.params0, [(edges, h0, labels)] * k,
                               cfg["lr"], cfg["model"], cfg["activation"])


def logits_pair(state, ctx, ref, precisions) -> tuple:
    """The trainer's logits at the trained weights, and the reference's at
    each of ``precisions``."""
    params, got = state.extra["final"]
    edges, h0, _ = _on_device(state, ctx, ref)
    cfg = ctx.cell.config
    return got, {p: ref.logits(params, edges, h0, p, cfg["model"],
                               cfg["activation"]) for p in precisions}
