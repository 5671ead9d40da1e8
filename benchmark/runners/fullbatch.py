"""Full-batch training, on the path ``python -m sgcn_tpu.train`` takes:
``build_comm_plan → FullBatchTrainer → make_train_data → shard_stacked``, then
one ``step()`` per epoch with the loss read back to the host, which is the body
of ``fit()``'s loop (``train/fullbatch.py``).

Series: ``step`` — wall seconds per epoch.
"""

from __future__ import annotations

import numpy as np

import runlib


def build(cell, ctx) -> runlib.State:
    from sgcn_tpu.parallel import build_comm_plan, make_mesh_1d, shard_stacked
    from sgcn_tpu.train import FullBatchTrainer, TrainData, make_train_data

    cfg, traffic = cell.config, cell.traffic
    k = int(traffic["k"])
    with ctx.span("partition"):
        pv = runlib.partition(ctx, k, traffic.get("partition", {}))
    with ctx.span("plan"):
        plan = build_comm_plan(ctx.ahat, pv, k)
    with ctx.span("placement"):
        mesh = make_mesh_1d(k, devices=ctx.devices[:k])
        trainer = FullBatchTrainer(plan, fin=cfg["f_in"], widths=cfg["widths"],
                                   mesh=mesh, lr=cfg["lr"], seed=ctx.seed)
        data = make_train_data(plan, ctx.feats, ctx.labels)
        data = TrainData(**shard_stacked(mesh, vars(data)))
    ctx.notes["trainer"] = {"comm_schedule": trainer.comm_schedule,
                            "b_per_chip": int(plan.b),
                            "pallas": "pallas_tb" in getattr(
                                trainer, "_fwd_static", {})}
    return runlib.State(
        trainer=trainer, data=data,
        halo_counts=[int(x) for x in plan.halo_counts],
        params0=[np.asarray(w) for w in trainer.params])


def warm(state, ctx) -> None:
    with ctx.span("warm.step"):
        state.losses.append(state.trainer.step(state.data))


def sample(state, ctx, seconds: float) -> None:
    runlib.timed_window(state, "step", seconds,
                        lambda: state.trainer.step(state.data))


def traced(state, ctx):
    """``(fn, epochs)``: what the profiler runs, and the epochs (runs of the
    step program) its window holds."""
    n = int(ctx.cell.traffic["trace_steps"])

    def steps():
        # one more than the window holds: it ends where step n + 1 starts
        for _ in range(n + 1):
            with ctx.span("step.dispatch"):
                loss = state.trainer.step(state.data, sync=False)
            with ctx.span("step.readback"):
                loss = float(loss)
            runlib.record(state, loss)

    return steps, n


def release(state) -> None:
    """Before the device is emptied for the reference: the trained weights
    and the logits the program's own ``predict()`` gives with them."""
    tr = state.trainer
    state.extra["final"] = ([np.asarray(w) for w in tr.params],
                            tr.predict(state.data))
    state.trainer = state.data = None


def first_updates(state, k: int) -> list:
    return state.losses[:k]         # one update per epoch


def _on_device(state, ctx, ref):
    """Â as edge chunks, features and labels on the first chip, put there
    once for both of the reference's uses."""
    import jax

    if "ref_inputs" not in state.extra:
        a = ctx.ahat
        state.extra["ref_inputs"] = jax.device_put(
            (ref.coo_chunks(a.indptr, a.indices, a.data), ctx.feats,
             ctx.labels), ctx.devices[0])
    return state.extra["ref_inputs"]


def reference_losses(state, ctx, ref, k: int) -> list:
    edges, h0, labels = _on_device(state, ctx, ref)
    return ref.training_losses(state.params0, [(edges, h0, labels)] * k,
                               ctx.cell.config["lr"])


def logits_pair(state, ctx, ref, precisions) -> tuple:
    """The trainer's logits at the trained weights, and the reference's at
    each of ``precisions``."""
    params, got = state.extra["final"]
    edges, h0, _ = _on_device(state, ctx, ref)
    return got, {p: ref.logits(params, edges, h0, p) for p in precisions}
