"""Benchmark: full-batch partitioned GCN per-epoch wall-clock on real hardware.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

Protocol mirrors the reference's (GPU/PGCN.py:202-228): 1 warm-up epoch, then
timed epochs; epoch = full forward + backward + optimizer step over the whole
graph. The synthetic workload is sized like ogbn-arxiv (169k vertices, ~1.2M
undirected edges, 128 features, 3 layers), matching BASELINE.json config #2.

``vs_baseline`` is the speedup of our jitted TPU epoch over the reference
implementation style run on this host: a torch (CPU) ``torch.sparse.mm`` GCN
epoch with identical shapes — the reference's own compute stack, since no
NCCL/V100 cluster numbers are published in-repo (BASELINE.json).
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

import numpy as np
import scipy.sparse as sp


def synth_graph(n: int, avg_deg: int, seed: int = 0,
                kind: str = "er") -> sp.csr_matrix:
    """Synthetic undirected benchmark graph at ogbn shape.

    ``er`` (default, the historical bench graph) has no degree tail;
    ``ba`` is preferential-attachment with a power-law tail — the profile
    of the real ogbn graphs, and the only one that exercises the
    degree-bucket/hub-spill layout the SpMM is designed around;
    ``dcsbm`` adds planted communities on top of the power-law tail — the
    only family where the partitioner can actually SHRINK the exchange
    (BA/ER are expanders), so it is the one that shows comm-volume-driven
    epoch differences on the multi-chip path."""
    from sgcn_tpu.io.datasets import ba_graph, dcsbm_graph, er_graph
    if kind == "ba":
        return ba_graph(n, max(1, avg_deg // 2), seed)
    if kind == "dcsbm":
        return dcsbm_graph(n, ncomm=max(8, n // 12_000), avg_deg=avg_deg,
                           seed=seed)
    return er_graph(n, avg_deg, seed)


def diff_time(make_run, lo: int, hi: int, reps: int = 5,
              retries: int = 6, estimates: int = 3) -> float:
    """See _diff_time_quality for the companion measurement-quality record."""
    value, n_clean = diff_time_q(make_run, lo, hi, reps, retries, estimates)
    _diff_time_quality["clean_estimates"] = n_clean
    _diff_time_quality["target_estimates"] = estimates
    return value


# Quality of the MOST RECENT diff_time call: how many clean differential
# estimates backed the reported median (ADVICE r3: a single-draw number must
# be distinguishable from a median-of-3 in the emitted JSON).
_diff_time_quality: dict = {}


def diff_time_q(make_run, lo: int, hi: int, reps: int = 5,
                retries: int = 6, estimates: int = 3) -> tuple[float, int]:
    """The round-3 differential protocol, shared by every bench mode:
    ``make_run(nep)`` returns a zero-arg callable that runs ``nep``
    on-device epochs and returns a synced finite scalar; the per-call
    dispatch + readback constant (measured ~5 ms per step() on a v5e,
    PERF.md bring-up) cancels in ``(t_hi − t_lo)/(hi − lo)``.

    Reports the MEDIAN of ``estimates`` independent differentials: a single
    differential is vulnerable to transients in either endpoint (an
    inflated ``t_lo`` shrinks it — one such draw under-reported the
    flagship by 1.7× in round 3; an inflated ``t_hi`` overstates it), and
    the per-point median-of-reps cannot remove a transient spanning a whole
    point.  Compiled programs are cached per epoch count, so the extra
    estimates cost only run time."""
    def once(nep):
        run = make_run(nep)
        run()                                     # compile + warm, retired
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            v = run()
            ts.append(time.perf_counter() - t0)
            if not np.isfinite(v):
                raise RuntimeError(f"non-finite loss {v}")
        return statistics.median(ts)

    est = []
    for _ in range(retries):
        t_lo, t_hi = once(lo), once(hi)
        if t_hi > t_lo:
            est.append((t_hi - t_lo) / (hi - lo))
            if len(est) == estimates:
                return statistics.median(est), len(est)
    if est:
        # fewer clean estimates than asked: still a differential, but the
        # robustness claim no longer holds — say so where the reader looks
        print(f"# diff_time: only {len(est)}/{estimates} clean differential "
              f"estimate(s) after {retries} attempts (chip contention?); "
              "treat the reported time as a single-draw measurement",
              file=sys.stderr)
        return statistics.median(est), len(est)
    # never fabricate a near-zero number out of timing noise
    raise RuntimeError(
        f"differential timing failed: t({hi} ep)={t_hi:.4f}s <= "
        f"t({lo} ep)={t_lo:.4f}s in every attempt (chip contention?)")


def paired_differential(make_a, make_b, nep: int, reps: int = 6,
                        what: str = "A/B"):
    """Rep-level PAIRED differential timing of two arms — THE shared A/B
    protocol of the one-process children (stale, ragged-schedule).

    This 2-core host drifts by tens of percent over minutes (measured
    exact-arm pre/post spreads up to 1.6×), so two separately-timed phases
    — or two separate child processes — turn a <10% effect into a coin
    flip.  Each rep times the four runs (arm-A lo/hi, arm-B lo/hi) back to
    back within seconds, forms BOTH differentials from the same machine
    state, and the medians over clean reps are compared.  ``make_*`` are
    ``make_run``-style factories (nep → zero-arg runner returning a synced
    finite scalar); returns ``(a_s, b_s, clean_pairs)`` per-epoch times.
    """
    times, clean = paired_differential_multi([make_a, make_b], nep,
                                             reps=reps, what=what)
    return times[0], times[1], clean


def paired_differential_multi(makes, nep: int, reps: int = 6,
                              what: str = "A/B"):
    """N-arm generalization of ``paired_differential`` (same protocol, same
    drift rationale): each rep times every arm's lo/hi back to back and a
    rep only counts when EVERY arm's differential is clean, so all medians
    come from identical machine states.  Returns ``(per_arm_epoch_s,
    clean_reps)``."""
    runs_lo = [m(1) for m in makes]
    runs_hi = [m(nep) for m in makes]
    for r in runs_lo + runs_hi:
        r()                                   # compile + warm, retired

    def timed(run):
        t0 = time.perf_counter()
        v = run()
        dt = time.perf_counter() - t0
        if not np.isfinite(v):
            raise RuntimeError(f"non-finite loss {v}")
        return dt

    diffs: list[list[float]] = [[] for _ in makes]
    for _ in range(reps):
        t_lo = [timed(r) for r in runs_lo]
        t_hi = [timed(r) for r in runs_hi]
        if all(h > lo for h, lo in zip(t_hi, t_lo)):
            for i, (h, lo) in enumerate(zip(t_hi, t_lo)):
                diffs[i].append((h - lo) / (nep - 1))
    if not diffs[0]:
        raise RuntimeError(f"{what}: no clean paired differentials")
    return [statistics.median(d) for d in diffs], len(diffs[0])


def bench_jax(ahat, feats, labels, widths, epochs: int, k: int,
              model: str = "gcn",
              dtype: str | None = None, remat: bool = False,
              halo_staleness: int = 0, halo_delta: bool = False,
              sync_every: int = 0, step_dispatch: bool = False,
              comm_schedule: str | None = None):
    """The flagship: ``k`` chips, stated by the caller (``--chips``) and
    never inferred from the device count — on a four-chip host the
    single-chip flagship must stay a single-chip run."""
    from sgcn_tpu.parallel import build_comm_plan, make_mesh_1d
    from sgcn_tpu.train import FullBatchTrainer, make_train_data
    from sgcn_tpu.parallel.mesh import shard_stacked

    n = ahat.shape[0]
    part_metrics = {"partitioner": "none", "km1": 0}
    if dtype is not None:
        part_metrics["compute_dtype"] = dtype
    if k > 1:
        # the flagship bench exercises the paper's core idea: comm volume is
        # driven by the native hypergraph partitioner, never random
        # (GPU/PGCN.py:171-173 consumes a partitioner vector)
        from sgcn_tpu.partition import partition_hypergraph_colnet
        pv, km1 = partition_hypergraph_colnet(ahat, k, seed=0)
        part_metrics = {"partitioner": "hp", "km1": int(km1)}
    else:
        pv = np.zeros(n, dtype=np.int64)
    plan = build_comm_plan(ahat, pv, k)
    part_metrics["comm_volume_rows"] = int(plan.predicted_send_volume.sum())
    part_metrics["comm_messages"] = int(plan.predicted_message_count.sum())
    mesh = make_mesh_1d(k)
    # PGAT semantics: bare stacked modules, no inter-layer activation
    # (GPU/PGAT.py:202-213; same default as the trainer CLI)
    kw = {"model": "gat", "activation": "none"} if model == "gat" else {}
    if halo_staleness:
        kw.update(halo_staleness=halo_staleness, halo_delta=halo_delta,
                  sync_every=sync_every)
        part_metrics.update(halo_staleness=halo_staleness,
                            halo_delta=halo_delta, sync_every=sync_every)
    if comm_schedule is not None:
        kw["comm_schedule"] = comm_schedule
    trainer = FullBatchTrainer(plan, fin=feats.shape[1], widths=widths,
                               mesh=mesh, compute_dtype=dtype, remat=remat,
                               **kw)
    # padded-vs-true accounting of the SELECTED transport (the resolved
    # schedule when 'auto' was asked; docs/comm_schedule.md) — both models
    # ship a transport now, so both report it
    part_metrics["comm_schedule"] = trainer.comm_schedule
    part_metrics["padding_efficiency"] = round(
        trainer.stats.padding_efficiency, 6)
    part_metrics["wire_rows_per_exchange"] = \
        trainer.stats.wire_rows_per_exchange
    data = make_train_data(plan, feats, labels)
    data = type(data)(**shard_stacked(mesh, vars(data)))
    # DIFFERENTIAL timing (round-3 protocol, see diff_time): the reference's
    # "timed epochs after warm-up" quantity (GPU/PGCN.py:202-228) free of
    # the per-call dispatch + readback constant.
    #
    # ``step_dispatch`` times one step() dispatch per epoch instead of the
    # fused on-device fori sweep — the stale-pipelining A/B runs both arms
    # this way: the CPU runtime overlaps the stale mode's consumer-less
    # all_to_all across step boundaries in per-step dispatch, but executes
    # fori bodies without that freedom, so the fused sweep would hide the
    # very effect being measured (dispatch cost still cancels in the
    # differential).
    if step_dispatch:
        def make_run(nep):
            def run():
                loss = None
                for _ in range(nep):
                    loss = trainer.step(data, sync=False)
                return float(loss)        # in-order dispatch: syncs the run
            return run
    else:
        def make_run(nep):
            def run():
                losses = trainer.run_epochs(data, nep, sync=False)
                return float(losses[-1])          # scalar readback = sync
            return run

    epoch_s = diff_time(make_run, 1, max(3, epochs))
    if model == "gcn" and plan.symmetric:
        if "pallas_tb" in trainer._fwd_static:
            # the trainer auto-selected the Pallas VMEM aggregator: the ELL
            # gather model below does not describe the compiled program, so
            # emitting achieved_gather_GBs / stream_ceiling_frac would
            # describe a program that didn't run — say so instead
            part_metrics["roofline_skipped"] = (
                "pallas aggregator selected (plan tables fit VMEM); the ELL "
                "gather-stream roofline does not describe this program")
        else:
            # roofline self-description: achieved
            # gathered GB/s vs the measured stream ceiling, from the SAME
            # analytic cost model the run-telemetry subsystem attributes
            # per-step events with (sgcn_tpu.obs.attribution — this used to
            # be hand-rolled here).  Plan fields are per-chip padded sizes,
            # so this is per-chip traffic (= global when k=1); bf16 compute
            # gathers 2-byte lanes
            from sgcn_tpu.obs.attribution import (roofline_fields, step_cost)
            cost = step_cost(plan, feats.shape[1], widths,
                             compute_dtype=dtype,
                             comm_schedule=trainer.comm_schedule)
            roof = roofline_fields(
                cost, epoch_s,
                device_kind=mesh.devices.flat[0].device_kind)
            part_metrics["gather_GB_per_epoch_per_chip"] = round(
                cost.gather_bytes / 1e9, 3)
            part_metrics["achieved_gather_GBs"] = round(
                roof["achieved_gather_GBs"], 1)
            if "stream_ceiling_frac" in roof:
                part_metrics["stream_ceiling_frac"] = round(
                    roof["stream_ceiling_frac"], 3)
            part_metrics["model_step_GFLOP"] = roof["model_step_GFLOP"]
    return epoch_s, part_metrics


def bench_minibatch(ahat, feats, labels, widths, batch_size: int,
                    epochs: int, k: int, dtype: str | None = None,
                    comm_schedule: str | None = None):
    """Mini-batch trainer epoch (PGCN-Mini-batch role, Reddit-config shape):
    one pass over all pre-sampled batches, run as ONE on-device program
    (``run_epochs_fused``) on ``k`` chips and timed differentially like the
    flagship."""
    from sgcn_tpu.train.minibatch import MiniBatchTrainer

    n = ahat.shape[0]
    if k > 1:
        from sgcn_tpu.partition import partition_hypergraph_colnet
        pv, _ = partition_hypergraph_colnet(ahat, k, seed=0)
    else:
        pv = np.zeros(n, dtype=np.int64)
    tr = MiniBatchTrainer(ahat, pv, k, fin=feats.shape[1], widths=widths,
                          batch_size=batch_size, compute_dtype=dtype,
                          comm_schedule=comm_schedule)

    def make_run(nep):
        def run():
            losses = tr.run_epochs_fused(feats, labels, epochs=nep,
                                         sync=False)
            return float(losses[-1])
        return run

    epoch_s = diff_time(make_run, 1, max(3, epochs))
    return epoch_s, {
        "nbatches": len(tr.plans),
        "batch_size": batch_size,
        # the RESOLVED transport — never measure one schedule while the
        # JSON claims another (same honesty rule as the flagship block).
        # Per-EXCHANGE wire rows are uniform across batches (all plans
        # share one padded envelope), so plans[0] speaks for every exchange
        # — same key, same semantics as the flagship/CommStats figure
        "comm_schedule": tr.inner.comm_schedule,
        "wire_rows_per_exchange":
            tr.plans[0].wire_rows_per_exchange(tr.inner.comm_schedule),
        "padding_efficiency": round(
            sum(int(p.predicted_send_volume.sum()) for p in tr.plans)
            / max(sum(p.wire_rows_per_exchange(tr.inner.comm_schedule)
                      for p in tr.plans), 1), 6),
        # deterministic per-epoch figure (the trainer-level CommStats
        # counters accumulate over warm-ups/retries and are not a metric)
        "comm_volume_rows_per_epoch":
            sum(int(p.predicted_send_volume.sum()) for p in tr.plans)
            * 2 * len(widths),
    }


# The roofline vocabulary (measured stream ceiling, gather-byte model) moved
# to sgcn_tpu/obs/attribution.py — ONE cost model shared by this bench, the
# per-step run-telemetry events, and scripts/obs_report.py.


def bench_dense_equiv(n: int, fin: int, widths, epochs: int) -> float:
    """Dense-matmul roofline epoch at identical shapes — the honest
    single-chip yardstick next to the torch-CPU comparison.

    Same layer stack, loss, backward, and Adam update, but each sparse
    aggregation Â·H is replaced by an (n,f)×(f,f) dense matmul over the same
    activation rows.  That stand-in does strictly MORE FLOPs than the SpMM
    (2·n·f² vs 2·nnz·f, ~9× at ogbn-arxiv shape) while mapping perfectly to
    the MXU, so ``epoch_s / dense_equiv_s`` isolates how much the gather-bound
    sparse path costs relative to a compiler-friendly dense epoch."""
    import jax
    import jax.numpy as jnp
    import optax

    key = jax.random.PRNGKey(0)
    dims = list(zip([fin] + widths[:-1], widths))
    keys = jax.random.split(key, len(dims) + 1)
    params = [jax.random.normal(k, d, jnp.float32) * 0.05
              for k, d in zip(keys[:-1], dims)]
    mixers = [jnp.eye(i, dtype=jnp.float32) for i, _ in dims]
    h0 = jax.random.normal(keys[-1], (n, fin), jnp.float32)
    labels = jnp.zeros((n,), jnp.int32)
    opt = optax.adam(0.01)
    opt_state = opt.init(params)

    def loss_fn(ps):
        h = h0
        for i, (w, m) in enumerate(zip(ps, mixers)):
            z = (h @ m) @ w
            h = z if i == len(ps) - 1 else jax.nn.relu(z)
        logp = jax.nn.log_softmax(h)
        return -logp[jnp.arange(n), labels].mean()

    def multi(nep):
        @jax.jit
        def run(ps, st):
            def body(i, c):
                ps, st, _ = c
                loss, g = jax.value_and_grad(loss_fn)(ps)
                up, st = opt.update(g, st, ps)
                return optax.apply_updates(ps, up), st, loss
            return jax.lax.fori_loop(0, nep, body,
                                     (ps, st, jnp.float32(0)))
        return run

    # same differential protocol as bench_jax
    compiled = {}                 # nep -> jitted program (reused across retries)

    def make_run(nep):
        if nep not in compiled:
            compiled[nep] = multi(nep)
        run = compiled[nep]
        return lambda: float(run(params, opt_state)[2])

    try:
        return diff_time(make_run, 1, max(3, epochs))
    except RuntimeError:
        return float("nan")   # diagnostic yardstick only; caller emits null


def bench_torch_reference(ahat, feats, labels, widths, epochs: int) -> float:
    """Reference-style torch implementation (sparse mm + Linear + ReLU),
    same math as GPU/PGCN.py:136-148 on one process."""
    import torch
    import torch.nn.functional as F

    coo = ahat.tocoo()
    idx = torch.tensor(np.stack([coo.row, coo.col]), dtype=torch.long)
    a = torch.sparse_coo_tensor(idx, torch.tensor(coo.data), coo.shape).coalesce()
    h0 = torch.tensor(feats)
    y = torch.tensor(labels, dtype=torch.long)
    dims = list(zip([feats.shape[1]] + widths[:-1], widths))
    ws = [torch.nn.Parameter(torch.empty(i, o)) for i, o in dims]
    for w in ws:
        torch.nn.init.xavier_uniform_(w)
    opt = torch.optim.Adam(ws, lr=0.01)

    def epoch():
        opt.zero_grad()
        h = h0
        for i, w in enumerate(ws):
            z = torch.sparse.mm(a, h) @ w
            h = z if i == len(ws) - 1 else F.relu(z)
        loss = F.cross_entropy(h, y)
        loss.backward()
        opt.step()

    epoch()                                   # warm-up
    t0 = time.perf_counter()
    for _ in range(epochs):
        epoch()
    return (time.perf_counter() - t0) / epochs


def _run_vdev_child(n: int, avg_deg: int, f: int, widths, epochs: int,
                    graph: str, extra_args=(), timeout_s: int = 1200):
    """Run one flagship config on the virtual 8-device CPU mesh in a
    subprocess (the test conftest's recipe) and return its
    parsed one-line JSON.  Raises on child failure/timeout — callers decide
    how to degrade."""
    env = dict(os.environ)
    flags = [x for x in env.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in x]
    flags.append("--xla_force_host_platform_device_count=8")
    env["XLA_FLAGS"] = " ".join(flags)
    env["JAX_PLATFORMS"] = "cpu"
    env["SGCN_RESTARTS"] = "1"
    cmd = [sys.executable, os.path.abspath(__file__), "--vdev-child",
           "-n", str(n), "--avg-deg", str(avg_deg), "-f", str(f),
           "--hidden", str(widths[0]), "--classes", str(widths[-1]),
           "-l", str(len(widths)), "-e", str(epochs), "--skip-torch",
           "--chips", "8", "--graph", graph, *extra_args]
    proc = subprocess.run(cmd, env=env, capture_output=True, text=True,
                          timeout=timeout_s,
                          cwd=os.path.dirname(os.path.abspath(__file__)))
    if proc.returncode != 0:
        raise RuntimeError(f"rc={proc.returncode}: {proc.stderr[-500:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def bench_vdev_partitioned(n: int, avg_deg: int, f: int, widths, epochs: int,
                           graph: str = "ba"):
    """Measure the actual distributed algorithm on a virtual 8-device CPU
    mesh: hp-partitioned graph, real halo exchanges (all_to_all) every layer,
    grad psum — the paper's core protocol (GPU/PGCN.py:202-238) — whatever
    chips the host has (the child never wants them).  Re-execs this script in a subprocess with
    the conftest env and parses its one-line JSON.  Returns a degraded
    partial block on any child failure (the flagship number must not die
    with the diagnostic one).

    The child graph defaults to the power-law (ba) family — the profile of
    the real ogbn graphs — and the child partitions live with one multilevel
    restart (SGCN_RESTARTS=1) so the partitioner fits the child's time
    budget; the full-restart partitioner quality evidence lives in the
    products_partition artifact instead."""
    try:
        child = _run_vdev_child(n, avg_deg, f, widths, epochs, graph)
        return {
            "epoch_s_8dev_cpu": child["value"],
            "n_8dev": n,
            "graph_8dev": graph,
            "partitioner_8dev": child.get("partitioner"),
            "km1_8dev": child.get("km1"),
            "comm_volume_rows_8dev": child.get("comm_volume_rows"),
            "comm_messages_8dev": child.get("comm_messages"),
        }
    except subprocess.TimeoutExpired as e:      # noqa: F841 — diagnostic path
        print("# vdev8 run exceeded its deadline", file=sys.stderr)
        return {"epoch_s_8dev_cpu": None, "vdev_degraded": "deadline"}
    except Exception as e:                      # noqa: BLE001 — diagnostic path
        print(f"# vdev8 run failed: {e!r}", file=sys.stderr)
        return {"epoch_s_8dev_cpu": None, "vdev_degraded": repr(e)[:200]}


def bench_stale_ab(n: int, avg_deg: int, f: int, widths, epochs: int,
                   graph: str):
    """A/B the exact vs pipelined (staleness-1) exchange on the 8-virtual-
    device CPU mesh — the measurable form of "the exchange left the critical
    path" this box can produce without an 8-chip ICI mesh.  BOTH arms run in
    ONE child process (``--stale-ab-child``), sharing the graph, partition,
    plan, data and process state, interleaved exact→stale→exact — the
    between-process variance of separate children (~±20% on a 2-core host)
    is larger than the effect and would make the comparison a coin flip.
    Degrades to a marked partial block on child failure."""
    block: dict = {"stale_ab_8dev": None}
    try:
        child = _run_vdev_child(
            n, avg_deg, f, widths, epochs, graph,
            extra_args=("--stale-ab-child",))
        child.pop("metric", None)
        child.pop("value", None)
        block["stale_ab_8dev"] = child
        return block
    except subprocess.TimeoutExpired:
        print("# stale A/B run exceeded its deadline", file=sys.stderr)
        block["stale_ab_degraded"] = "deadline"
        return block
    except Exception as e:                      # noqa: BLE001 — diagnostic path
        print(f"# stale A/B run failed: {e!r}", file=sys.stderr)
        block["stale_ab_degraded"] = repr(e)[:200]
        return block


def bench_stale_ab_child(ahat, feats, labels, widths, epochs: int,
                         graph: str) -> dict:
    """One-process exact-vs-staleness-1 A/B (the ``--stale-ab-child`` body).

    One plan, one mesh, both trainers; per-step dispatch timing for both
    arms (the mode in which the runtime may float the stale a2a across the
    step boundary — a fused fori sweep executes loop bodies without that
    freedom and hides the effect).  The exact arm is timed BEFORE and AFTER
    the stale arm and averaged, so slow machine drift cancels instead of
    crediting either arm.  The stale arm is pure pipelining: stale feature
    and gradient exchanges, no delta wire, no periodic sync."""
    import jax

    from sgcn_tpu.parallel import build_comm_plan, make_mesh_1d
    from sgcn_tpu.parallel.mesh import shard_stacked
    from sgcn_tpu.partition import partition_hypergraph_colnet
    from sgcn_tpu.train import FullBatchTrainer, make_train_data

    k = len(jax.devices())
    n = ahat.shape[0]
    if k > 1:
        pv, km1 = partition_hypergraph_colnet(ahat, k, seed=0)
    else:
        pv, km1 = np.zeros(n, dtype=np.int64), 0
    plan = build_comm_plan(ahat, pv, k)
    mesh = make_mesh_1d(k)
    data = make_train_data(plan, feats, labels)
    data = type(data)(**shard_stacked(mesh, vars(data)))

    def arm(**kw):
        tr = FullBatchTrainer(plan, fin=feats.shape[1], widths=widths,
                              mesh=mesh, **kw)

        def make_run(nep):
            def run():
                loss = None
                for _ in range(nep):
                    loss = tr.step(data, sync=False)
                return float(loss)    # in-order dispatch syncs the run
            return run
        return make_run

    # arm-level measured span (never per-step: instrumentation inside the
    # timed differential loop would perturb the measurement itself) — lands
    # in the parent bench's run dir through the inherited $SGCN_METRICS_OUT
    from sgcn_tpu.obs.tracing import scoped_span
    with scoped_span("bench:stale_ab", phase="ab_child",
                     detail=f"n={n} graph={graph}"):
        exact_s, stale_s, clean = paired_differential(
            arm(), arm(halo_staleness=1), max(8, epochs), what="stale A/B")
    return {
        "epoch_s_exact": round(exact_s, 6),
        "epoch_s_stale1": round(stale_s, 6),
        # the A/B delta IS the exposed-comm time estimate: same program
        # minus the per-layer exchange dependence
        "exposed_comm_s_estimate": round(exact_s - stale_s, 6),
        "stale_speedup": round(exact_s / stale_s, 3),
        "clean_pairs": clean,
        "n": n, "graph": graph, "km1": int(km1),
        "timing": "per-step dispatch, one process, rep-level paired "
                  "differentials (see paired_differential)",
    }


def bench_ragged_ab(n: int, avg_deg: int, f: int, widths, epochs: int,
                    graph: str = "ba", model: str = "gcn"):
    """A/B the dense a2a vs the ragged ppermute-ring schedule on the
    8-virtual-device CPU mesh, across one BALANCED (random) and one SKEWED
    (native hp) partition of the same power-law graph — the configs where
    the padded/true ratio differs most (docs/comm_schedule.md).  One child
    process runs all four arms over shared process state (the
    between-process variance lesson of ``bench_stale_ab``).  Degrades to a
    marked partial block on child failure.  ``model='gat'`` runs the SAME
    harness with the GAT trainer (the ``gat_ragged_ab_8dev`` block): the
    ring then carries the ``(fout+1)``-lane attention tables in both
    exchange directions."""
    prefix = "ragged_ab" if model == "gcn" else "gat_ragged_ab"
    block: dict = {f"{prefix}_8dev": None}
    try:
        child = _run_vdev_child(n, avg_deg, f, widths, epochs, graph,
                                extra_args=(f"--{prefix.replace('_', '-')}"
                                            "-child",))
        child.pop("metric", None)
        child.pop("value", None)
        block[f"{prefix}_8dev"] = child
        return block
    except subprocess.TimeoutExpired:
        print(f"# {model} ragged A/B run exceeded its deadline",
              file=sys.stderr)
        block[f"{prefix}_degraded"] = "deadline"
        return block
    except Exception as e:                      # noqa: BLE001 — diagnostic path
        print(f"# {model} ragged A/B run failed: {e!r}", file=sys.stderr)
        block[f"{prefix}_degraded"] = repr(e)[:200]
        return block


def bench_ragged_ab_child(ahat, feats, labels, widths, epochs: int,
                          graph: str, model: str = "gcn") -> dict:
    """One-process a2a-vs-ragged A/B (the ``--ragged-ab-child`` /
    ``--gat-ragged-ab-child`` body).

    Per partition (balanced random, skewed hp): one plan, one mesh, both
    schedule trainers; rep-level PAIRED differentials exactly like
    ``bench_stale_ab_child`` (this 2-core host drifts too much for
    separately timed phases); per-step dispatch so neither arm hides
    behind the fused sweep.  Each config emits the padded/true wire-row
    ratio next to its timings — the quantity the ragged schedule exists to
    shrink.  The wire-row win on the skewed partition is ASSERTED here (and
    re-checked by ``scripts/validate_bench.py``): epoch speed on the
    virtual CPU mesh is reported honestly but never the claim — no ICI, so
    the byte win is the TPU-relevant figure."""
    import jax

    from sgcn_tpu.parallel import build_comm_plan, make_mesh_1d
    from sgcn_tpu.parallel.mesh import shard_stacked
    from sgcn_tpu.partition import (balanced_random_partition,
                                    partition_hypergraph_colnet)
    from sgcn_tpu.train import FullBatchTrainer, make_train_data

    k = len(jax.devices())
    n = ahat.shape[0]
    out: dict = {"n": n, "graph": graph, "k": k, "model": model,
                 "timing": "per-step dispatch, one process, rep-level "
                           "paired differentials (see paired_differential)"}
    parts: list[tuple[str, np.ndarray, int | None]] = [
        ("random", balanced_random_partition(n, k, seed=1), None)]
    if k > 1:
        pv_hp, km1 = partition_hypergraph_colnet(ahat, k, seed=0)
        parts.append(("hp", pv_hp, int(km1)))
    mesh = make_mesh_1d(k)
    nep = max(6, epochs)
    model_kw = ({"model": "gat", "activation": "none"}
                if model == "gat" else {})
    for name, pv, km1 in parts:
        plan = build_comm_plan(ahat, pv, k)
        plan.ensure_ragged()
        data = make_train_data(plan, feats, labels)
        data = type(data)(**shard_stacked(mesh, vars(data)))

        def arm(schedule):
            tr = FullBatchTrainer(plan, fin=feats.shape[1], widths=widths,
                                  mesh=mesh, comm_schedule=schedule,
                                  **model_kw)

            def make_run(n_ep):
                def run():
                    loss = None
                    for _ in range(n_ep):
                        loss = tr.step(data, sync=False)
                    return float(loss)    # in-order dispatch syncs the run
                return run
            return make_run

        # arm-level span (see bench_stale_ab_child: never inside the loop)
        from sgcn_tpu.obs.tracing import scoped_span
        with scoped_span(f"bench:{model}_ragged_ab:{name}",
                         phase="ab_child", detail=f"n={n} graph={graph}"):
            a2a_s, rag_s, clean = paired_differential(
                arm("a2a"), arm("ragged"), nep,
                what=f"{model} ragged A/B ({name})")
        true = int(plan.predicted_send_volume.sum())
        wire_a2a = plan.wire_rows_per_exchange("a2a")
        wire_rag = plan.wire_rows_per_exchange("ragged")
        if name == "hp" and not wire_rag < wire_a2a:
            # the acceptance invariant of the schedule: per-round pads must
            # beat the global pad on the skewed partition
            raise RuntimeError(
                f"{model} ragged A/B (hp): wire_rows_ragged={wire_rag} not "
                f"below wire_rows_a2a={wire_a2a}")
        cfg = {
            "epoch_s_a2a": round(a2a_s, 6),
            "epoch_s_ragged": round(rag_s, 6),
            "ragged_speedup": round(a2a_s / rag_s, 3),
            "clean_pairs": clean,
            "padding_efficiency": round(plan.padding_efficiency(), 6),
            # the padded/true wire-row ratio of each schedule — the dense
            # a2a's is the overhead the ragged ring deletes
            "padded_true_ratio_a2a": (round(wire_a2a / true, 3)
                                      if true else None),
            "padded_true_ratio_ragged": (round(wire_rag / true, 3)
                                         if true else None),
            "wire_rows_a2a": wire_a2a,
            "wire_rows_ragged": wire_rag,
            "true_rows": true,
            "rounds": len(plan.rr_sizes),
        }
        if km1 is not None:
            cfg["km1"] = km1
        out[name] = cfg
    return out


def bench_pallas_ragged_ab(n: int, avg_deg: int, f: int, widths, epochs: int,
                           graph: str = "ba"):
    """Three-way A/B of the schedule-agnostic Pallas aggregation
    (``pallas_ragged_ab_8dev``, ISSUE 15): ELL-ragged vs Pallas-ragged vs
    Pallas-a2a on the 8-virtual-device CPU mesh over the skewed hp
    partition.  EMULATE-mode (no TPU here — the kernel's jnp emulation
    runs, so CPU epoch time is reported honestly and is NEVER the claim);
    the acceptance figures are the DETERMINISTIC counters: the Pallas
    ragged arm ships wire rows identical to the ELL ragged arm's, carries
    ZERO analytic HBM halo-table bytes (the ring receives feed the kernel
    directly), and trains f32-bit-identically to the Pallas a2a arm.
    Degrades to a marked partial block on child failure."""
    block: dict = {"pallas_ragged_ab_8dev": None}
    try:
        child = _run_vdev_child(n, avg_deg, f, widths, epochs, graph,
                                extra_args=("--pallas-ragged-ab-child",))
        child.pop("metric", None)
        child.pop("value", None)
        block["pallas_ragged_ab_8dev"] = child
        return block
    except subprocess.TimeoutExpired:
        print("# pallas ragged A/B run exceeded its deadline",
              file=sys.stderr)
        block["pallas_ragged_ab_degraded"] = "deadline"
        return block
    except Exception as e:                      # noqa: BLE001 — diagnostic path
        print(f"# pallas ragged A/B run failed: {e!r}", file=sys.stderr)
        block["pallas_ragged_ab_degraded"] = repr(e)[:200]
        return block


def bench_pallas_ragged_ab_child(ahat, feats, labels, widths, epochs: int,
                                 graph: str) -> dict:
    """One-process kernel × schedule A/B (the ``--pallas-ragged-ab-child``
    body): arms ``ell_ragged`` / ``pallas_ragged`` / ``pallas_a2a`` over
    the skewed hp partition, rep-level paired differentials
    (``paired_differential_multi``).  The VMEM budget is forced generous
    and ``SGCN_PALLAS_SPMM=1`` pins the selection for the pallas arms —
    off-TPU the kernel runs in emulate mode, so the epoch times describe
    THIS host's XLA programs (honest, never the claim); the asserted
    figures are plan-derived deterministic counters."""
    import jax

    from sgcn_tpu.models.gcn import exchange_widths
    from sgcn_tpu.ops.pallas_spmm import pallas_spmm_fits
    from sgcn_tpu.parallel import build_comm_plan, make_mesh_1d
    from sgcn_tpu.parallel.mesh import shard_stacked
    from sgcn_tpu.partition import partition_hypergraph_colnet
    from sgcn_tpu.train import FullBatchTrainer, make_train_data

    k = len(jax.devices())
    n = ahat.shape[0]
    out: dict = {"n": n, "graph": graph, "k": k,
                 "timing": "per-step dispatch, one process, rep-level "
                           "paired differentials; EMULATE-mode kernels "
                           "(CPU mesh) — epoch speed is reported "
                           "honestly but is never the claim; the "
                           "deterministic counters are"}
    pv, km1 = partition_hypergraph_colnet(ahat, k, seed=0)
    out["km1"] = int(km1)
    plan = build_comm_plan(ahat, pv, k)
    plan.ensure_ragged()
    os.environ["SGCN_PALLAS_VMEM"] = str(256 * 1024 * 1024)
    assert pallas_spmm_fits(plan, feats.shape[1], widths,
                            schedule="ragged")
    mesh = make_mesh_1d(k)
    data = make_train_data(plan, feats, labels)
    data = type(data)(**shard_stacked(mesh, vars(data)))
    nep = max(6, epochs)

    arms = (("ell_ragged", "0", "ragged"),
            ("pallas_ragged", "1", "ragged"),
            ("pallas_a2a", "1", "a2a"))
    trainers = {}

    def make_trainer(env, schedule):
        os.environ["SGCN_PALLAS_SPMM"] = env
        try:
            return FullBatchTrainer(plan, fin=feats.shape[1],
                                    widths=widths, mesh=mesh,
                                    comm_schedule=schedule, seed=2)
        finally:
            os.environ.pop("SGCN_PALLAS_SPMM", None)

    def arm(name, env, schedule):
        tr = make_trainer(env, schedule)
        assert ("pallas_tb" in tr._fwd_static) == env.startswith("1")
        trainers[name] = tr

        def make_run(n_ep):
            def run():
                loss = None
                for _ in range(n_ep):
                    loss = tr.step(data, sync=False)
                return float(loss)
            return run
        return make_run

    from sgcn_tpu.obs.tracing import scoped_span
    with scoped_span("bench:pallas_ragged_ab:hp", phase="ab_child",
                     detail=f"n={n} graph={graph}"):
        times, clean = paired_differential_multi(
            [arm(*a) for a in arms], nep, what="pallas ragged A/B (hp)")

    # f32 bit-identity between the two pallas arms (same tile fold order
    # across transports — the tentpole parity contract, asserted on fresh
    # trainers so the timed state does not leak in)
    losses = {}
    for name, env, schedule in arms[1:]:
        tr = make_trainer(env, schedule)
        losses[name] = [float(tr.step(data)) for _ in range(3)]
    if losses["pallas_ragged"] != losses["pallas_a2a"]:
        raise RuntimeError(
            f"pallas ragged/a2a losses not bit-identical: {losses}")

    # deterministic counters: identical ragged wire, zero halo-table bytes
    # in the pallas-ragged arm's analytic roofline
    wire_rag = plan.wire_rows_per_exchange("ragged")
    wire_a2a = plan.wire_rows_per_exchange("a2a")
    fs = exchange_widths(feats.shape[1], list(widths))
    halo_tab_a2a = 2 * sum(int(plan.r) * int(f_) * 4 for f_ in fs) * k
    for (name, _env, schedule), t in zip(arms, times):
        cfg = {
            "epoch_s": round(t, 6),
            "measured": True,
            "wire_rows_per_exchange": (wire_rag if schedule == "ragged"
                                       else wire_a2a),
            # per-step bytes of materialized (R, f_ℓ) halo tables across
            # the mesh (fwd+bwd): the ragged arms fold receives directly
            # (ELL: redge scatter-add; pallas: in-kernel), only the dense
            # a2a assembles halo tables
            "halo_table_bytes_per_step": (0 if schedule == "ragged"
                                          else halo_tab_a2a),
        }
        out[name] = cfg
    out["clean_reps"] = clean
    out["true_rows"] = int(plan.predicted_send_volume.sum())
    if not out["pallas_ragged"]["wire_rows_per_exchange"] == \
            out["ell_ragged"]["wire_rows_per_exchange"]:
        raise RuntimeError("pallas ragged arm's wire differs from ELL "
                           "ragged's — the transport must be untouched")
    if out["pallas_ragged"]["halo_table_bytes_per_step"] != 0:
        raise RuntimeError("pallas ragged arm books halo-table bytes")
    out["pallas_dispatch"] = trainers["pallas_ragged"].comm_decision.get(
        "pallas_dispatch")
    return out


def bench_ragged_stale_ab(n: int, avg_deg: int, f: int, widths, epochs: int,
                          graph: str = "ba"):
    """Three-way A/B of the COMPOSED mode (``ragged_stale_ab_8dev``):
    a2a+stale vs ragged+exact vs ragged+stale on the 8-virtual-device CPU
    mesh over the skewed hp partition — the configs whose union the
    composition claims to beat.  One child process runs all three arms over
    shared state (the between-process variance lesson of
    ``bench_stale_ab``); degrades to a marked partial block on failure."""
    block: dict = {"ragged_stale_ab_8dev": None}
    try:
        child = _run_vdev_child(n, avg_deg, f, widths, epochs, graph,
                                extra_args=("--ragged-stale-ab-child",))
        child.pop("metric", None)
        child.pop("value", None)
        block["ragged_stale_ab_8dev"] = child
        return block
    except subprocess.TimeoutExpired:
        print("# ragged-stale A/B run exceeded its deadline", file=sys.stderr)
        block["ragged_stale_ab_degraded"] = "deadline"
        return block
    except Exception as e:                      # noqa: BLE001 — diagnostic path
        print(f"# ragged-stale A/B run failed: {e!r}", file=sys.stderr)
        block["ragged_stale_ab_degraded"] = repr(e)[:200]
        return block


def bench_ragged_stale_ab_child(ahat, feats, labels, widths, epochs: int,
                                graph: str, sync_every: int = 4) -> dict:
    """One-process three-way A/B (the ``--ragged-stale-ab-child`` body):
    the composed (ragged + staleness-1) mode against BOTH single levers on
    the same hp-partitioned plan, mesh and data.

    The asserted figure is the EXPOSED-COMM accounting, not CPU-mesh epoch
    speed (no ICI here — timings are reported honestly but are not the
    claim): per arm, the exposed-comm fraction (exposed / total exchanges
    from ``CommStats`` over the steps the arm actually ran) and the average
    exposed wire rows per step it implies.  The composed arm must be ≤ both
    single levers on the fraction and STRICTLY below both on exposed wire
    rows per step: vs ragged+exact because most of its steps are hidden,
    vs a2a+stale because its exposed (sync) steps ship the ragged ring's
    smaller wire.  Both inequalities are asserted here and re-checked by
    ``scripts/validate_bench.py``."""
    import jax

    from sgcn_tpu.parallel import build_comm_plan, make_mesh_1d
    from sgcn_tpu.parallel.mesh import shard_stacked
    from sgcn_tpu.partition import partition_hypergraph_colnet
    from sgcn_tpu.train import FullBatchTrainer, make_train_data

    k = len(jax.devices())
    n = ahat.shape[0]
    if k > 1:
        pv, km1 = partition_hypergraph_colnet(ahat, k, seed=0)
    else:
        pv, km1 = np.zeros(n, dtype=np.int64), 0
    plan = build_comm_plan(ahat, pv, k)
    plan.ensure_ragged()
    mesh = make_mesh_1d(k)
    data = make_train_data(plan, feats, labels)
    data = type(data)(**shard_stacked(mesh, vars(data)))

    arms_spec = {
        "a2a_stale": dict(comm_schedule="a2a", halo_staleness=1,
                          sync_every=sync_every),
        "ragged_exact": dict(comm_schedule="ragged"),
        "ragged_stale": dict(comm_schedule="ragged", halo_staleness=1,
                             sync_every=sync_every),
    }
    trainers = {name: FullBatchTrainer(plan, fin=feats.shape[1],
                                       widths=widths, mesh=mesh, **kw)
                for name, kw in arms_spec.items()}

    def make(tr):
        def make_run(nep):
            def run():
                loss = None
                for _ in range(nep):
                    loss = tr.step(data, sync=False)
                return float(loss)    # in-order dispatch syncs the run
            return run
        return make_run

    names = list(trainers)
    # arm-level span (see bench_stale_ab_child: never inside the loop)
    from sgcn_tpu.obs.tracing import scoped_span
    with scoped_span("bench:ragged_stale_ab", phase="ab_child",
                     detail=f"n={n} graph={graph}"):
        times, clean = paired_differential_multi(
            [make(trainers[nm]) for nm in names], max(6, epochs),
            what="ragged-stale A/B")
    nl = len(widths)
    arms: dict = {}
    for nm, t in zip(names, times):
        rep = trainers[nm].stats.report()
        frac = (rep["exposed_exchanges"] / rep["exchanges"]
                if rep["exchanges"] else 1.0)
        arms[nm] = {
            "epoch_s": round(t, 6),
            "wire_rows_per_exchange": rep["wire_rows_per_exchange"],
            "exposed_comm_frac": round(frac, 6),
            # average exposed wire rows per training step (2L exchanges) —
            # the schedule-and-staleness-aware cost the composition shrinks
            "exposed_wire_rows_per_step": round(
                frac * rep["wire_rows_per_exchange"] * 2 * nl, 2),
        }
    comp, a2s, rex = (arms["ragged_stale"], arms["a2a_stale"],
                      arms["ragged_exact"])
    # the composition's acceptance inequality — never epoch speed
    if not (comp["exposed_comm_frac"] <= a2s["exposed_comm_frac"]
            and comp["exposed_comm_frac"] <= rex["exposed_comm_frac"]):
        raise RuntimeError(
            f"composed exposed_comm_frac {comp['exposed_comm_frac']} not "
            f"<= both single levers ({a2s['exposed_comm_frac']}, "
            f"{rex['exposed_comm_frac']})")
    if not (comp["exposed_wire_rows_per_step"]
            < a2s["exposed_wire_rows_per_step"]
            and comp["exposed_wire_rows_per_step"]
            < rex["exposed_wire_rows_per_step"]):
        raise RuntimeError(
            f"composed exposed wire rows {comp['exposed_wire_rows_per_step']}"
            f" not strictly below both single levers "
            f"({a2s['exposed_wire_rows_per_step']}, "
            f"{rex['exposed_wire_rows_per_step']})")
    return {
        "n": n, "graph": graph, "k": k, "km1": int(km1),
        "sync_every": sync_every,
        "clean_pairs": clean,
        "padding_efficiency": round(plan.padding_efficiency(), 6),
        "true_rows": int(plan.predicted_send_volume.sum()),
        "arms": arms,
        "note": "CPU-mesh epoch speed is reported honestly but is NOT the "
                "asserted figure (no ICI; k-1 ring dispatches are host "
                "overhead here) — the acceptance figure is the exposed-comm "
                "accounting: the composed arm's exposed fraction <= both "
                "single levers and its exposed wire rows per step strictly "
                "below both",
        "timing": "per-step dispatch, one process, rep-level paired "
                  "differentials across all three arms "
                  "(see paired_differential_multi)",
    }


def bench_replica_ab(n: int, avg_deg: int, f: int, widths, epochs: int,
                     graph: str = "ba"):
    """A/B hot-halo replication (``--replica-budget``) against the
    no-replica trainer on the 8-virtual-device CPU mesh, across one
    BALANCED (random) and one SKEWED (native cache-aware hp) partition of
    the same power-law graph — the ``replica_ab_8dev`` block
    (docs/replication.md).  One child process runs all four arms over
    shared state; degrades to a marked partial block on child failure."""
    block: dict = {"replica_ab_8dev": None}
    try:
        child = _run_vdev_child(n, avg_deg, f, widths, epochs, graph,
                                extra_args=("--replica-ab-child",))
        child.pop("metric", None)
        child.pop("value", None)
        block["replica_ab_8dev"] = child
        return block
    except subprocess.TimeoutExpired:
        print("# replica A/B run exceeded its deadline", file=sys.stderr)
        block["replica_ab_degraded"] = "deadline"
        return block
    except Exception as e:                      # noqa: BLE001 — diagnostic path
        print(f"# replica A/B run failed: {e!r}", file=sys.stderr)
        block["replica_ab_degraded"] = repr(e)[:200]
        return block


def bench_replica_ab_child(ahat, feats, labels, widths, epochs: int,
                           graph: str, sync_every: int = 4) -> dict:
    """One-process replica-vs-no-replica A/B (the ``--replica-ab-child``
    body).

    Per partition (balanced random, skewed CACHE-AWARE hp — the native
    driver co-optimizing the cut with the replica budget): one plan, one
    mesh, both trainers; rep-level PAIRED differentials like every other
    one-process child.  Both arms dispatch the same step count, so the
    cumulative CommStats byte gauges are directly comparable — and the
    asserted figures are exactly the replication contract:

      * ``halo_bytes_true_total`` STRICTLY lower with B>0 on the hp arm
        (replicated rows genuinely leave the exchange — the CaPGNN
        before/after metric the ROADMAP names);
      * average wire rows per STEP strictly lower (shrunken send pads);
      * the native cache-aware km1 <= the cache-blind driver's partition
        evaluated under the SAME objective (independent numpy evaluator).

    CPU-mesh epoch speed is reported honestly but never the claim — no
    ICI, so wire bytes are the TPU-relevant figure."""
    import jax

    from sgcn_tpu.parallel import build_comm_plan, make_mesh_1d
    from sgcn_tpu.parallel.mesh import shard_stacked
    from sgcn_tpu.partition import (balanced_random_partition,
                                    partition_hypergraph_colnet,
                                    partition_hypergraph_colnet_cache)
    from sgcn_tpu.partition.native import cache_aware_km1
    from sgcn_tpu.train import FullBatchTrainer, make_train_data

    k = len(jax.devices())
    n = ahat.shape[0]
    # budget ~ the hub head of a power-law graph: n/16 rows is a few % of
    # the vertex set but a double-digit share of Σλ on BA-style skew (hubs
    # are consumed by most chips), so the A/B demonstrates a real wire win
    # while the replica tables stay small (RP × L rows per chip)
    budget = max(64, n // 16)
    nl = len(widths)
    out: dict = {"n": n, "graph": graph, "k": k, "model": "gcn",
                 "replica_budget": budget, "sync_every": sync_every,
                 "timing": "per-step dispatch, one process, rep-level "
                           "paired differentials (see paired_differential)"}
    parts: list[tuple[str, np.ndarray, dict]] = [
        ("random", balanced_random_partition(n, k, seed=1), {})]
    if k > 1:
        # the hp arm trains on the CACHE-AWARE partition; the cache-blind
        # driver's partition is scored under the SAME objective by the
        # independent numpy evaluator — the km1 acceptance inequality
        pv_blind, km1_blind = partition_hypergraph_colnet(ahat, k, seed=0)
        pv_hp, km1_hp, km1_cache = partition_hypergraph_colnet_cache(
            ahat, k, budget, seed=0)
        blind_cache = cache_aware_km1(ahat, pv_blind, budget)
        if not km1_cache <= blind_cache:
            raise RuntimeError(
                f"cache-aware km1 {km1_cache} not <= the cache-blind "
                f"partition's cache objective {blind_cache}")
        parts.append(("hp", pv_hp, {
            "km1": int(km1_hp), "km1_blind": int(km1_blind),
            "km1_cache_aware": int(km1_cache),
            "km1_cache_blind_partition": int(blind_cache)}))
    mesh = make_mesh_1d(k)
    nep = max(6, epochs)
    for name, pv, extra in parts:
        plan = build_comm_plan(ahat, pv, k)
        data = make_train_data(plan, feats, labels)
        data = type(data)(**shard_stacked(mesh, vars(data)))

        def arm(b):
            tr = FullBatchTrainer(plan, fin=feats.shape[1], widths=widths,
                                  mesh=mesh, replica_budget=b,
                                  sync_every=sync_every if b else 0)

            def make_run(n_ep):
                def run():
                    loss = None
                    for _ in range(n_ep):
                        loss = tr.step(data, sync=False)
                    return float(loss)    # in-order dispatch syncs the run
                return run
            return tr, make_run

        tr_none, mk_none = arm(0)
        tr_rep, mk_rep = arm(budget)
        # arm-level span (see bench_stale_ab_child: never inside the loop)
        from sgcn_tpu.obs.tracing import scoped_span
        with scoped_span(f"bench:replica_ab:{name}", phase="ab_child",
                         detail=f"n={n} graph={graph} B={budget}"):
            none_s, rep_s, clean = paired_differential(
                mk_none, mk_rep, nep, what=f"replica A/B ({name})")
        rn, rr = tr_none.stats.report(), tr_rep.stats.report()
        if rn["exchanges"] != rr["exchanges"]:
            raise RuntimeError(
                f"replica A/B ({name}): arms ran unequal exchange counts "
                f"({rn['exchanges']} vs {rr['exchanges']}) — totals not "
                "comparable")
        steps = rn["exchanges"] // (2 * nl)
        cfg = {
            "epoch_s_noreplica": round(none_s, 6),
            "epoch_s_replica": round(rep_s, 6),
            "replica_speedup": round(none_s / rep_s, 3),
            "clean_pairs": clean,
            "steps": steps,
            "replica_rows": int(plan.replica_rows),
            "replica_send_saving": int(plan.replica_send_saving),
            "true_rows_per_exchange": rn["true_rows_per_exchange"],
            "true_rows_per_exchange_replica":
                rr["true_rows_per_exchange_replica"],
            "wire_rows_per_exchange": rn["wire_rows_per_exchange"],
            "wire_rows_per_exchange_replica":
                rr["wire_rows_per_exchange_replica"],
            # cumulative over the SAME dispatched step sequence — the
            # before/after metric of the feature (CaPGNN, ROADMAP item 2)
            "halo_bytes_true_total_noreplica": rn["halo_bytes_true_total"],
            "halo_bytes_true_total_replica": rr["halo_bytes_true_total"],
            "wire_rows_per_step_noreplica": round(
                rn["wire_rows_total"] / steps, 2),
            "wire_rows_per_step_replica": round(
                rr["wire_rows_total"] / steps, 2),
            **extra,
        }
        if name == "hp":
            # the acceptance inequalities of the feature — STRICT on the
            # skewed partition (re-checked by scripts/validate_bench.py)
            if not (cfg["halo_bytes_true_total_replica"]
                    < cfg["halo_bytes_true_total_noreplica"]):
                raise RuntimeError(
                    f"replica A/B (hp): halo_bytes_true_total "
                    f"{cfg['halo_bytes_true_total_replica']} not below "
                    f"{cfg['halo_bytes_true_total_noreplica']}")
            if not (cfg["wire_rows_per_step_replica"]
                    < cfg["wire_rows_per_step_noreplica"]):
                raise RuntimeError(
                    f"replica A/B (hp): wire rows/step "
                    f"{cfg['wire_rows_per_step_replica']} not below "
                    f"{cfg['wire_rows_per_step_noreplica']}")
        out[name] = cfg
    out["note"] = (
        "CPU-mesh epoch speed is reported honestly but is NOT the asserted "
        "figure (no ICI) — the acceptance figures are the wire/true-byte "
        "accounting: halo_bytes_true_total and wire rows/step strictly "
        "lower with B>0 on the hp arm, and cache-aware km1 <= the "
        "cache-blind partition's cache objective")
    return out


def bench_controller_ab(n: int, avg_deg: int, f: int, widths, epochs: int,
                        graph: str = "ba"):
    """A/B the adaptive communication controller (``--comm-schedule auto``
    + ``--replica-budget auto`` + drift-banded ``--sync-every`` retune)
    against FOUR static settings on the skewed-hp partition of a power-law
    graph — the ``controller_ab_8dev`` block (docs/comm_schedule.md).  One
    child process runs all five arms over shared state; degrades to a
    marked partial block on failure."""
    block: dict = {"controller_ab_8dev": None}
    try:
        child = _run_vdev_child(n, avg_deg, f, widths, epochs, graph,
                                extra_args=("--controller-ab-child",))
        child.pop("metric", None)
        child.pop("value", None)
        block["controller_ab_8dev"] = child
        return block
    except subprocess.TimeoutExpired:
        print("# controller A/B run exceeded its deadline", file=sys.stderr)
        block["controller_ab_degraded"] = "deadline"
        return block
    except Exception as e:                      # noqa: BLE001 — diagnostic path
        print(f"# controller A/B run failed: {e!r}", file=sys.stderr)
        block["controller_ab_degraded"] = repr(e)[:200]
        return block


def bench_controller_ab_child(ahat, feats, labels, widths, epochs: int,
                              graph: str, sync_every: int = 4) -> dict:
    """One-process controller-vs-static A/B (the ``--controller-ab-child``
    body): the adaptive controller against four static settings on the
    SAME skewed-hp-partitioned plan, mesh and data.

    The asserted figure is EXPOSED WIRE ROWS PER STEP (the
    ``exposed_wire_rows_total`` gauge over the steps each arm actually
    dispatched) — never CPU-mesh epoch time (no ICI here; timings are
    reported honestly but are not the claim).  The controller arm must be
    ≤ every static arm and STRICTLY below at least one: against the exact
    arms because its steady-state exchanges are hidden AND shrunken,
    against the stale/replica arms because its drift-banded retune can
    only widen the sync cadence when the measured drift permits (and
    holds it otherwise — a tie, never a regression).  Re-checked by
    ``scripts/validate_bench.py::check_controller_ab``."""
    import jax

    from sgcn_tpu.parallel import build_comm_plan, make_mesh_1d
    from sgcn_tpu.parallel.mesh import shard_stacked
    from sgcn_tpu.partition import partition_hypergraph_colnet
    from sgcn_tpu.train import FullBatchTrainer, make_train_data

    k = len(jax.devices())
    n = ahat.shape[0]
    if k > 1:
        pv, km1 = partition_hypergraph_colnet(ahat, k, seed=0)
    else:
        pv, km1 = np.zeros(n, dtype=np.int64), 0
    plan = build_comm_plan(ahat, pv, k)
    plan.ensure_ragged()
    mesh = make_mesh_1d(k)
    data = make_train_data(plan, feats, labels)
    data = type(data)(**shard_stacked(mesh, vars(data)))
    budget = max(64, n // 16)

    arms_spec = {
        "a2a_exact": dict(),
        "ragged_exact": dict(comm_schedule="ragged"),
        "ragged_stale": dict(comm_schedule="ragged", halo_staleness=1,
                             sync_every=sync_every),
        "replica_stale": dict(comm_schedule="ragged", halo_staleness=1,
                              replica_budget=budget,
                              sync_every=sync_every),
        "controller": dict(comm_schedule="auto", halo_staleness=1,
                           replica_budget="auto", sync_every=sync_every),
    }
    trainers = {name: FullBatchTrainer(plan, fin=feats.shape[1],
                                       widths=widths, mesh=mesh, **kw)
                for name, kw in arms_spec.items()}

    def make(tr):
        def make_run(nep):
            def run():
                loss = None
                for _ in range(nep):
                    loss = tr.step(data, sync=False)
                return float(loss)    # in-order dispatch syncs the run
            return run
        return make_run

    names = list(trainers)
    from sgcn_tpu.obs.tracing import scoped_span
    with scoped_span("bench:controller_ab", phase="ab_child",
                     detail=f"n={n} graph={graph}"):
        times, clean = paired_differential_multi(
            [make(trainers[nm]) for nm in names], max(8, epochs),
            what="controller A/B")
    nl = len(widths)
    arms: dict = {}
    for nm, t in zip(names, times):
        rep = trainers[nm].stats.report()
        steps = rep["exchanges"] // (2 * nl)
        frac = (rep["exposed_exchanges"] / rep["exchanges"]
                if rep["exchanges"] else 1.0)
        arms[nm] = {
            "epoch_s": round(t, 6),
            "steps": steps,
            "wire_rows_per_exchange": rep["wire_rows_per_exchange"],
            "exposed_comm_frac": round(frac, 6),
            # EXACT exposed wire rows per dispatched step — the subset-
            # priced gauge (full vs shrunken × exposed vs hidden) the
            # composition exists to shrink; the hidden figure shows where
            # the replica shrink lands (hidden exchanges ship nrep_* pads)
            "exposed_wire_rows_per_step": round(
                rep["exposed_wire_rows_total"] / max(steps, 1), 2),
            "hidden_wire_rows_per_step": round(
                rep["hidden_wire_rows_total"] / max(steps, 1), 2),
        }
    ctr = trainers["controller"]
    cdec = ctr.comm_decision
    arms["controller"].update(
        resolved_schedule=ctr.comm_schedule,
        replica_budget=int(ctr.replica_budget),
        sync_every_final=int(ctr.sync_every),
        retunes=len((cdec.get("controller") or {}).get("retunes", [])),
    )
    ce = arms["controller"]["exposed_wire_rows_per_step"]
    statics = [nm for nm in names if nm != "controller"]
    worse = [nm for nm in statics
             if ce > arms[nm]["exposed_wire_rows_per_step"]]
    if worse:
        raise RuntimeError(
            f"controller exposed wire rows/step {ce} above static arm(s) "
            f"{ {nm: arms[nm]['exposed_wire_rows_per_step'] for nm in worse} }")
    if not any(ce < arms[nm]["exposed_wire_rows_per_step"]
               for nm in statics):
        raise RuntimeError(
            f"controller exposed wire rows/step {ce} not STRICTLY below "
            "any static arm — the controller must beat at least one "
            "setting, not merely tie the field")
    return {
        "n": n, "graph": graph, "k": k, "km1": int(km1),
        "replica_budget": budget, "sync_every": sync_every,
        "clean_pairs": clean,
        "arms": arms,
        "note": "CPU-mesh epoch speed is reported honestly but is NOT the "
                "asserted figure (no ICI) — the acceptance figure is "
                "exposed wire rows per step: the controller arm <= every "
                "static arm, strictly below at least one",
        "timing": "per-step dispatch, one process, rep-level paired "
                  "differentials across all five arms "
                  "(see paired_differential_multi)",
    }


def bench_serve_qps(n: int, avg_deg: int, f: int, widths, graph: str = "ba"):
    """Sustained-QPS serving bench on the 8-virtual-device CPU mesh (the
    ``serve_qps_8dev`` block): synthetic open-loop traffic at a fixed
    offered rate against the forward-only serve engine
    (``sgcn_tpu/serve/``), reporting achieved QPS + p50/p99 latency per
    transport, and an a2a-vs-ragged serving A/B asserting the wire-row win
    carries over to the forward-only path.  One child process runs both
    arms over shared state (the between-process variance lesson of
    ``bench_stale_ab``); degrades to a marked partial block on failure."""
    block: dict = {"serve_qps_8dev": None}
    try:
        child = _run_vdev_child(n, avg_deg, f, widths, 2, graph,
                                extra_args=("--serve-qps-child",))
        child.pop("metric", None)
        child.pop("value", None)
        block["serve_qps_8dev"] = child
        return block
    except subprocess.TimeoutExpired:
        print("# serve QPS run exceeded its deadline", file=sys.stderr)
        block["serve_qps_degraded"] = "deadline"
        return block
    except Exception as e:                      # noqa: BLE001 — diagnostic path
        print(f"# serve QPS run failed: {e!r}", file=sys.stderr)
        block["serve_qps_degraded"] = repr(e)[:200]
        return block


def bench_serve_qps_child(ahat, feats, labels, widths, graph: str,
                          offered_qps: float = 50.0,
                          latency_budget_ms: float = 100.0,
                          max_batch: int = 16, queries: int = 200) -> dict:
    """One-process serving A/B (the ``--serve-qps-child`` body): the SAME
    hp-partitioned plan, features and open-loop query trace served through
    an a2a engine and a ragged engine back to back.

    The asserted figure is the WIRE-ROW accounting: inference has no
    gradient ring, so the forward halo exchange is the entire comm cost and
    the ragged ring must ship strictly fewer wire rows than the dense pad
    on the skewed hp partition (asserted here and re-checked by
    ``scripts/validate_bench.py``).  CPU-mesh latency/QPS are measured live
    and reported honestly — p50/p99 under ``measured: true`` provenance —
    but never the cross-transport claim (no ICI: the ring's k−1 dispatches
    are host overhead here)."""
    import jax

    from sgcn_tpu.parallel import build_comm_plan
    from sgcn_tpu.partition import partition_hypergraph_colnet
    from sgcn_tpu.serve import ServeEngine, run_loadgen, synthetic_query_ids

    k = len(jax.devices())
    n = ahat.shape[0]
    if k > 1:
        pv, km1 = partition_hypergraph_colnet(ahat, k, seed=0)
    else:
        pv, km1 = np.zeros(n, dtype=np.int64), 0
    plan = build_comm_plan(ahat, pv, k)
    plan.ensure_ragged()
    qids = synthetic_query_ids(n, queries, seed=0)
    out: dict = {
        "n": n, "graph": graph, "k": k, "km1": int(km1),
        # nnz + nlayers scope the trend series: the wire-row counters are
        # plan-derived, so a denser graph or a deeper model is a DIFFERENT
        # measurement, not a regression (the _TIME_CFG_KEYS lesson)
        "nnz": int(ahat.nnz), "nlayers": len(widths),
        "offered_qps": offered_qps,
        "latency_budget_ms": latency_budget_ms,
        "max_batch": max_batch,
        # live host-clock latency measurement from THIS process — the serve
        # flavor of the epoch-time provenance flag (validate_bench checks)
        "measured": True,
        "weights": "random-init",   # serving latency is weight-agnostic;
        #                             parity vs evaluate() is tier-1's job
        "arms": {},
        "note": "CPU-mesh latency/QPS are measured live and reported "
                "honestly but are NOT the cross-transport claim (no ICI; "
                "ring dispatches are host overhead here) — the asserted "
                "figure is the wire-row accounting: the forward exchange "
                "is serving's entire comm cost, and ragged must ship "
                "strictly fewer wire rows than a2a on the skewed hp "
                "partition",
    }
    wire = {}
    from sgcn_tpu.obs.tracing import scoped_span
    for sched in ("a2a", "ragged"):
        eng = ServeEngine(plan, fin=feats.shape[1], widths=widths,
                          comm_schedule=sched, max_batch=max_batch,
                          latency_budget_ms=latency_budget_ms, seed=0)
        eng.set_features(feats)
        eng.warmup(qids)     # every bucket, outside the measured window
        with scoped_span(f"bench:serve_qps:{sched}", phase="serve_child",
                         detail=f"n={n} graph={graph}"):
            res = run_loadgen(eng, qids, offered_qps=offered_qps)
        g = eng.gauges()
        wire[sched] = g["wire_rows_per_exchange"]
        out["arms"][sched] = {
            **res.summary(),
            "deadline_flushes": eng.batcher.deadline_flushes,
            "full_flushes": eng.batcher.full_flushes,
            "compiles": g["compiles"],
            "buckets": g["buckets"],
            "wire_rows_per_exchange": g["wire_rows_per_exchange"],
            "wire_rows_per_query": g["wire_rows_per_query"],
            "true_rows_per_exchange": g["true_rows_per_exchange"],
        }
    if k > 1 and not wire["ragged"] < wire["a2a"]:
        # the acceptance invariant carried over from training: per-round
        # pads must beat the global pad on the skewed partition
        raise RuntimeError(
            f"serve A/B (hp): wire_rows_ragged={wire['ragged']} not below "
            f"wire_rows_a2a={wire['a2a']}")
    return out


def bench_serve_subgraph(n: int, avg_deg: int, f: int, widths,
                         graph: str = "ba"):
    """Full-forward vs sub-graph serving A/B on the 8-virtual-device CPU
    mesh (the ``serve_subgraph_ab_8dev`` block): shared open-loop traffic
    against the hp partition through a ``mode='full'`` engine and a
    ``mode='subgraph'`` engine, asserting the ≥10× per-query
    FLOP/touched-row cut on the ANALYTIC gauges (docs/serving.md phase 2).

    ``avg_deg`` is capped at the CORA-LIKE sparsity the acceptance claim
    names (avg degree ~4): the receptive-set size — and therefore the cut
    — is a property of the graph's density, not of the engine (measured on
    the BA family at n=20000: deg 4 cuts rows/query ~41×, deg 10 only
    ~10× because hub 2-hop neighborhoods swallow the graph).  The block
    reports both arms' analytic figures either way, so a future denser-
    graph round is a new trend series, not a hidden regression.  Degrades
    to a marked partial block on failure."""
    avg_deg = min(int(avg_deg), 4)
    block: dict = {"serve_subgraph_ab_8dev": None}
    try:
        child = _run_vdev_child(n, avg_deg, f, widths, 2, graph,
                                extra_args=("--serve-subgraph-ab-child",))
        child.pop("metric", None)
        child.pop("value", None)
        block["serve_subgraph_ab_8dev"] = child
        return block
    except subprocess.TimeoutExpired:
        print("# serve subgraph A/B exceeded its deadline", file=sys.stderr)
        block["serve_subgraph_degraded"] = "deadline"
        return block
    except Exception as e:                      # noqa: BLE001 — diagnostic path
        print(f"# serve subgraph A/B failed: {e!r}", file=sys.stderr)
        block["serve_subgraph_degraded"] = repr(e)[:200]
        return block


def bench_serve_subgraph_child(ahat, feats, labels, widths, graph: str,
                               offered_qps: float = 50.0,
                               latency_budget_ms: float = 100.0,
                               max_batch: int = 16,
                               queries: int = 200) -> dict:
    """One-process full-vs-subgraph serving A/B (the
    ``--serve-subgraph-ab-child`` body): the SAME hp-partitioned plan,
    features and open-loop query trace served through the PR-8 full-forward
    engine and the sub-graph engine, both with double-buffered dispatch.

    The asserted figures are the ANALYTIC per-query gauges: at cora-like
    query rates a full forward computes ``k·B`` rows per micro-batch while
    the sub-graph program touches only the routed queries' L-hop receptive
    sets — both the touched-row and the FLOP per-query cut must be ≥10×
    (re-checked by ``scripts/validate_bench.py::check_serve_subgraph_ab``).
    CPU-mesh latency/QPS are measured live and reported honestly — never
    the cross-arm claim (the host-side receptive-set packing is the
    sub-graph arm's dominant cost on a no-ICI mesh; the FLOP bill is the
    TPU-relevant figure)."""
    import jax

    from sgcn_tpu.parallel import build_comm_plan
    from sgcn_tpu.partition import partition_hypergraph_colnet
    from sgcn_tpu.serve import ServeEngine, run_loadgen, synthetic_query_ids

    k = len(jax.devices())
    n = ahat.shape[0]
    if k > 1:
        pv, km1 = partition_hypergraph_colnet(ahat, k, seed=0)
    else:
        pv, km1 = np.zeros(n, dtype=np.int64), 0
    plan = build_comm_plan(ahat, pv, k)
    qids = synthetic_query_ids(n, queries, seed=0)
    out: dict = {
        "n": n, "graph": graph, "k": k, "km1": int(km1),
        "nnz": int(ahat.nnz), "nlayers": len(widths),
        "schedule": "a2a",
        "offered_qps": offered_qps,
        "latency_budget_ms": latency_budget_ms,
        "max_batch": max_batch,
        "measured": True,
        "weights": "random-init",
        "arms": {},
        "note": "CPU-mesh latency/QPS are measured live and reported "
                "honestly but are NOT the cross-arm claim (no ICI; the "
                "sub-graph arm's receptive-set packing is host overhead "
                "here) — the asserted figures are the ANALYTIC per-query "
                "gauges: touched rows/query and FLOPs/query must both cut "
                ">=10x vs the full forward at this query rate",
    }
    from sgcn_tpu.obs.tracing import scoped_span
    gauges = {}
    for arm, mode in (("full", "full"), ("subgraph", "subgraph")):
        eng = ServeEngine(plan, fin=feats.shape[1], widths=widths,
                          comm_schedule="a2a", max_batch=max_batch,
                          latency_budget_ms=latency_budget_ms, seed=0,
                          mode=mode)
        eng.set_features(feats)
        eng.warmup(qids)     # every bucket, outside the measured window
        # the sub-graph arm's shape keys depend on the TRAFFIC's receptive
        # sets, not just the query-count buckets — one unmeasured pass over
        # the same open-loop trace warms them so the measured window's
        # latency describes serving, not compilation (the PR-8 warmup
        # lesson, extended to the receptive-size ladder)
        run_loadgen(eng, qids, offered_qps=offered_qps, concurrent=True)
        eng.batcher.deadline_flushes = 0
        eng.batcher.full_flushes = 0
        with scoped_span(f"bench:serve_subgraph:{arm}",
                         phase="serve_subgraph_child",
                         detail=f"n={n} graph={graph}"):
            res = run_loadgen(eng, qids, offered_qps=offered_qps,
                              concurrent=True)
        g = eng.gauges()
        gauges[arm] = g
        batches = max(res.batches, 1)
        nq = max(res.queries, 1)
        if mode == "full":
            rows_q = g["full_rows_per_forward"] * batches / nq
            flops_q = g["full_forward_flops"] * batches / nq
        else:
            rows_q = g["touched_rows_per_query"]
            flops_q = g["subgraph_flops_per_query"]
        out["arms"][arm] = {
            **res.summary(),
            "deadline_flushes": eng.batcher.deadline_flushes,
            "full_flushes": eng.batcher.full_flushes,
            "compiles": g["compiles"],
            "rows_per_query": round(float(rows_q), 3),
            "flops_per_query": round(float(flops_q), 3),
            "wire_rows_per_query": g["wire_rows_per_query"],
        }
    out["arms"]["subgraph"]["touched_rows_per_query"] = \
        gauges["subgraph"]["touched_rows_per_query"]
    out["arms"]["subgraph"]["recipe_edges_total"] = \
        gauges["subgraph"]["recipe_edges_total"]
    # DETERMINISTIC analytic gauges (the zero-band trend series + the
    # asserted cut): the measured arms' per-query figures depend on the
    # open loop's REAL-CLOCK batch composition (deadline flushes vary with
    # host load), so the acceptance figures are recomputed over a FIXED
    # chunking of the same query trace — plan/seed-derived only, byte-
    # reproducible across rounds at equal config
    out["analytic"] = _subgraph_deterministic_gauges(
        plan, feats, qids, max_batch, widths,
        offered_qps=offered_qps, latency_budget_ms=latency_budget_ms)
    rows_cut = (out["analytic"]["full_rows_per_query"]
                / max(out["analytic"]["subgraph_rows_per_query"], 1e-9))
    flops_cut = (out["analytic"]["full_flops_per_query"]
                 / max(out["analytic"]["subgraph_flops_per_query"], 1e-9))
    out["rows_per_query_cut"] = round(float(rows_cut), 3)
    out["flops_per_query_cut"] = round(float(flops_cut), 3)
    if k > 1 and not (rows_cut >= 10.0 and flops_cut >= 10.0):
        # the acceptance invariant: sub-graph serving must be
        # query-proportional enough to cut BOTH analytic per-query bills
        # >=10x at this query rate
        raise RuntimeError(
            f"serve subgraph A/B (hp): per-query cut below 10x "
            f"(rows {rows_cut:.2f}x, flops {flops_cut:.2f}x)")
    return out


def _subgraph_deterministic_gauges(plan, feats, qids, max_batch: int,
                                   widths, offered_qps: float = 50.0,
                                   latency_budget_ms: float = 100.0) -> dict:
    """Per-query analytic figures of the full-vs-subgraph A/B over a FIXED
    chunking of ``qids`` — no clock anywhere, so these are zero-band
    bench-trend counters (``scripts/bench_trend.py``); the measured arms
    keep their real batch compositions for the honest latency/QPS report.

    The chunk size is the open loop's EXPECTED deadline-flush batch,
    derived from config alone: ``offered_qps × latency_budget`` queries
    arrive per budget window (capped at ``max_batch``).  Chunking at
    ``max_batch`` instead would under-state the full forward's per-query
    bill — small batches are exactly what makes graph-proportional
    serving expensive, the regime the ≥10× claim names."""
    import numpy as np

    from sgcn_tpu.obs.attribution import forward_flops, subgraph_batch_flops
    from sgcn_tpu.serve import SubgraphIndex, VertexRouter
    from sgcn_tpu.serve.batcher import pad_pow2

    chunk_size = min(int(max_batch), max(1, int(round(
        offered_qps * latency_budget_ms / 1e3))))
    index = SubgraphIndex(plan, "gcn")
    router = VertexRouter(plan)
    qids = np.asarray(qids, dtype=np.int64)
    nq = max(len(qids), 1)
    nlayers = len(widths)
    touched = edges = wire = 0
    nbatches = 0
    for i in range(0, len(qids), chunk_size):
        chunk = qids[i: i + chunk_size]
        by = router.route(chunk)
        sets = [index.receptive(q, nlayers) for q in by.values()]
        touched += sum(len(u) for u in sets)
        edges += sum(index.edges_in(u) for u in sets)
        wire += pad_pow2(len(chunk), 1)       # the logit psum's padded rows
        nbatches += 1
    fin = feats.shape[1]
    return {
        "chunking": f"fixed {chunk_size} = min(max_batch, "
                    "offered_qps x latency_budget)",
        "full_rows_per_query": round(plan.k * plan.b * nbatches / nq, 3),
        "full_flops_per_query": round(
            forward_flops(plan, fin, widths) * nbatches / nq, 3),
        "subgraph_rows_per_query": round(touched / nq, 3),
        "subgraph_flops_per_query": round(
            subgraph_batch_flops(touched, edges, fin, widths) / nq, 3),
        "wire_rows_per_query": round(wire / nq, 3),
    }


def shard_epoch_model_block() -> dict:
    """Surface the measured 8-chip products epoch model:
    chip-0's shard of the k=8 hp-partitioned products-shape graph measured
    on the real chip (``scripts/shard_epoch_model.py``), composed with the
    plan's exact exchange bytes over the ring-ICI model.  Regenerated
    offline (~25 min TPU per graph family), not inside the bench."""
    base = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "bench_artifacts")
    block = {}
    for fam, fname in (("ba", "shard_epoch_model.json"),
                       ("dcsbm", "shard_epoch_model_dcsbm.json")):
        path = os.path.join(base, fname)
        if not os.path.exists(path):
            continue
        try:
            with open(path) as fh:
                rec = json.load(fh)
            fam_block = {"k": rec["config"]["k"], "n": rec["config"]["n"],
                         "source": f"bench_artifacts/{fname}"}
            for model in ("gcn", "gat"):
                if model in rec and "error" not in rec[model]:
                    fam_block[model] = {
                        "per_chip_compute_s":
                            round(rec[model]["per_chip_compute_s"], 4),
                        "comm_s_model":
                            round(rec["comm"][model]["comm_s_per_epoch"], 4)
                            if isinstance(rec.get("comm"), dict)
                            and model in rec.get("comm", {}) else None,
                        "epoch_s_8chip_model":
                            round(rec[model]["epoch_s_8chip_model"], 4),
                        "epoch_s_8chip_model_overlapped": round(
                            rec[model]["epoch_s_8chip_model_overlapped"], 4),
                    }
            if len(fam_block) > 3:
                block[fam] = fam_block
        except Exception as e:                  # noqa: BLE001 — diagnostic path
            print(f"# shard epoch model artifact unreadable: {e!r}",
                  file=sys.stderr)
    return {"epoch_s_8chip_model": block} if block else {}


def memory_footprint_block(n: int, avg_deg: int, f: int, widths,
                           graph: str = "ba", k: int = 8) -> dict:
    """Analytic per-chip HBM footprint gauges (the ``memory_footprint_8dev``
    block, ISSUE 18): the plan-derived residency model of
    ``sgcn_tpu.obs.memory`` evaluated for a representative mode set on the
    8-chip diagnostic shape.  No clock, no compile, no allocator anywhere —
    every byte count is a pure function of (CommPlan, model config), so
    ``scripts/bench_trend.py`` registers each (mode, array family) figure
    as a ZERO-band counter series scoped on (n, nnz, k).  ``analytic:
    true`` is the provenance flag the memory-provenance rule of
    ``scripts/validate_bench.py`` requires on residency-byte claims."""
    block: dict = {"memory_footprint_8dev": None}
    try:
        ahat = synth_graph(n, avg_deg, seed=0, kind=graph)
        from sgcn_tpu.obs.memory import memory_model
        from sgcn_tpu.parallel import build_comm_plan
        from sgcn_tpu.partition import balanced_random_partition

        pv = balanced_random_partition(ahat.shape[0], k, seed=1)
        plan = build_comm_plan(ahat, pv, k)
        modes = {
            "train_gcn_a2a": dict(workload="train", model="gcn",
                                  comm_schedule="a2a"),
            "train_gcn_ragged": dict(workload="train", model="gcn",
                                     comm_schedule="ragged"),
            "train_gcn_ragged_stale": dict(workload="train", model="gcn",
                                           comm_schedule="ragged",
                                           halo_staleness=1),
            "train_gat_a2a": dict(workload="train", model="gat",
                                  comm_schedule="a2a"),
            "serve_gcn_ragged": dict(workload="serve", model="gcn",
                                     comm_schedule="ragged"),
        }
        out: dict = {"n": int(ahat.shape[0]), "nnz": int(ahat.nnz),
                     "k": int(k), "graph": graph, "fin": int(f),
                     "nlayers": len(widths), "analytic": True, "modes": {}}
        for mid, kw in modes.items():
            m = memory_model(plan, f, list(widths), **kw)
            out["modes"][mid] = {
                "analytic": True,
                "model_bytes": int(m.total_bytes),
                **{f"{name}_bytes": int(v)
                   for name, v in sorted(m.families.items()) if v},
            }
        block["memory_footprint_8dev"] = out
        return block
    except Exception as e:                      # noqa: BLE001 — diagnostic path
        print(f"# memory footprint block failed: {e!r}", file=sys.stderr)
        block["memory_footprint_degraded"] = repr(e)[:200]
        return block


def products_partition_block() -> dict:
    """Products-scale partitioner evidence: the native
    hypergraph/graph partitioners run OFFLINE on the exact products-shape
    bench graph (2.45M vertices, 122M nnz, power-law) — a ~20-minute
    single-core job regenerated by ``scripts/products_partition.py``, not
    re-run inside the bench.  Surfaces the recorded km1 / wall-clock /
    balance so every BENCH_r*.json carries the products-scale partitioner
    numbers with provenance."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "bench_artifacts", "products_partition.json")
    if not os.path.exists(path):
        return {}
    try:
        with open(path) as fh:
            rec = json.load(fh)
        block = {
            "n": rec["graph"]["n"],
            "nnz": rec["graph"]["nnz"],
            "k": rec["k"],
            "km1_8dev": rec["hp"]["km1"],
            "km1_random": rec["rp"]["km1"],
            "hp_time_s": rec["hp"]["time_s"],
            "hp_nnz_balance": rec["hp"]["nnz_max_over_mean"],
            "gp_km1": rec["gp"]["km1"],
            "gp_time_s": rec["gp"]["time_s"],
            "source": "bench_artifacts/products_partition.json "
                      "(offline single-core run of scripts/"
                      "products_partition.py on the bench graph)",
        }
        if "plan_send_rows_per_pass" in rec["hp"]:
            # the REAL 8-chip comm plan built under the saved partvec
            # (scripts/products_plan_volume.py); equals km1 exactly — the
            # plan-volume invariant verified at products scale
            block["plan_send_rows_per_pass"] = \
                rec["hp"]["plan_send_rows_per_pass"]
            block["plan_messages_per_pass"] = \
                rec["hp"]["plan_messages_per_pass"]
            block["plan_b_per_chip"] = rec["hp"]["plan_b"]
        return {"products_partition_8dev": block}
    except Exception as e:                      # noqa: BLE001 — diagnostic path
        print(f"# products partition artifact unreadable: {e!r}",
              file=sys.stderr)
        return {}


def _emit_result(result: dict, args) -> None:
    """Print the one-line JSON and, under ``--metrics-out``, also persist it
    as a run directory (manifest + summary event) through the telemetry
    subsystem — the same loadable shape as a trainer run, so bench results
    and training runs share one loader (``sgcn_tpu.obs.load_run``)."""
    print(json.dumps(result))
    out = getattr(args, "metrics_out", None)
    if not out:
        return
    try:
        from sgcn_tpu.obs import RunRecorder

        with RunRecorder(out, config={k: v for k, v in vars(args).items()},
                         run_kind="bench") as rec:
            rec.record_summary(result)
    except Exception as e:              # noqa: BLE001 — observability only
        print(f"# --metrics-out write failed: {e!r}", file=sys.stderr)


def main() -> None:
    from sgcn_tpu.utils.backend import place_compile_cache
    place_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("-n", type=int, default=169_343)      # ogbn-arxiv scale
    p.add_argument("--avg-deg", type=int, default=14)
    p.add_argument("-f", type=int, default=128)
    p.add_argument("--hidden", type=int, default=128)
    p.add_argument("--classes", type=int, default=40)
    p.add_argument("-l", "--layers", type=int, default=3)
    p.add_argument("--model", default="gcn", choices=["gcn", "gat"],
                   help="gat = attention-weighted aggregation (PGAT role); "
                        "torch/dense yardsticks are GCN-shaped, so they are "
                        "skipped for gat")
    p.add_argument("-e", "--epochs", type=int, default=5)
    p.add_argument("--batch-size", type=int, default=None,
                   help="bench the mini-batch trainer (fused epoch sweep) "
                        "instead of the full-batch flagship")
    p.add_argument("--dtype", default=None, choices=["bfloat16"],
                   help="mixed-precision compute (f32 master params)")
    p.add_argument("--remat", action="store_true",
                   help="rematerialize layer activations in the backward "
                        "(HBM-for-FLOPs trade for huge vertex counts)")
    p.add_argument("--halo-staleness", type=int, default=0, choices=[0, 1],
                   help="1 = pipelined one-step-stale halo exchange (the "
                        "a2a leaves the critical path; GCN symmetric only)")
    p.add_argument("--halo-delta", action="store_true",
                   help="halo-delta cache: boundary rows ship as bf16 "
                        "deltas accumulated into the carried halo "
                        "(requires --halo-staleness 1)")
    p.add_argument("--sync-every", type=int, default=0,
                   help="stale mode: run a full-sync (exact) step every N "
                        "steps to bound drift (0 = only the first step)")
    p.add_argument("--skip-stale-ab", action="store_true",
                   help="skip the exact-vs-staleness-1 A/B on the virtual "
                        "8-device mesh")
    p.add_argument("--stale-ab-n", type=int, default=40_000,
                   help="graph size for the stale A/B children (two extra "
                        "CPU-mesh runs; smaller than --vdev-n by default)")
    p.add_argument("--comm-schedule", default=None,
                   choices=["a2a", "ragged", "auto"],
                   help="halo transport for the flagship run "
                        "(docs/comm_schedule.md): dense all_to_all, "
                        "per-round-sized ppermute ring, or plan-driven "
                        "auto-select; default $SGCN_COMM_SCHEDULE else a2a")
    p.add_argument("--skip-ragged-ab", action="store_true",
                   help="skip the a2a-vs-ragged schedule A/B on the "
                        "virtual 8-device mesh")
    p.add_argument("--ragged-ab-n", type=int, default=30_000,
                   help="graph size for the ragged A/B child (one extra "
                        "CPU-mesh run covering a balanced-random and a "
                        "skewed hp partition)")
    p.add_argument("--skip-gat-ragged-ab", action="store_true",
                   help="skip the GAT a2a-vs-ragged schedule A/B on the "
                        "virtual 8-device mesh")
    p.add_argument("--gat-ragged-ab-n", type=int, default=15_000,
                   help="graph size for the GAT ragged A/B child (one "
                        "extra CPU-mesh run; smaller than --ragged-ab-n — "
                        "the attention tables make the arms heavier)")
    p.add_argument("--skip-replica-ab", action="store_true",
                   help="skip the hot-halo replication A/B child "
                        "(replica_ab_8dev)")
    p.add_argument("--replica-ab-n", type=int, default=30_000,
                   help="graph size for the replica A/B child (one extra "
                        "8-vdev process, four arms over two partitions)")
    p.add_argument("--skip-controller-ab", action="store_true",
                   help="skip the adaptive-controller five-arm A/B "
                        "(controller_ab_8dev: controller vs four static "
                        "comm settings on the skewed-hp partition)")
    p.add_argument("--controller-ab-n", type=int, default=20_000,
                   help="graph size for the controller A/B child (five "
                        "arms in one extra CPU-mesh run)")
    p.add_argument("--skip-serve-qps", action="store_true",
                   help="skip the sustained-QPS serving bench "
                        "(serve_qps_8dev: open-loop traffic + a2a-vs-ragged "
                        "serving A/B) on the virtual 8-device mesh")
    p.add_argument("--serve-qps-n", type=int, default=20_000,
                   help="graph size for the serve QPS child (forward-only, "
                        "lighter than the training A/Bs)")
    p.add_argument("--skip-serve-subgraph", action="store_true",
                   help="skip the full-vs-subgraph serving A/B "
                        "(serve_subgraph_ab_8dev: shared open-loop traffic, "
                        ">=10x analytic per-query FLOP/touched-row cut)")
    p.add_argument("--skip-memory-footprint", action="store_true",
                   help="skip the analytic per-chip HBM footprint gauges "
                        "(memory_footprint_8dev: plan-derived bytes per "
                        "mode x array family, zero-band trend counters)")
    p.add_argument("--serve-subgraph-n", type=int, default=20_000,
                   help="graph size for the serve subgraph A/B child")
    p.add_argument("--skip-pallas-ragged-ab", action="store_true",
                   help="skip the kernel × schedule A/B (ELL-ragged vs "
                        "Pallas-ragged vs Pallas-a2a, emulate-mode) on "
                        "the virtual 8-device mesh")
    p.add_argument("--pallas-ragged-ab-n", type=int, default=15_000,
                   help="graph size for the pallas ragged A/B child "
                        "(three arms in one extra CPU-mesh run)")
    p.add_argument("--skip-ragged-stale-ab", action="store_true",
                   help="skip the three-way composed-mode A/B (a2a+stale "
                        "vs ragged+exact vs ragged+stale) on the virtual "
                        "8-device mesh")
    p.add_argument("--ragged-stale-ab-n", type=int, default=20_000,
                   help="graph size for the composed-mode A/B child "
                        "(three arms in one extra CPU-mesh run)")
    p.add_argument("--step-dispatch", action="store_true",
                   help="time one step() dispatch per epoch instead of the "
                        "fused on-device epoch loop (the stale A/B timing "
                        "mode)")
    p.add_argument("--chips", type=int, default=1,
                   help="chips the flagship / mini-batch run uses (a "
                        "k-way hp partition for k > 1); stated here, never "
                        "inferred from how many devices the host shows")
    p.add_argument("--graph", default="er",
                   choices=["er", "ba", "dcsbm"],
                   help="synthetic graph family: er (no hubs) or ba "
                        "(power-law tail, the ogbn-like profile)")
    p.add_argument("--skip-torch", action="store_true")
    p.add_argument("--metrics-out", default=None, metavar="DIR",
                   help="also persist the result as a telemetry run "
                        "directory (manifest + summary event, "
                        "sgcn_tpu.obs; render with scripts/obs_report.py)")
    p.add_argument("--skip-vdev", action="store_true",
                   help="skip the virtual-8-device partitioned diagnostic run")
    p.add_argument("--vdev-n", type=int, default=120_000,
                   help="graph size for the virtual-8-device run (CPU-bound)")
    p.add_argument("--vdev-graph", default="ba",
                   choices=["er", "ba", "dcsbm"],
                   help="graph family for the virtual-8-device run "
                        "(default ba: the ogbn-like power-law profile)")
    p.add_argument("--vdev-child", action="store_true", help=argparse.SUPPRESS)
    p.add_argument("--stale-ab-child", action="store_true",
                   help=argparse.SUPPRESS)
    p.add_argument("--ragged-ab-child", action="store_true",
                   help=argparse.SUPPRESS)
    p.add_argument("--gat-ragged-ab-child", action="store_true",
                   help=argparse.SUPPRESS)
    p.add_argument("--ragged-stale-ab-child", action="store_true",
                   help=argparse.SUPPRESS)
    p.add_argument("--pallas-ragged-ab-child", action="store_true",
                   help=argparse.SUPPRESS)
    p.add_argument("--replica-ab-child", action="store_true",
                   help=argparse.SUPPRESS)
    p.add_argument("--controller-ab-child", action="store_true",
                   help=argparse.SUPPRESS)
    p.add_argument("--serve-qps-child", action="store_true",
                   help=argparse.SUPPRESS)
    p.add_argument("--serve-subgraph-ab-child", action="store_true",
                   help=argparse.SUPPRESS)
    args = p.parse_args()

    if args.metrics_out:
        # measured spans from THIS process and every A/B child land in the
        # run directory's event stream (obs.tracing.emit_span is env-gated,
        # exactly like heartbeats; children inherit the env)
        os.environ["SGCN_METRICS_OUT"] = os.path.abspath(args.metrics_out)

    # --comm-schedule ragged + --halo-staleness 1 is the supported COMPOSED
    # mode (pspmm_stale_ragged) — the flagship can bench it directly
    if (args.halo_delta or args.sync_every) and not args.halo_staleness:
        # match the trainer CLI: silently measuring exact mode while the
        # JSON reader believes it was the delta wire would be a lie
        raise SystemExit(
            "--halo-delta/--sync-every configure the stale pipelined "
            "exchange; add --halo-staleness 1")

    from sgcn_tpu.prep import normalize_adjacency
    a = synth_graph(args.n, args.avg_deg, kind=args.graph)
    ahat = normalize_adjacency(a)
    rng = np.random.default_rng(0)
    feats = rng.standard_normal((args.n, args.f)).astype(np.float32)
    labels = rng.integers(0, args.classes, size=args.n).astype(np.int32)
    widths = [args.hidden] * (args.layers - 1) + [args.classes]

    if args.stale_ab_child:
        print(json.dumps({
            "metric": "stale_ab",
            "value": None,      # the arm fields below are the payload
            **bench_stale_ab_child(ahat, feats, labels, widths, args.epochs,
                                   graph=args.graph),
        }))
        return

    if args.ragged_ab_child:
        print(json.dumps({
            "metric": "ragged_ab",
            "value": None,      # the per-partition blocks are the payload
            **bench_ragged_ab_child(ahat, feats, labels, widths, args.epochs,
                                    graph=args.graph),
        }))
        return

    if args.gat_ragged_ab_child:
        print(json.dumps({
            "metric": "gat_ragged_ab",
            "value": None,      # the per-partition blocks are the payload
            **bench_ragged_ab_child(ahat, feats, labels, widths, args.epochs,
                                    graph=args.graph, model="gat"),
        }))
        return

    if args.pallas_ragged_ab_child:
        print(json.dumps({
            "metric": "pallas_ragged_ab",
            "value": None,      # the three-arm block is the payload
            **bench_pallas_ragged_ab_child(ahat, feats, labels, widths,
                                           args.epochs, graph=args.graph),
        }))
        return

    if args.ragged_stale_ab_child:
        print(json.dumps({
            "metric": "ragged_stale_ab",
            "value": None,      # the three-arm block is the payload
            **bench_ragged_stale_ab_child(ahat, feats, labels, widths,
                                          args.epochs, graph=args.graph),
        }))
        return

    if args.replica_ab_child:
        print(json.dumps({
            "metric": "replica_ab",
            "value": None,      # the per-partition blocks are the payload
            **bench_replica_ab_child(ahat, feats, labels, widths,
                                     args.epochs, graph=args.graph),
        }))
        return

    if args.controller_ab_child:
        print(json.dumps({
            "metric": "controller_ab",
            "value": None,      # the five-arm block is the payload
            **bench_controller_ab_child(ahat, feats, labels, widths,
                                        args.epochs, graph=args.graph),
        }))
        return

    if args.serve_qps_child:
        print(json.dumps({
            "metric": "serve_qps_ab",
            "value": None,      # the per-transport arm blocks are the payload
            **bench_serve_qps_child(ahat, feats, labels, widths,
                                    graph=args.graph),
        }))
        return

    if args.serve_subgraph_ab_child:
        print(json.dumps({
            "metric": "serve_subgraph_ab",
            "value": None,      # the per-mode arm blocks are the payload
            **bench_serve_subgraph_child(ahat, feats, labels, widths,
                                         graph=args.graph),
        }))
        return

    if args.batch_size is not None:
        if args.model != "gcn":
            raise SystemExit(
                "--batch-size benches the GCN mini-batch trainer; "
                "--model gat is not wired through it")
        if args.remat:
            raise SystemExit("--remat is not wired through the mini-batch "
                             "trainer; drop it or bench full-batch")
        mb_s, mb_metrics = bench_minibatch(ahat, feats, labels, widths,
                                           args.batch_size, args.epochs,
                                           args.chips, dtype=args.dtype,
                                           comm_schedule=args.comm_schedule)
        if args.dtype:
            mb_metrics["compute_dtype"] = args.dtype
        _emit_result({
            "metric": "minibatch_gcn_epoch_time",
            "value": round(mb_s, 6),
            "unit": "s",
            "graph": args.graph,
            # provenance: this number came out of a live differential
            # measurement in THIS process — scripts/validate_bench.py
            # requires the flag on every epoch-time claim from round 6 on
            "measured": True,
            "measurement": dict(_diff_time_quality),
            **mb_metrics,
        }, args)
        return

    from sgcn_tpu.obs.tracing import scoped_span
    with scoped_span("bench:flagship", phase="flagship",
                     detail=f"{args.model} n={args.n} k={args.chips}"):
        epoch_s, part_metrics = bench_jax(
            ahat, feats, labels, widths, args.epochs, args.chips,
            model=args.model, dtype=args.dtype, remat=args.remat,
            halo_staleness=args.halo_staleness,
            halo_delta=args.halo_delta, sync_every=args.sync_every,
            step_dispatch=args.step_dispatch,
            comm_schedule=args.comm_schedule)
    flagship_quality = dict(_diff_time_quality)   # before later diff_time calls
    if args.model == "gat":
        args.skip_torch = True          # yardsticks below are GCN-shaped
        args.skip_vdev = True
    # two honest yardsticks: the reference-style torch
    # CPU stack (kept, as vs_torch_cpu) and the dense-matmul roofline epoch at
    # identical shapes (epoch_vs_dense >= 1; 1.0 = sparse path at MXU parity).
    # The dense epoch is single-device, so the ratio is only meaningful for
    # the single-chip run — on a multi-chip mesh it would conflate parallel
    # speedup with gather efficiency; emit null there.
    single = args.chips == 1 and args.model == "gcn"
    try:
        dense_s = bench_dense_equiv(args.n, args.f, widths, args.epochs) \
            if single else None
    except Exception as e:                      # noqa: BLE001 — yardstick only
        print(f"# dense yardstick failed: {e!r}", file=sys.stderr)
        dense_s = None
    if args.skip_torch:
        vs = None                               # never fabricate parity
    else:
        try:
            ref_s = bench_torch_reference(ahat, feats, labels, widths,
                                          max(2, args.epochs // 2))
            vs = round(ref_s / epoch_s, 3)
        except Exception as e:                  # noqa: BLE001 — yardstick only
            print(f"# torch yardstick failed: {e!r}", file=sys.stderr)
            vs = None
    vdev_metrics = {}
    if not (args.skip_vdev or args.vdev_child):
        vdev_metrics = bench_vdev_partitioned(
            args.vdev_n, args.avg_deg, args.f, widths, max(2, args.epochs // 2),
            graph=args.vdev_graph)
        if (args.model == "gcn" and args.halo_staleness == 0
                and not args.skip_stale_ab):
            vdev_metrics.update(bench_stale_ab(
                args.stale_ab_n, args.avg_deg, args.f, widths,
                max(2, args.epochs // 2), graph=args.vdev_graph))
        if (args.model == "gcn" and args.halo_staleness == 0
                and not args.skip_ragged_ab):
            vdev_metrics.update(bench_ragged_ab(
                args.ragged_ab_n, args.avg_deg, args.f, widths,
                max(2, args.epochs // 2), graph=args.vdev_graph))
        if (args.model == "gcn" and args.halo_staleness == 0
                and not args.skip_gat_ragged_ab):
            # the GAT schedule A/B rides the same diagnostic sweep (the
            # gat flagship path skips vdev entirely, so it runs here)
            vdev_metrics.update(bench_ragged_ab(
                args.gat_ragged_ab_n, args.avg_deg, args.f, widths,
                max(2, args.epochs // 2), graph=args.vdev_graph,
                model="gat"))
        if (args.model == "gcn" and args.halo_staleness == 0
                and not args.skip_ragged_stale_ab):
            # the composed-mode three-way A/B (docs/comm_schedule.md):
            # a2a+stale vs ragged+exact vs ragged+stale
            vdev_metrics.update(bench_ragged_stale_ab(
                args.ragged_stale_ab_n, args.avg_deg, args.f, widths,
                max(2, args.epochs // 2), graph=args.vdev_graph))
        if (args.model == "gcn" and args.halo_staleness == 0
                and not args.skip_pallas_ragged_ab):
            # kernel × schedule composition A/B (ISSUE 15): ELL-ragged vs
            # Pallas-ragged vs Pallas-a2a, emulate-mode deterministic
            # counters the claim
            vdev_metrics.update(bench_pallas_ragged_ab(
                args.pallas_ragged_ab_n, args.avg_deg, args.f, widths,
                max(2, args.epochs // 2), graph=args.vdev_graph))
        if (args.model == "gcn" and args.halo_staleness == 0
                and not args.skip_replica_ab):
            # the hot-halo replication A/B (docs/replication.md): B>0 vs
            # no-replica over balanced-random + cache-aware hp partitions
            vdev_metrics.update(bench_replica_ab(
                args.replica_ab_n, args.avg_deg, args.f, widths,
                max(2, args.epochs // 2), graph=args.vdev_graph))
        if (args.model == "gcn" and args.halo_staleness == 0
                and not args.skip_controller_ab):
            # the adaptive-controller five-arm A/B (docs/comm_schedule.md):
            # controller vs four static comm settings, exposed wire
            # rows/step the acceptance figure
            vdev_metrics.update(bench_controller_ab(
                args.controller_ab_n, args.avg_deg, args.f, widths,
                max(2, args.epochs // 2), graph=args.vdev_graph))
        if (args.model == "gcn" and args.halo_staleness == 0
                and not args.skip_serve_qps):
            # the serving roofline next to the training one (docs/serving.md)
            vdev_metrics.update(bench_serve_qps(
                args.serve_qps_n, args.avg_deg, args.f, widths,
                graph=args.vdev_graph))
        if (args.model == "gcn" and args.halo_staleness == 0
                and not args.skip_serve_subgraph):
            # full-vs-subgraph serving A/B (docs/serving.md phase 2)
            vdev_metrics.update(bench_serve_subgraph(
                args.serve_subgraph_n, args.avg_deg, args.f, widths,
                graph=args.vdev_graph))
    extra = {}
    if not args.vdev_child:
        extra.update(products_partition_block())
        extra.update(shard_epoch_model_block())
        if not args.skip_memory_footprint:
            # analytic footprint gauges: pure plan math (no child process,
            # no mesh) — runs for the gat flagship too
            extra.update(memory_footprint_block(
                args.vdev_n, args.avg_deg, args.f, widths,
                graph=args.vdev_graph))
    _emit_result({
        "metric": f"fullbatch_{args.model}_epoch_time",
        "value": round(epoch_s, 6),
        "unit": "s",
        "graph": args.graph,
        # provenance: a live differential measurement from THIS process —
        # scripts/validate_bench.py enforces it from round 6 on
        "measured": True,
        "vs_baseline": vs,
        "vs_torch_cpu": vs,
        # ADVICE r3: label the yardstick — vs_baseline is measured against
        # the reference's own compute stack (torch.sparse CPU) on THIS host;
        # the BASELINE.json north star (<=1.2x NCCL/V100 at 8 chips) needs
        # hardware this box does not have and is NOT what this ratio claims.
        "vs_baseline_is": "torch-CPU reference-stack proxy on this host, "
                          "not the V100/NCCL north star (BASELINE.json)",
        "dense_equiv_s": round(dense_s, 6)
            if dense_s and np.isfinite(dense_s) else None,
        "epoch_vs_dense": round(epoch_s / dense_s, 3)
            if dense_s and np.isfinite(dense_s) else None,
        "measurement": flagship_quality,
        **part_metrics,
        **vdev_metrics,
        **extra,
    }, args)


if __name__ == "__main__":
    main()
