"""Mini-batch distributed GCN trainer — per-batch sampled adjacency + plans.

Reference: ``GPU/PGCN-Mini-batch.py`` — pre-samples ``nbatches = 3·(n/batch+1)``
random vertex subsets before training (``:220-230``), builds a per-batch
sampled adjacency restricted to the batch (``sample_adjacency_matrix``
``:58-69``) and per-batch comm maps (``:228``), then loops batches through a
fixed layer stack; its partition vector comes from SHP as a pickle
(``:217-218``).  ``GPU/PGCN-Accuracy.py`` is the variant with real labels and
comm restricted to ``boundary ∩ batch`` (``:92-139``) — here that restriction
is structural: batch plans are built from the batch subgraph, so only
boundary-of-batch rows are exchanged, and training on a batch touches only
batch vertices.

TPU design: per-batch nnz/halo sizes vary, which under XLA would mean one
compilation per batch.  Every batch plan is therefore padded to the max
envelope across batches (``pad_comm_plan``) so ONE jitted shard_map train step
serves every batch — the XLA-native mirror of the reference's
pre-sample-everything strategy (SURVEY.md §7.3).
"""

from __future__ import annotations

import contextlib
from dataclasses import dataclass

import jax
import numpy as np
import optax
import scipy.sparse as sp

from ..parallel.mesh import make_mesh_1d, shard_stacked
from ..parallel.plan import build_comm_plan, pad_comm_plan, shared_ell_buckets
from ..utils.stats import CommStats
from .fullbatch import (MODELS, FullBatchTrainer, TrainData, _plan_arrays,
                        _unblock, make_train_data)


def sample_batches(n: int, batch_size: int, nbatches: int | None = None,
                   seed: int = 0) -> list[np.ndarray]:
    """Pre-sample vertex subsets; default count = 3·(n//batch + 1)
    (``GPU/PGCN-Mini-batch.py:220-230``)."""
    rng = np.random.default_rng(seed)
    if nbatches is None:
        nbatches = 3 * (n // batch_size + 1)
    batch_size = min(batch_size, n)
    return [np.sort(rng.choice(n, size=batch_size, replace=False))
            for _ in range(nbatches)]


def sample_adjacency(a: sp.spmatrix, batch: np.ndarray) -> sp.csr_matrix:
    """Batch-restricted adjacency ``A[batch][:, batch]`` reindexed to
    ``0..|batch|-1`` (``GPU/PGCN-Mini-batch.py:58-69``)."""
    a = sp.csr_matrix(a)
    return a[batch][:, batch]


@dataclass
class Batch:
    vertices: np.ndarray
    plan: object          # padded CommPlan over the batch subgraph
    pa: dict              # sharded plan arrays
    data: TrainData       # sharded per-chip batch blocks
    stats: CommStats      # per-batch-plan counters (own send/recv volumes)


class MiniBatchTrainer:
    """PGCN-Mini-batch-equivalent trainer on the 1D vertex mesh."""

    def __init__(
        self,
        a: sp.spmatrix,
        partvec: np.ndarray,
        k: int,
        fin: int,
        widths: list[int],
        batch_size: int,
        nbatches: int | None = None,
        mesh=None,
        lr: float = 0.01,
        activation: str = "relu",
        model: str = "gcn",
        loss: str = "xent",
        optimizer: optax.GradientTransformation | None = None,
        seed: int = 0,
        pad_rows_to: int = 8,
        compute_dtype: str | None = None,
        comm_schedule: str | None = None,
        replica_budget: int = 0,
        memory_budget: int | None = None,
    ):
        if MODELS[model].full_batch_only:
            raise ValueError(
                f"model={model!r} is a full-batch model here: its "
                "model_args and per-row backward state have no mini-batch "
                "wiring — run the full-batch trainer")
        if replica_budget:
            # the replica carries cache per-layer activations of ONE plan's
            # boundary rows across steps; every mini-batch step runs a
            # DIFFERENT batch plan (different vertex set, different halo
            # structure), so a carried replica has no stable identity to
            # refresh against — same exclusion family as staleness/delta
            # (analysis/modes.py records the decision; docs/replication.md)
            raise ValueError(
                "replica_budget is a full-batch training lever: the "
                "mini-batch trainer re-plans per batch, so replica carries "
                "have no stable identity across batch plans — run the "
                "full-batch trainer for hot-halo replication")
        self.a = sp.csr_matrix(a)
        n = self.a.shape[0]
        self.partvec = np.asarray(partvec, dtype=np.int64)
        self.k = k
        self.mesh = mesh if mesh is not None else make_mesh_1d(k)
        self.batches_idx = sample_batches(n, batch_size, nbatches, seed=seed)

        # build per-batch plans, then pad all to the shared envelope
        raw = []
        for bv in self.batches_idx:
            sub = sample_adjacency(self.a, bv)
            pv = self.partvec[bv]
            # remap part ids unchanged: chips keep their global rank even if a
            # batch misses some part entirely
            raw.append(build_comm_plan(sub, pv, k, pad_rows_to=pad_rows_to))
        env = tuple(max(getattr(p, f) for p in raw)
                    for f in ("b", "s", "r", "e", "el", "eh", "tl"))
        shared = shared_ell_buckets(raw, env[0])
        self.plans = [pad_comm_plan(p, *env, ell_buckets=shared) for p in raw]
        if MODELS[model].combined_edges:
            # the combined-edge (GAT) layout is lazy; build it ONCE per plan
            # with a shared bucket structure AND a shared tail length (the
            # spill is derivable from degree profiles without materializing)
            cshared = shared_ell_buckets(self.plans, env[0], combined=True)
            caps = np.concatenate(
                [np.full(nb, wb, np.int64) for nb, wb in cshared])
            ctl_shared = 1
            for p in self.plans:
                for chip in range(k):
                    deg = np.bincount(p.edge_dst[chip][: int(p.nnz[chip])],
                                      minlength=p.b)
                    ctl_shared = max(ctl_shared, int(
                        np.maximum(deg - caps[: p.b], 0).sum()))
            for p in self.plans:
                p.ensure_cell(buckets=cshared, ctl=ctl_shared)
        # one compiled step serves every batch, so the symmetric fast path is
        # only safe if every batch plan is symmetric (sampled subgraphs of a
        # symmetric graph are, but keep the guard exact)
        if not all(p.symmetric for p in self.plans):
            for p in self.plans:
                p.symmetric = False

        # one compiled step serves every batch, so the ragged per-round
        # envelope must be SHARED across batch plans, exactly like the
        # B/S/R/E envelope above: resolve the schedule over the whole batch
        # set (the shared rule in parallel/plan.py), then pad every plan's
        # round sizes to the elementwise max
        from ..parallel.plan import resolve_comm_schedule
        self.comm_decision: dict = {}   # selection inputs → run manifest
        comm_schedule = resolve_comm_schedule(
            comm_schedule, self.plans, model, fin=fin, widths=list(widths),
            compute_dtype=compute_dtype, decision=self.comm_decision,
            ragged=MODELS[model].ragged)
        if comm_schedule == "ragged":
            # EVERY plan needs the layout (the fused sweep stacks the ragged
            # arrays across batches), padded to the shared round envelope;
            # k=1 plans have zero rounds and stack trivially
            for p in self.plans:
                p.ensure_ragged()
            if k > 1:
                shared_s = tuple(int(x) for x in np.max(
                    [p.rr_sizes for p in self.plans], axis=0))
                shared_e = tuple(int(x) for x in np.max(
                    [p.rr_edge_sizes for p in self.plans], axis=0))
                for p in self.plans:
                    p.ensure_ragged(rr_sizes=shared_s,
                                    rr_edge_sizes=shared_e)

        # one inner trainer = one compiled step for every batch.
        # allow_pallas=False: the VMEM kernel family's tile layout is
        # per-plan (per-class Emax_c statics, ptile_* arrays built by
        # ensure_pallas_tiles) — plans[0]'s compiled step cannot serve the
        # other batches' plans, whose tile arrays would never be built, so
        # the shared envelope stays on the slot-pass/ELL aggregators.
        # shared_envelope=True, for the same reason: the hub tail and the
        # halo-source edges stay COO lists (padded to tl / eh); their slot
        # form's virtual-row counts differ from plan to plan, and every
        # batch brings its own Â and h0, so nothing is hoisted out of the
        # step (models/setup.py::Hoist)
        self.inner = FullBatchTrainer(
            self.plans[0], fin, widths, mesh=self.mesh, lr=lr,
            activation=activation, model=model, loss=loss,
            optimizer=optimizer, seed=seed,
            compute_dtype=compute_dtype, comm_schedule=comm_schedule,
            allow_pallas=False, memory_budget=memory_budget,
            shared_envelope=True)
        # the inner trainer's plan IS the shared envelope every batch pads
        # to, so its analytic footprint (obs/memory.py) covers every batch's
        # step — the --memory-budget gate above already held it to account
        self.memory = self.inner.memory
        # checkpoints save through `inner`, whose plan is a padded per-BATCH
        # plan — its digest varies with batch_size/nbatches/pad envelope, so
        # it is not a stable run identity; suppress it (utils/checkpoint.py
        # honors the sentinel) rather than make every cross-batch-shape
        # resume a digest error.  Model config is still recorded + verified.
        self.inner.checkpoint_plan = None
        self.nlayers = len(widths)
        self._fullgraph_eval = None   # built lazily, cached across calls
        self.recorder = None          # run telemetry (sgcn_tpu.obs)
        self._gstep = 0               # completed batch steps (events are
        #                               1-based, like FullBatchTrainer's)
        self._comm_cum = None         # running cross-batch comm cumulative

    def attach_recorder(self, recorder) -> None:
        """Attach a ``sgcn_tpu.obs.RunRecorder``: every ``step(batch)``
        appends one JSONL event (loss, wall time, merged comm split across
        the per-batch counters).  The fused epoch sweep stays available but
        emits no per-step events — use the stepwise ``fit`` under
        telemetry."""
        self.recorder = recorder
        # span events ride the inner trainer's SpanTimer (one timer, one
        # span stack for both trainers — docs/observability.md)
        self.inner.spans.recorder = recorder
        if getattr(self, "comm_decision", None):
            recorder.set_comm_schedule(self.comm_decision)
        if getattr(self, "memory", None) is not None:
            recorder.set_memory(self.memory.block())

    def _comm_snapshot(self, stats: CommStats) -> dict:
        """O(k) running equivalent of ``CommStats.merged_report`` over every
        batch counter that has passed through ``step()``: one step advances
        exactly one batch's counters by a fixed per-step delta, so the
        cross-batch cumulative is maintained incrementally instead of
        re-merging all B counters each step (O(B²) per epoch).  Covers
        RECORDED steps only — attach the recorder before training (the CLI
        does) or the snapshot starts from the attach point."""
        d = 2 * self.nlayers
        per = (stats.send_volume_per_exchange, stats.send_msgs_per_exchange,
               stats.recv_volume_per_exchange, stats.recv_msgs_per_exchange)
        if self._comm_cum is None:
            self._comm_cum = {
                "arrs": [np.zeros_like(p, dtype=np.int64) for p in per],
                "exchanges": 0, "send_volume": 0, "wire_rows": 0,
            }
        c = self._comm_cum
        for acc, p in zip(c["arrs"], per):
            acc += p.astype(np.int64) * d
        c["exchanges"] += d
        c["send_volume"] += int(per[0].sum()) * d
        c["wire_rows"] += stats.wire_rows_per_exchange * d
        rep = CommStats.report_from_cumulative(*c["arrs"])
        rep.update(                 # mini-batch steps are never pipelined
            exchanges=c["exchanges"],
            exposed_exchanges=c["exchanges"], hidden_exchanges=0,
            exposed_send_volume=c["send_volume"], hidden_send_volume=0,
            # the same wire gauges the full-batch snapshot carries
            # (docs/observability.md): the per-exchange figures are the
            # CURRENT batch's (wire is uniform — all batch plans share one
            # padded envelope; true rows vary per batch), the cumulative
            # ones cover every recorded step
            comm_schedule=stats.schedule,
            true_rows_per_exchange=int(per[0].sum()),
            wire_rows_per_exchange=stats.wire_rows_per_exchange,
            wire_rows_total=c["wire_rows"],
            padding_efficiency=(c["send_volume"] / c["wire_rows"]
                                if c["wire_rows"] else 1.0),
        )
        return rep

    # ------------------------------------------------------------------- data
    def make_batches(self, features: np.ndarray, labels: np.ndarray,
                     train_mask: np.ndarray | None = None) -> list[Batch]:
        """Scatter global features/labels into per-batch per-chip blocks."""
        out = []
        for bv, plan in zip(self.batches_idx, self.plans):
            tm = train_mask[bv] if train_mask is not None else None
            data = make_train_data(plan, features[bv], labels[bv], tm)
            out.append(Batch(
                vertices=bv,
                plan=plan,
                pa=shard_stacked(self.mesh,
                                 _plan_arrays(plan, self.inner.plan_fields)),
                data=TrainData(**shard_stacked(self.mesh, vars(data))),
                stats=CommStats.from_plan(
                    plan, schedule=self.inner.comm_schedule,
                    # same per-layer wire lane widths as the inner trainer's
                    # counters, so per-batch byte gauges stay comparable
                    lane_widths=self.inner.stats.lane_widths,
                    wire_itemsize=self.inner.stats.wire_itemsize,
                    wire_itemsize_bwd=self.inner.stats.wire_itemsize_bwd),
            ))
        return out

    # ------------------------------------------------------------------- api
    def lower_step(self):
        """AOT-lower the ONE shared-envelope train step every batch runs
        (no compile, no execution) — the mini-batch entry point of the
        static-analysis HLO audit (``sgcn_tpu/analysis``): the program
        ``step(batch)`` dispatches is the inner trainer's step over the
        padded batch envelope (shared B/S/R/E + ragged round sizes), so its
        collective census / wire dtype / donation contracts are audited on
        exactly that envelope."""
        return self.inner.lower_step()

    def step(self, batch: Batch) -> float:
        tr = self.inner
        # under a recorder, the step span brackets dispatch AND the loss
        # readback, so its duration is the measured step time the event
        # carries; without one, nullcontext keeps the SAME body (one copy
        # of the step bookkeeping for both paths)
        cm = (tr.spans.span("step", step=self._gstep + 1)
              if self.recorder is not None else contextlib.nullcontext())
        with cm as sp:
            tr.params, tr.opt_state, loss, tr.last_err = tr._step(
                tr.params, tr.opt_state, batch.pa, batch.data.h0,
                batch.data.labels, batch.data.train_valid)
            loss = float(loss)
        # per-batch counters advance exactly like the full-batch trainer's —
        # the reference's mini-batch code shares one counter dict across
        # batches (GPU/PGCN-Mini-batch.py), so end-of-run stats carry the
        # same 8-number vocabulary
        batch.stats.count_step(nlayers=self.nlayers)
        self._gstep += 1
        if self.recorder is not None:
            self.recorder.record_step(
                step=self._gstep, loss=loss, wall_s=sp.dur_s,
                comm=self._comm_snapshot(batch.stats))
        return loss

    def fit(self, features: np.ndarray, labels: np.ndarray,
            train_mask: np.ndarray | None = None, epochs: int = 1,
            warmup: int = 1, verbose: bool = True) -> dict:
        """Epoch = one pass over all pre-sampled batches (reference epoch
        structure, ``GPU/PGCN-Mini-batch.py:231-306``).  Timing routes
        through the inner trainer's ``PhaseTimer`` (one phase-accounting
        code path for both trainers)."""
        timer = self.inner.timer
        spans = self.inner.spans
        batches = self.make_batches(features, labels, train_mask)
        with spans.span("warmup", sync=lambda: self.inner.params):
            for _ in range(warmup):
                self.step(batches[0])
        history = []
        # inclusive: under a recorder each batch step opens a nested span
        # that claims the self time (utils/timers.py nesting contract)
        t_prior = timer.inclusive_total("train_step")
        for ep in range(epochs):
            ep_loss = 0.0
            with spans.span("train_step", sync=lambda: self.inner.params):
                for b in batches:
                    ep_loss += self.step(b)
            ep_loss /= len(batches)
            history.append(ep_loss)
            if verbose:
                print(f"epoch {ep}: batch-avg loss {ep_loss:.6f}", flush=True)
        elapsed = timer.inclusive_total("train_step") - t_prior
        report = CommStats.merged_report([b.stats for b in batches])
        report.update(
            epochs=epochs,
            nbatches=len(batches),
            elapsed_s=elapsed,
            epoch_s=elapsed / max(epochs, 1),
            loss_history=history,
            phases=timer.report(),
            # legacy alias of total_send_volume (rows shipped across all
            # exchanges) — derived, not independently counted
            total_exchanged_rows=report["total_send_volume"],
        )
        if self.recorder is not None:
            self.recorder.record_summary(
                {k: v for k, v in report.items() if k != "loss_history"})
        return report

    # ------------------------------------------------------- fused epoch path
    def _stack_inputs(self, features, labels, train_mask=None):
        """Stack every batch's plan arrays and data along a new axis 1:
        (k, nb, ...) — shard axis stays leading, so one shard_map program
        can ``fori_loop`` over batches on-device."""
        per_plan = [_plan_arrays(p, self.inner.plan_fields)
                    for p in self.plans]
        pa = {f: np.stack([d[f] for d in per_plan], axis=1)
              for f in self.inner.plan_fields}
        datas = []
        for bv, p in zip(self.batches_idx, self.plans):
            tm = train_mask[bv] if train_mask is not None else None
            datas.append(make_train_data(p, features[bv], labels[bv], tm))
        # eval_valid is never consumed by the fused train program — alias it
        # to train_valid instead of stacking/shipping a second mask array
        sh = shard_stacked(self.mesh, dict(
            h0=np.stack([d.h0 for d in datas], axis=1),
            labels=np.stack([d.labels for d in datas], axis=1),
            train_valid=np.stack([d.train_valid for d in datas], axis=1)))
        return (shard_stacked(self.mesh, pa),
                TrainData(**sh, eval_valid=sh["train_valid"]))

    def _build_fused(self, epochs: int):
        """Compile ``epochs`` full passes over ALL batches as ONE program.

        The reference dispatches one step per batch from Python
        (``GPU/PGCN-Mini-batch.py:231-306``); under a high-latency host link
        that dominates wall-clock, so the whole epoch loop runs on-device —
        same semantics, one dispatch (cf. ``FullBatchTrainer.run_epochs``).
        """
        import jax.numpy as jnp
        from jax import lax
        from jax.sharding import PartitionSpec as P

        tr = self.inner
        nb = len(self.plans)

        def per_chip(params, opt_state, pa_s, h0, lab, val):
            pa_s, h0, lab, val = _unblock((pa_s, h0, lab, val))
            z_ep = jnp.zeros((epochs,), jnp.float32)
            z_nb = jnp.zeros((nb,), jnp.float32)

            def batch_body(i, carry):
                params, opt_state, losses, _ = carry
                pa_i = jax.tree.map(lambda x: x[i], pa_s)
                params, opt_state, loss, err = tr._one_step(
                    params, opt_state, pa_i, h0[i], lab[i], val[i])
                return params, opt_state, losses.at[i].add(loss), err

            def epoch_body(e, carry):
                params, opt_state, ep_losses, err = carry
                params, opt_state, s, err = lax.fori_loop(
                    0, nb, batch_body, (params, opt_state, z_nb, err))
                return params, opt_state, ep_losses.at[e].set(s.mean()), err

            return lax.fori_loop(0, epochs, epoch_body,
                                 (params, opt_state, z_ep, z_ep.sum()))

        smapped = jax.shard_map(
            per_chip, mesh=self.mesh,
            in_specs=(P(), P(), P("v"), P("v"), P("v"), P("v")),
            out_specs=(P(), P(), P(), P()))
        return jax.jit(smapped, donate_argnums=(0, 1))

    def run_epochs_fused(self, features, labels, train_mask=None,
                         epochs: int = 1, sync: bool = True):
        """Run ``epochs`` full batch sweeps in one device program; returns
        per-epoch batch-averaged losses.  Identical trajectory to
        ``epochs × len(batches)`` sequential ``step()`` calls."""
        if not hasattr(self, "_fused"):
            self._fused = {}
            self._fused_inputs = None
            self._fused_key = None
        # cheap content probe so a call with DIFFERENT data rebuilds the
        # stacked device inputs instead of silently training on stale ones
        key = (np.asarray(features).shape, np.asarray(labels).shape,
               None if train_mask is None else np.asarray(train_mask).shape,
               float(np.asarray(features).ravel()[:16].sum()),
               int(np.asarray(labels).ravel()[:16].sum()),
               None if train_mask is None
               else float(np.asarray(train_mask).sum()))
        if self._fused_inputs is None or key != self._fused_key:
            self._fused_inputs = self._stack_inputs(features, labels,
                                                    train_mask)
            self._fused_key = key
        if epochs not in self._fused:
            self._fused[epochs] = self._build_fused(epochs)
        pa_s, data = self._fused_inputs
        tr = self.inner
        tr.params, tr.opt_state, losses, tr.last_err = self._fused[epochs](
            tr.params, tr.opt_state, pa_s, data.h0, data.labels,
            data.train_valid)
        # same 8-number comm accounting as the stepwise path (one counter
        # set per batch plan, merged on report)
        if not hasattr(self, "_fused_stats"):
            self._fused_stats = [
                CommStats.from_plan(p, schedule=self.inner.comm_schedule)
                for p in self.plans]
        for _ in range(epochs):
            for st in self._fused_stats:
                st.count_step(nlayers=self.nlayers)
        return np.asarray(losses) if sync else losses

    def fused_stats_report(self) -> dict:
        return CommStats.merged_report(getattr(self, "_fused_stats", []))

    # full-graph evaluation path (accuracy-parity experiments evaluate on the
    # whole graph after mini-batch training — GPU/PGCN-Accuracy.py role)
    def evaluate_fullgraph(self, features: np.ndarray, labels: np.ndarray,
                           eval_mask: np.ndarray | None = None):
        if self._fullgraph_eval is None:
            plan = build_comm_plan(self.a, self.partvec, self.k)
            self._fullgraph_eval = (plan, FullBatchTrainer(
                plan, features.shape[1], self.inner.widths,
                mesh=self.mesh, activation=self.inner.activation,
                model=self.inner.model, loss=self.inner.loss_name,
                compute_dtype=self.inner.compute_dtype))
            # new data every call, fed to _eval directly: nothing to
            # hoist (switched off before any program is traced)
            self._fullgraph_eval[1].hoist = None
        plan, tr = self._fullgraph_eval
        tr.params = self.inner.params
        data = make_train_data(plan, features, labels,
                               np.ones(self.a.shape[0], np.float32),
                               eval_mask)
        data = TrainData(**shard_stacked(self.mesh, vars(data)))
        loss, acc, _ = tr._eval(tr.params, tr.pa, data.h0, data.labels,
                                data.eval_valid)
        return float(loss), float(acc)
