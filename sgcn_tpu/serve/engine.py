"""AOT-compiled partitioned inference engine (forward-only, no VJP).

The serving counterpart of ``train.fullbatch``: load a checkpoint and a
``CommPlan``, verify provenance (plan digest + model config — a wrong-plan
or wrong-config restore must fail at load, not as a deep tree-shape error
or a cleanly-restored wrong model), and AOT-compile
(``jax.jit(...).lower(...).compile()``, the trick ``FullBatchTrainer
.lower_step`` already uses) ONE forward program per padded batch-size
bucket.  No optimizer state, no gradient ring — the per-layer halo exchange
is the ENTIRE comm cost, so the training transports transfer directly: the
engine supports the same ``comm_schedule``/``halo_dtype`` levers, resolved
through the SAME ``resolve_forward_setup`` the trainer uses (that shared
resolver is what makes the served logits f32-bit-identical to the trainer's
``evaluate()`` — tier-1-pinned by ``tests/test_serve.py``).

Query path per micro-batch (host stages spanned via ``SpanTimer``, the
schema-v2 machinery):

  * ``serve:route``          — global vertex ids → (owner, local slot)
    through the ``VertexRouter``;
  * ``serve:batch``          — pad the batch up to its compiled bucket
    (owner −1 on padding: matches no chip, contributes zero);
  * ``serve:compile_lookup`` — fetch the bucket's AOT executable (a MISS
    compiles and bumps ``compile_count`` — steady-state traffic must never
    miss, the no-recompile contract);
  * ``serve:forward``        — run the program and block on the replicated
    ``(Q, nout)`` result.  The halo exchange executes INSIDE this one XLA
    program, so it cannot carry its own measured span — it is attributed
    analytically instead (``halo_*`` fields of ``gauges()``, the same
    measured-vs-analytic discipline as ``docs/observability.md``).

In-program query gather: each chip ``take``s its local logits rows for the
whole padded query vector, masks to the queries it owns, and one ``psum``
replicates the summed result — exact in f32 (every non-owner contributes
literal zeros), one tiny collective per batch instead of shipping the full
``(k, B, nout)`` logits to the host.
"""

from __future__ import annotations

import numpy as np

from ..parallel.mesh import AXIS, make_mesh_1d, replicate, shard_stacked
from ..utils.timers import PhaseTimer
from .batcher import MicroBatcher, default_buckets
from .router import VertexRouter

# host-side stages of one served micro-batch, in order — the span names the
# engine emits (docs/serving.md glossary).  ``serve:overlap`` wraps the
# host-side route/pack/dispatch of batch t+1 while batch t's device program
# is still in flight (double-buffered dispatch — run_loadgen(concurrent=True)
# emits it, and the PR-7 trace parser measures the overlap it names).
SERVE_STAGES = ("serve:route", "serve:batch", "serve:compile_lookup",
                "serve:forward", "serve:overlap")


class InFlightBatch:
    """Handle of one dispatched micro-batch (``ServeEngine.submit``): the
    device program is already running asynchronously; ``result()`` blocks on
    the replicated logits and slices off the bucket padding.  The separation
    is what double-buffered dispatch rides — the caller routes/packs/submits
    batch t+1 BEFORE consuming batch t's result."""

    def __init__(self, engine, out, nq: int):
        self._engine = engine
        self._out = out
        self._nq = nq

    def result(self) -> np.ndarray:
        with self._engine.spans.span("serve:forward"):
            out = np.asarray(self._out)            # readback = sync
        return out[: self._nq]


class CheckpointWatcher:
    """Poll a ``CheckpointManager`` directory (PR-13 rotation layout) and
    hot-swap the newest INTACT checkpoint into a running engine — the
    ``--watch-checkpoint-dir`` machinery: one ``poll`` per flush window,
    zero re-compiles (params are inputs to the AOT programs), corrupt
    candidates skipped with a loud warning (the manager's newest-intact
    rule), provenance mismatches raised loudly (a wrong-plan checkpoint in
    the watch directory is a config bug, not something to serve past)."""

    def __init__(self, directory: str, last_step: int = -1):
        from ..resilience.checkpoint import CheckpointManager

        self.manager = CheckpointManager(directory)
        self.last_step = int(last_step)

    def poll(self, engine) -> bool:
        """Swap in the newest intact checkpoint stamped past ``last_step``;
        returns True when a swap happened.  Corruption is detected by the
        swap itself (``load_checkpoint_leaves`` checksums every array
        BEFORE provenance checking or any engine state change), so each
        candidate is read exactly once — a separate verify pass would
        double the checkpoint I/O sitting in front of queued queries."""
        import warnings

        from ..utils.checkpoint import CheckpointCorruptError

        for step, path in reversed(self.manager.checkpoints()):
            if step <= self.last_step:
                return False
            try:
                engine.swap_weights(path)
            except CheckpointCorruptError as e:
                warnings.warn(
                    f"checkpoint watch: {path!r} is corrupt ({e}); trying "
                    "the previous candidate", RuntimeWarning, stacklevel=2)
                continue
            self.last_step = step
            return True
        return False


class ServeEngine:
    """Forward-only partitioned inference over one plan + checkpoint."""

    def __init__(
        self,
        plan,
        fin: int,
        widths: list[int],
        model: str = "gcn",
        activation: str | None = None,
        final_activation: str = "none",
        comm_schedule: str | None = None,
        halo_dtype: str | None = None,
        mesh=None,
        params=None,
        checkpoint: str | None = None,
        max_batch: int = 64,
        buckets: tuple | None = None,
        latency_budget_ms: float = 50.0,
        shed_factor: float | None = None,
        seed: int = 0,
        precompile: bool = True,
        mode: str = "full",
        memory_budget: int | None = None,
    ):
        """``mode='full'`` is the PR-8 engine: one full partitioned forward
        per micro-batch.  ``mode='subgraph'`` is query-proportional
        (``docs/serving.md`` phase 2): each batch computes only the routed
        queries' L-hop receptive sets (``serve/subgraph.py``) with no
        per-layer exchange.  Routed logits are f32-bit-identical to
        ``evaluate()`` in full mode and equal to the ulp in sub-graph mode
        (another compiled shape of the same op sequence)."""
        if halo_dtype is not None and model != "gcn":
            raise ValueError(
                "halo_dtype is a GCN wire lever; the GAT exchange ships "
                "attention tables (same rule as the trainer)")
        if mode not in ("full", "subgraph"):
            raise ValueError(f"unknown serve mode {mode!r} "
                             "(know 'full', 'subgraph')")
        from ..train.fullbatch import MODELS, resolve_forward_setup

        if MODELS[model].full_batch_only:
            raise ValueError(
                f"model={model!r} is not served yet: the engine carries no "
                "model_args (mhgat: heads, concat, slope) and its checkpoint "
                "provenance does not record them — use "
                "FullBatchTrainer.predict()")

        self.plan = plan
        self.fin = int(fin)
        self.widths = list(widths)
        self.model = model
        self.mode = mode
        self.weights_rev = 0          # bumped by every swap_weights — the
        # serve-event attribution key for windows spanning a hot-swap
        # PGAT semantics: bare stacked modules, no inter-layer activation —
        # the trainer CLI's default; parity with evaluate() needs the same
        self.activation = activation if activation is not None else (
            "none" if model == "gat" else "relu")
        self.final_activation = final_activation
        self.halo_dtype = halo_dtype
        self.setup = resolve_forward_setup(
            plan, fin, widths, model=model, comm_schedule=comm_schedule,
            serve_subgraph=(mode == "subgraph"), halo_dtype=halo_dtype)
        self.comm_schedule = self.setup.comm_schedule
        self.comm_decision = self.setup.decision
        # analytic per-chip HBM footprint (obs/memory.py) + the
        # --memory-budget plan-time gate — before params/array shipping,
        # failing loudly with the itemized per-family table
        from ..obs.memory import check_memory_budget, memory_model
        self.memory = memory_model(
            plan, fin, widths,
            workload="serve_subgraph" if mode == "subgraph" else "serve",
            model=model, halo_dtype=halo_dtype, setup=self.setup)
        self._memory_measured = None       # best measured join so far (the
        # widest compiled bucket's memory_analysis — _ensure_compiled)
        check_memory_budget(self.memory, memory_budget,
                            what=f"{model} serve engine ({mode})")
        self.mesh = mesh if mesh is not None else make_mesh_1d(plan.k)
        self.router = VertexRouter(plan)
        self.batcher = MicroBatcher(
            max_batch=max_batch,
            latency_budget_ms=latency_budget_ms,
            buckets=buckets if buckets is not None
            else default_buckets(max_batch),
            shed_factor=shed_factor)
        self.recorder = None
        self.timer = PhaseTimer()
        from ..obs.tracing import SpanTimer
        self.spans = SpanTimer(timer=self.timer)

        # ---- params: checkpoint (provenance-verified) or given/fresh init
        dims = list(zip([fin] + self.widths[:-1], self.widths))
        if checkpoint is not None:
            params = self._load_params(checkpoint, dims)
        elif params is None:
            import jax
            params = self.setup.init_fn(jax.random.PRNGKey(seed), dims)
        self.params = replicate(self.mesh, params)
        self.pa = shard_stacked(self.mesh, self.setup.ship_arrays(plan))
        self._h0 = None                    # set_features()
        self._compiled: dict[int, object] = {}   # bucket size → executable
        self.compile_count = 0
        # sub-graph serving state (mode='subgraph')
        self.sgindex = None
        self._features = None              # global (n, fin) numpy rows
        self._sg_compiled: dict[tuple, object] = {}   # shape key → program
        self._stabilizers = None           # GAT per-layer cg (host f32)
        self._cg_dev = None
        self._stab_prog = None
        self._watch = None                 # CheckpointWatcher
        self._sg_totals = {"queries": 0, "batches": 0, "touched_rows": 0,
                           "recipe_edges": 0, "wire_rows": 0, "flops": 0}
        if mode == "subgraph":
            # resolve_forward_setup(serve_subgraph=True) already refused
            # the Pallas aggregator (the one fold the compact mirror
            # cannot reproduce bit-exactly)
            from .subgraph import SubgraphIndex
            self.sgindex = SubgraphIndex(plan, model)
        if precompile and mode == "full":
            for b in self.batcher.buckets:
                self._ensure_compiled(b)

    # ------------------------------------------------------------- loading
    def _load_params(self, path: str, dims):
        """Restore the params tree (opt state skipped — inference has none)
        from a trainer checkpoint, verifying plan digest + model config
        FIRST so a wrong-plan/model restore fails with a clear message."""
        import jax

        from ..utils.checkpoint import (load_checkpoint_leaves,
                                        verify_checkpoint_provenance)
        leaves, meta = load_checkpoint_leaves(path)
        verify_checkpoint_provenance(
            meta, plan=self.plan, model=self.model, fin=self.fin,
            widths=self.widths, activation=self.activation,
            final_activation=self.final_activation,
            what=f"serve engine ({path!r})")
        template = self.setup.init_fn(jax.random.PRNGKey(0), dims)
        tleaves, treedef = jax.tree.flatten(template)
        if len(leaves) < len(tleaves):
            raise ValueError(
                f"checkpoint {path!r} has {len(leaves)} leaves, the "
                f"{self.model} params tree needs {len(tleaves)} — not a "
                "checkpoint of this model config")
        # (params, opt_state) flattens params-first; the leading leaves ARE
        # the params in tree order
        got = leaves[: len(tleaves)]
        for have, want in zip(got, tleaves):
            if tuple(have.shape) != tuple(np.shape(want)):
                raise ValueError(
                    f"checkpoint param leaf shape {have.shape} != expected "
                    f"{np.shape(want)} — wrong fin/widths for this "
                    "checkpoint (read_checkpoint_meta shows its config)")
        self.checkpoint_meta = meta
        return jax.tree.unflatten(treedef, got)

    # ------------------------------------------------------------ features
    def set_features(self, features: np.ndarray) -> None:
        """Scatter + shard the global ``(n, fin)`` feature rows once — the
        serving working set every forward reads (features are part of the
        model's input, not of a query)."""
        features = np.asarray(features, dtype=np.float32)
        if features.shape != (self.plan.n, self.fin):
            raise ValueError(
                f"features shape {features.shape} != "
                f"({self.plan.n}, {self.fin})")
        h0 = self.plan.scatter_rows(features)
        self._h0 = shard_stacked(self.mesh, h0)
        self._features = features
        if self.mode == "subgraph" and self.model == "gat":
            self._refresh_stabilizers()

    # ------------------------------------------------- GAT stabilizer cache
    def _refresh_stabilizers(self) -> None:
        """Precompute the per-layer softmax stabilizers ``cg`` of the FULL
        graph under the current (params, features) — the one full-graph
        quantity the sub-graph program consumes as an input
        (``gat_forward_local(collect_stabilizers=True)``; see
        ``serve/subgraph.py``).  Constant until the next weight swap or
        feature load, so the cost is one full forward per swap, amortized
        over every query served from it."""
        import jax
        from jax.sharding import PartitionSpec as P

        if self._stab_prog is None:
            fwd = self.setup.forward_fn
            fwd_static = self.setup.fwd_static
            symmetric = self.plan.symmetric

            def per_chip(params, pa, h0):
                pa = jax.tree.map(lambda x: x[0], pa)
                _, cgs = fwd(
                    params, h0[0], pa,
                    activation=self.activation,
                    final_activation=self.final_activation,
                    symmetric=symmetric,
                    collect_stabilizers=True,
                    **fwd_static,
                )
                return cgs                       # pmax'd → replicated

            self._stab_prog = jax.jit(jax.shard_map(
                per_chip, mesh=self.mesh,
                in_specs=(P(), P(AXIS), P(AXIS)), out_specs=P()))
        self._stabilizers = np.asarray(
            self._stab_prog(self.params, self.pa, self._h0),
            dtype=np.float32)
        self._cg_dev = None                      # re-replicated on next use

    def _cgs(self):
        """Replicated device (L,) stabilizer vector (zeros for GCN — the
        program never reads them and jit prunes the argument)."""
        if self._cg_dev is None:
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P

            host = (self._stabilizers if self._stabilizers is not None
                    else np.zeros((self.nlayers,), np.float32))
            self._cg_dev = jax.device_put(
                host, NamedSharding(self.mesh, P()))
        return self._cg_dev

    # ------------------------------------------------------------- compile
    def lower_bucket(self, q: int):
        """AOT-LOWER the bucket-``q`` forward+gather program (no compile,
        no execution) — the serve entry point of the static-analysis HLO
        audit (``sgcn_tpu/analysis``): the lowered module is exactly the
        program ``_ensure_compiled(q)`` compiles, so the audit checks the
        real serving step's collective census (L halo exchanges + ONE
        logit-gather psum), wire dtypes and the no-donation contract
        (engine params are reused across batches — a donated buffer here
        would be a use-after-free by design)."""
        import jax
        from jax import lax
        from jax.sharding import NamedSharding, PartitionSpec as P
        import jax.numpy as jnp

        fwd = self.setup.forward_fn
        fwd_static = self.setup.fwd_static
        extra = ({"halo_dtype": self.halo_dtype}
                 if self.halo_dtype is not None else {})
        symmetric = self.plan.symmetric

        def per_chip(params, pa, h0, q_owner, q_local):
            pa = jax.tree.map(lambda x: x[0], pa)
            h0 = h0[0]
            logits = fwd(
                params, h0, pa,
                activation=self.activation,
                final_activation=self.final_activation,
                symmetric=symmetric,
                **fwd_static, **extra,
            ).astype("float32")
            sel = jnp.take(logits, q_local, axis=0)        # (Q, nout)
            mine = (q_owner == lax.axis_index(AXIS)).astype(
                jnp.float32)[:, None]
            # non-owners contribute exact zeros, so the psum'd row IS the
            # owner's f32 logits row bit-for-bit
            return lax.psum(sel * mine, AXIS)

        smapped = jax.shard_map(
            per_chip,
            mesh=self.mesh,
            in_specs=(P(), P(AXIS), P(AXIS), P(), P()),
            out_specs=P(),
        )
        rep = NamedSharding(self.mesh, P())
        shd = NamedSharding(self.mesh, P(AXIS))
        params_s = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=rep),
            self.params)
        pa_s = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=shd),
            self.pa)
        h0_s = jax.ShapeDtypeStruct((self.plan.k, self.plan.b, self.fin),
                                    np.dtype(np.float32), sharding=shd)
        qs = jax.ShapeDtypeStruct((q,), np.dtype(np.int32), sharding=rep)
        return jax.jit(smapped).lower(params_s, pa_s, h0_s, qs, qs)

    def _ensure_compiled(self, q: int):
        if q not in self._compiled:
            self._compiled[q] = self.lower_bucket(q).compile()
            self.compile_count += 1
            self._join_memory(f"bucket{q}", self._compiled[q])
        return self._compiled[q]

    def _join_memory(self, program: str, compiled) -> None:
        """Join XLA's measured per-device figures against the analytic
        footprint for one freshly compiled program (schema v6): keeps the
        peak-heaviest join as the engine's measured side and, under a
        recorder, re-publishes the manifest memory block and appends one
        ``memory`` event — the serving half of the model-vs-measured
        memory contract (docs/observability.md)."""
        from ..obs.memory import measure_compiled

        measured = measure_compiled(compiled)
        if measured is None:
            return
        if (self._memory_measured is None
                or measured["peak_bytes"]
                > self._memory_measured["peak_bytes"]):
            self._memory_measured = measured
        if self.recorder is not None:
            self.recorder.set_memory(
                self.memory.block(self._memory_measured))
            self.recorder.record_memory(
                program=program, model=self.memory, measured=measured)

    def lower_subgraph(self, key: tuple):
        """AOT-LOWER the sub-graph program for one shape key (no compile,
        no execution) — the ``serve_subgraph`` entry point of the
        static-analysis HLO audit: the lowered module is exactly the
        program a real batch of this key runs, and its audited contract is
        the tentpole's: NO collective beyond the single logit-gather psum
        (every per-layer exchange is gone — sources are computed locally
        from host-gathered features), zero donation, no host callbacks."""
        import jax
        from jax import lax
        from jax.sharding import NamedSharding, PartitionSpec as P
        import jax.numpy as jnp

        from .subgraph import (batch_struct, key_buckets,
                               subgraph_forward_gat, subgraph_forward_gcn)

        if self.sgindex is None:
            raise ValueError("engine was built with mode='full' — "
                             "sub-graph programs exist under "
                             "mode='subgraph'")
        model, qb = key[0], key[1]
        buckets = key_buckets(self.sgindex, key)

        def per_chip(params, cgs, arrays, q_owner, q_pos):
            arrays = jax.tree.map(lambda x: x[0], arrays)
            if model == "gcn":
                h = subgraph_forward_gcn(
                    params, arrays["feats"], arrays, buckets,
                    activation=self.activation,
                    final_activation=self.final_activation,
                    halo_dtype=self.halo_dtype)
            else:
                h = subgraph_forward_gat(
                    params, cgs, arrays["feats"], arrays, buckets,
                    activation=self.activation,
                    final_activation=self.final_activation)
            h = h.astype("float32")
            sel = jnp.take(h, q_pos, axis=0)           # (Qb, nout)
            mine = q_owner == lax.axis_index(AXIS)
            # where, not multiply: the receptive set's outer-shell rows are
            # computed with incomplete neighborhoods and may hold NaN —
            # a non-owner's masked gather must contribute EXACT zeros
            return lax.psum(jnp.where(mine[:, None], sel, 0.0), AXIS)

        smapped = jax.shard_map(
            per_chip, mesh=self.mesh,
            in_specs=(P(), P(), P(AXIS), P(), P()), out_specs=P())
        rep = NamedSharding(self.mesh, P())
        shd = NamedSharding(self.mesh, P(AXIS))
        params_s = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=rep),
            self.params)
        cgs_s = jax.ShapeDtypeStruct((self.nlayers,), np.dtype(np.float32),
                                     sharding=rep)
        arr_s = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=shd),
            batch_struct(self.sgindex, key, self.fin))
        qs = jax.ShapeDtypeStruct((qb,), np.dtype(np.int32), sharding=rep)
        return jax.jit(smapped).lower(params_s, cgs_s, arr_s, qs, qs)

    def _ensure_compiled_sg(self, key: tuple):
        if key not in self._sg_compiled:
            self._sg_compiled[key] = self.lower_subgraph(key).compile()
            self.compile_count += 1
            self._join_memory(f"subgraph{key[1]}", self._sg_compiled[key])
        return self._sg_compiled[key]

    # --------------------------------------------------------------- query
    def submit(self, qids) -> "InFlightBatch":
        """Dispatch one micro-batch WITHOUT blocking: host stages (route,
        pack, compile lookup) run and the device program launches
        asynchronously; the returned handle's ``result()`` blocks.  This is
        the double-buffered dispatch primitive — submit batch t+1 while
        batch t runs, then consume t (``run_loadgen(concurrent=True)``)."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        if self._h0 is None:
            raise ValueError(
                "no features loaded — call set_features(features) before "
                "serving queries")
        qids = np.asarray(qids, dtype=np.int64).reshape(-1)
        nq = len(qids)
        if nq == 0:
            return InFlightBatch(
                self, np.zeros((0, self.widths[-1]), np.float32), 0)
        if self._watch is not None:
            # one poll per flush window: a newer intact checkpoint in the
            # watched directory hot-swaps in before this batch dispatches
            self._watch.poll(self)
        if self.mode == "subgraph":
            return self._submit_subgraph(qids)
        with self.spans.span("serve:route"):
            owners, locals_ = self.router.lookup(qids)
        with self.spans.span("serve:batch"):
            bucket = self.batcher.bucket_for(nq)
            q_owner = np.full(bucket, -1, np.int32)    # pad: matches no chip
            q_local = np.zeros(bucket, np.int32)
            q_owner[:nq] = owners
            q_local[:nq] = locals_
            rep = NamedSharding(self.mesh, P())
            q_owner = jax.device_put(q_owner, rep)
            q_local = jax.device_put(q_local, rep)
        with self.spans.span("serve:compile_lookup"):
            prog = self._ensure_compiled(bucket)
        out = prog(self.params, self.pa, self._h0, q_owner, q_local)
        return InFlightBatch(self, out, nq)

    def _submit_subgraph(self, qids) -> "InFlightBatch":
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ..obs.attribution import subgraph_batch_flops
        from .subgraph import build_batch

        if self._features is None:
            raise ValueError(
                "sub-graph serving gathers receptive-set features on the "
                "host — call set_features(features) first")
        if self.model == "gat" and self._stabilizers is None:
            self._refresh_stabilizers()
        with self.spans.span("serve:route"):
            # router-grouped receptive sets: co-located queries share
            # receptive rows, the spill-minimizing batching route() exists
            # for (docs/serving.md phase 2)
            batch = build_batch(self.sgindex, self.router, self._features,
                                qids, self.nlayers)
        with self.spans.span("serve:batch"):
            rep = NamedSharding(self.mesh, P())
            shd = NamedSharding(self.mesh, P(AXIS))
            arrs = jax.tree.map(lambda a: jax.device_put(a, shd),
                                batch.arrays)
            q_owner = jax.device_put(batch.q_owner, rep)
            q_pos = jax.device_put(batch.q_pos, rep)
        with self.spans.span("serve:compile_lookup"):
            prog = self._ensure_compiled_sg(batch.key)
        out = prog(self.params, self._cgs(), arrs, q_owner, q_pos)
        t = self._sg_totals
        t["queries"] += batch.nq
        t["batches"] += 1
        t["touched_rows"] += batch.touched_rows
        t["recipe_edges"] += batch.recipe_edges
        t["wire_rows"] += batch.key[1]              # padded psum rows
        t["flops"] += subgraph_batch_flops(
            batch.touched_rows, batch.recipe_edges, self.fin, self.widths,
            model=self.model)
        return InFlightBatch(self, out, batch.nq)

    def query(self, qids) -> np.ndarray:
        """Serve one micro-batch of global vertex ids → ``(len(qids), nout)``
        f32 logits.  Stages are spanned (``SERVE_STAGES``); the batch is
        padded to its bucket(s) so no query count — and in sub-graph mode no
        receptive-set size — triggers a recompile after warm-up."""
        return self.submit(qids).result()

    def swap_weights(self, checkpoint: str) -> dict:
        """Hot-swap a new checkpoint into the running engine with ZERO
        re-lowering/re-compilation: provenance (plan digest + model config)
        is verified FIRST — a mismatch raises before any engine state
        changes — then the new leaves replace ``self.params`` (params are
        ordinary inputs to every AOT program, so ``compile_count`` is
        pinned across the swap), ``weights_rev`` bumps for window
        attribution, and the GAT stabilizer cache refreshes (one full
        forward — the per-swap cost sub-graph serving amortizes).  Returns
        the new checkpoint's meta block."""
        import time as _time

        t0 = _time.perf_counter()
        dims = list(zip([self.fin] + self.widths[:-1], self.widths))
        params = self._load_params(checkpoint, dims)   # verifies first
        self.params = replicate(self.mesh, params)
        self.weights_rev += 1
        if self.mode == "subgraph" and self.model == "gat" \
                and self._h0 is not None:
            self._refresh_stabilizers()
        if self.recorder is not None:
            self.recorder.record_swap(
                path=checkpoint, weights_rev=self.weights_rev,
                checkpoint_step=self.checkpoint_meta.get("step"),
                wall_s=_time.perf_counter() - t0)
        return self.checkpoint_meta

    def attach_checkpoint_watch(self, directory: str) -> "CheckpointWatcher":
        """Watch a PR-13 rotation directory: each flush window polls once
        and hot-swaps the newest intact checkpoint in (CLI:
        ``--watch-checkpoint-dir``)."""
        last = -1
        if getattr(self, "checkpoint_meta", None):
            step = self.checkpoint_meta.get("step")
            if step is not None:        # step 0 is a real stamp, not falsy
                last = int(step)
        self._watch = CheckpointWatcher(directory, last_step=last)
        return self._watch

    def warmup(self, qids) -> None:
        """Serve one throwaway batch per pre-compiled bucket (cycling
        ``qids`` to fill each).  A bucket's FIRST dispatch pays runtime
        autotuning even with an AOT program, and deadline flushes land on
        the small buckets — run this before a measured window or the
        overhead lands in the published p99."""
        qids = np.asarray(qids, dtype=np.int64).reshape(-1)
        if qids.size == 0:
            raise ValueError("warmup needs at least one query id")
        for b in self.batcher.buckets:
            self.query(np.resize(qids, b))

    # -------------------------------------------------------------- gauges
    @property
    def nlayers(self) -> int:
        return len(self.widths)

    def gauges(self) -> dict:
        """Analytic per-batch/per-query gauges of the serving forward —
        plan-derived (full mode) or accumulated over the served batches'
        true receptive sets (sub-graph mode); deterministic either way
        (zero-band in the bench trend).  In full mode the forward runs
        ``nlayers`` exchanges per micro-batch regardless of batch size, so
        the steady-state per-QUERY wire cost is the full-batch amortization
        ``nlayers · wire_rows/exchange ÷ max_batch``."""
        from ..obs.attribution import forward_flops

        # plan-derived per-chip residency (obs/memory.py) — `analytic: true`
        # says the *_bytes figures are the model's, not the allocator's
        mem = {"analytic": True,
               "model_bytes": self.memory.total_bytes,
               **{f"{name}_bytes": int(v)
                  for name, v in self.memory.families.items() if v}}
        if self._memory_measured is not None:
            mem["measured"] = True
            mem["measured_peak_bytes"] = self._memory_measured["peak_bytes"]
        if self.mode == "subgraph":
            t = self._sg_totals
            nq = max(t["queries"], 1)
            return {
                "serve_mode": "subgraph",
                "memory": mem,
                "comm_schedule": self.comm_schedule,
                "weights_rev": self.weights_rev,
                # prefixed: these are ENGINE-LIFETIME accumulators (warmup
                # included), not one window's measured counts — a bare
                # "queries" key would shadow ServeResult.summary()'s in the
                # CLI report merge (observed: 24-query window reported 32)
                "subgraph_queries_total": t["queries"],
                "subgraph_batches_total": t["batches"],
                "touched_rows_total": t["touched_rows"],
                "touched_rows_per_query": round(t["touched_rows"] / nq, 6),
                "recipe_edges_total": t["recipe_edges"],
                "subgraph_flops_per_query": round(t["flops"] / nq, 3),
                # the ONLY wire traffic is the logit-gather psum's padded
                # (Qb, nout) buffer — per query ~one logits row
                "wire_rows_per_query": round(t["wire_rows"] / nq, 6),
                # the full-forward figures a batch of this plan WOULD have
                # paid — the denominators of the per-query cut
                "full_rows_per_forward": int(self.plan.k * self.plan.b),
                "full_forward_flops": forward_flops(
                    self.plan, self.fin, self.widths, model=self.model),
                "buckets": sorted(self._sg_compiled),
                "compiles": self.compile_count,
            }
        wire = self.plan.wire_rows_per_exchange(self.comm_schedule)
        true = int(self.plan.predicted_send_volume.sum())
        return {
            "serve_mode": "full",
            "memory": mem,
            "comm_schedule": self.comm_schedule,
            "weights_rev": self.weights_rev,
            "exchanges_per_batch": self.nlayers,
            "wire_rows_per_exchange": wire,
            "true_rows_per_exchange": true,
            "wire_rows_per_batch": self.nlayers * wire,
            "wire_rows_per_query": round(
                self.nlayers * wire / self.batcher.max_batch, 6),
            "full_rows_per_forward": int(self.plan.k * self.plan.b),
            "full_forward_flops": forward_flops(
                self.plan, self.fin, self.widths, model=self.model),
            "buckets": list(self.batcher.buckets),
            "compiles": self.compile_count,
        }

    # ------------------------------------------------------------ recorder
    def attach_recorder(self, recorder) -> None:
        """Attach a ``RunRecorder``: stage spans become schema events and
        the transport decision lands in the manifest (the same
        reconstructibility contract as the trainers)."""
        self.recorder = recorder
        self.spans.recorder = recorder
        if self.comm_decision:
            recorder.set_comm_schedule(self.comm_decision)
        if getattr(self, "memory", None) is not None:
            # includes the measured join when a bucket already compiled
            # (precompile=True attaches after __init__)
            recorder.set_memory(self.memory.block(self._memory_measured))

    def record_window(self, result, offered_qps: float | None = None,
                      mode: str = "open") -> None:
        """Emit one schema-v3 ``serve`` event for a completed traffic
        window (``loadgen.ServeResult``) with the batching counters and the
        analytic wire gauge riding along."""
        if self.recorder is None:
            return
        g = self.gauges()
        self.recorder.record_serve(
            queries=result.queries,
            achieved_qps=result.achieved_qps,
            latency_p50_ms=result.p50_ms,
            latency_p95_ms=result.p95_ms,
            latency_p99_ms=result.p99_ms,
            window_s=result.window_s,
            offered_qps=offered_qps,
            mode=mode,
            batches=result.batches,
            mean_batch=result.mean_batch,
            deadline_flushes=self.batcher.deadline_flushes,
            full_flushes=self.batcher.full_flushes,
            latency_budget_ms=self.batcher.latency_budget_ms,
            compiles=self.compile_count,
            buckets=list(self.batcher.buckets),
            comm_schedule=self.comm_schedule,
            wire_rows_per_query=g["wire_rows_per_query"],
            # v5 additive: hot-swap attribution + sub-graph gauges (a
            # window spanning a swap_weights names both revisions via the
            # swap event between two serve events)
            serve_mode=self.mode,
            weights_rev=self.weights_rev,
            touched_rows_per_query=g.get("touched_rows_per_query"),
            subgraph_flops_per_query=g.get("subgraph_flops_per_query"),
            # v4 additive: deadline-shed count of the window — present
            # only when shedding is configured, so pre-shedding events
            # keep their exact shape
            shed=(getattr(result, "shed", 0)
                  if self.batcher.shed_factor is not None else None),
            shed_factor=self.batcher.shed_factor,
        )
