"""Trainer CLI — flag-compatible with the reference's PGCN/PGAT family.

Reference: ``python PGCN.py -a A.mtx -p partvec -b nccl|gloo -s size -l layers
-f features`` (``README.md:92``, ``GPU/PGCN.py:262-278``); ``PGCN-Mini-batch``
adds ``-n batch_size``; ``PGAT.py`` is the attention flavor.  Here one CLI
covers all four trainers:

  * ``-b jax``  — run on the platform's real devices (TPU mesh), the
    NCCL-equivalent backend per ``BASELINE.json``;
  * ``-b cpu``  — force ``-s`` virtual host CPU devices, the Gloo-equivalent
    "cluster on one box" mode (``GPU/PGCN.py:166-169``);
  * ``--model gat`` — PGAT;  ``-n BATCH`` — PGCN-Mini-batch.

Without ``--features-mtx/--labels-mtx`` the synthetic benchmark harness inputs
are used, like the reference benchmark scripts: ``H[i] = [i]·f`` and
``labels = arange % f`` (``GPU/PGCN.py:186-192``).

The backend env setup must happen before JAX initializes, so heavy imports
are deferred into ``main`` after arg parsing.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys


def _budget(text: str):
    """``--replica-budget`` values: a non-negative int or ``auto`` (the
    λ·degree-knee rule, ``parallel/plan.py::choose_replica_budget``)."""
    if text == "auto":
        return "auto"
    return int(text)


def _mem_budget(text: str) -> int:
    """``--memory-budget`` values: bytes with optional binary suffix
    (``512M``, ``2G``; ``obs/memory.py::parse_bytes``)."""
    from ..obs.memory import parse_bytes

    try:
        return parse_bytes(text)
    except ValueError as e:
        raise argparse.ArgumentTypeError(str(e)) from e


def _resume_auto(mgr, target, recorder):
    """The ONE --resume auto sequence for both trainers: restore the
    newest intact checkpoint into ``target``, surface the partial-state
    flag the loader set, and emit the schema-v4 resume event.  Returns
    ``(start_step, resumed_block)``."""
    start_step, rpath, skipped = mgr.load_latest(target)
    partial = getattr(target, "last_restore_partial", False)
    resumed = {"step": start_step, "path": rpath,
               "fallback": bool(skipped)}
    if recorder is not None:
        recorder.record_resume(step=start_step, path=rpath,
                               fallback=bool(skipped),
                               partial_state=partial,
                               skipped=skipped or None)
    return start_step, resumed


def _fit_minibatch_durable(tr, feats, labels, args, mgr, recorder, ctx,
                           start_ep: int = 0) -> dict:
    """Mini-batch flavor of the durable path: fit in chunks of
    ``--checkpoint-every`` EPOCHS (the mini-batch trainer's natural
    checkpoint grain — its per-batch plans have no stable step identity),
    saving the inner trainer's state after each chunk.  ``--warmup`` runs
    only on a fresh start (warm-up steps are real optimizer steps; a
    resumed run must not repeat them).  No bit-identity claim here — that
    contract is the full-batch trainer's (docs/resilience.md)."""
    from ..resilience.runner import save_and_record

    every = args.checkpoint_every
    total = args.epochs
    history: list = []
    warm = args.warmup if start_ep == 0 else 0
    done, report = start_ep, None
    while done < total:
        run = total - done
        if every:
            run = min(run, every - done % every)
        report = tr.fit(feats, labels, epochs=run, warmup=warm)
        warm = 0
        history += report.get("loss_history", [])
        done += run
        if every and done % every == 0 and ctx.is_coordinator:
            save_and_record(mgr, tr.inner, done, recorder=recorder)
    if report is None:
        # resumed at (or past) the full schedule: nothing left to train
        report = {"note": "resume found the epoch schedule complete"}
    report.update(epochs=done, loss_history=history, start_epoch=start_ep)
    return report


def main() -> None:
    p = argparse.ArgumentParser(description="sgcn_tpu distributed trainer")
    p.add_argument("-a", "--adjacency", default=None,
                   help=".mtx adjacency (or use --npz)")
    p.add_argument("-p", "--partvec", required=True,
                   help="part vector: text (.gp/.hp/.rp) or pickle")
    p.add_argument("-b", "--backend", default="jax", choices=["jax", "cpu"])
    p.add_argument("-s", "--nparts", type=int, required=True)
    p.add_argument("-l", "--nlayers", type=int, default=2)
    p.add_argument("-f", "--nfeatures", type=int, default=16)
    p.add_argument("-n", "--batch-size", type=int, default=None,
                   help="enable the mini-batch trainer")
    p.add_argument("--model", default="gcn",
                   choices=["gcn", "gat", "mhgat", "deepergcn", "rgcn"],
                   help="gat = the reference's single-head PGAT layer; "
                        "mhgat = multi-head graph attention as published "
                        "(LeakyReLU scores, per-edge softmax, bias, linear "
                        "skips; full-batch, a2a, f32 only): --hidden is the "
                        "width PER HEAD, hidden layers concatenate --heads "
                        "heads, the last layer averages them; deepergcn = "
                        "DeeperGCN as published (softmax_sg GENConv, res+ "
                        "blocks, BatchNorm; full-batch, a2a, f32 only): an "
                        "encoder, -l layers of --hidden as one scanned, "
                        "per-layer-checkpointed body, and a head; rgcn = "
                        "R-GCN as published for typed graphs (a mean and a "
                        "weight per relation, a weight per node type on the "
                        "row itself, per-node embeddings for types without "
                        "features; full-batch, a2a, f32 only): give "
                        "--node-types, --relations and --label-type")
    p.add_argument("--node-types", default=None,
                   help="--model rgcn: the node types in id order, "
                        "name:count:features|embedding, comma-separated "
                        "(ids are contiguous per type; the counts sum to n)")
    p.add_argument("--relations", default=None,
                   help="--model rgcn: source:name:destination, "
                        "comma-separated; at most one per ordered pair of "
                        "types; a reverse relation is listed like any other")
    p.add_argument("--label-type", default=None,
                   help="--model rgcn: the node type whose rows have labels")
    p.add_argument("--heads", type=int, default=1,
                   help="attention heads per layer (--model mhgat)")
    p.add_argument("--activation", default=None,
                   choices=["relu", "sigmoid", "elu", "none"],
                   help="inter-layer activation; defaults to relu for gcn "
                        "(GPU/PGCN.py:147) and none for gat — the reference "
                        "stacks bare PGAT modules with no nonlinearity "
                        "between them (GPU/PGAT.py:202-213)")
    p.add_argument("--loss", default="xent", choices=["xent", "bce"],
                   help="xent = torch-stack log-softmax+NLL "
                        "(GPU/PGCN.py:204-205); bce = the MPI stack's "
                        "sigmoid+BCE with the reported `err` metric "
                        "(Parallel-GCN/main.c:70-90,318-335)")
    p.add_argument("--dtype", default=None, choices=["bfloat16"],
                   help="mixed-precision compute (f32 master params)")
    p.add_argument("--halo-dtype", default=None, choices=["bfloat16"],
                   help="wire-only exchange dtype: halves a2a ICI bytes, "
                        "all compute stays f32 (full-batch GCN only)")
    p.add_argument("--halo-staleness", type=int, default=0, choices=[0, 1],
                   help="0 (default) = exact per-layer halo exchange; 1 = "
                        "pipelined one-step-stale exchange: layer L of step "
                        "t aggregates with the halo exchanged during step "
                        "t-1, so the a2a leaves the critical path "
                        "(full-batch GCN, symmetric adjacency only; see "
                        "docs/stale_halo.md)")
    p.add_argument("--halo-delta", action="store_true",
                   help="halo-delta cache on top of --halo-staleness 1: "
                        "boundary rows ship as bf16 deltas accumulated "
                        "into the carried remote halo (half the wire bytes)")
    p.add_argument("--sync-every", type=int, default=0,
                   help="stale mode: run a full-sync (exact-math) step "
                        "every N steps to bound staleness/quantization "
                        "drift; replica mode: refresh the replica tables "
                        "every N steps; 0 = only the initializing first "
                        "step")
    p.add_argument("--replica-budget", type=_budget, default=0,
                   metavar="B|auto",
                   help="hot-halo replication (docs/replication.md): "
                        "promote the top-B boundary rows (by λ·degree from "
                        "the comm plan) to persistent replicas on their "
                        "consumer chips — they leave the per-layer wire "
                        "entirely, refreshed only on --sync-every refresh "
                        "steps (at --sync-every 1 the trajectory is f32-"
                        "bit-identical to the no-replica path); full-batch "
                        "GCN, symmetric adjacency, f32; composes with "
                        "--comm-schedule a2a/ragged, --halo-dtype AND "
                        "--halo-staleness 1 (the composed mode: stale "
                        "steps hide the already-shrunken exchange); "
                        "'auto' picks B at the knee of the plan's "
                        "λ·degree curve (the pick lands in the manifest "
                        "comm_schedule block); 0 = off")
    p.add_argument("--refresh-band", type=float, default=None, metavar="RHO",
                   help="drift-driven PARTIAL replica refresh "
                        "(docs/replication.md): scheduled refresh steps "
                        "ship only the replica rows whose relative drift "
                        "‖x−base‖/‖base‖ exceeds RHO, as deltas against "
                        "the refresh baseline (CaPGNN-style) — booked at "
                        "the actual shipped rows; requires "
                        "--replica-budget > 0, --comm-schedule a2a, no "
                        "staleness; step 0 always refreshes in full")
    p.add_argument("--comm-schedule", default=None,
                   choices=["a2a", "ragged", "auto"],
                   help="halo transport (docs/comm_schedule.md): a2a = "
                        "dense globally-padded all_to_all (default); "
                        "ragged = per-round-sized ppermute ring (same "
                        "math, bit-identical f32 losses, fewer wire bytes "
                        "on skewed partitions; symmetric adjacency — GCN "
                        "ships feature rows, GAT its attention tables; "
                        "composes with --halo-staleness 1: the carry "
                        "becomes round-structured and BOTH perf levers "
                        "apply); auto = ragged when the plan's padding "
                        "efficiency drops below 0.5 (under staleness: "
                        "whenever ragged ships fewer wire rows — the "
                        "hidden exchange makes latency moot).  Default: "
                        "$SGCN_COMM_SCHEDULE, else a2a")
    p.add_argument("--epochs", type=int, default=4)
    p.add_argument("--warmup", type=int, default=1)
    p.add_argument("--lr", type=float, default=0.01)
    p.add_argument("--hidden", type=int, default=None,
                   help="hidden width (default: nfeatures)")
    p.add_argument("--normalize", action="store_true",
                   help="apply Â normalization to the input adjacency")
    p.add_argument("--features-mtx", default=None)
    p.add_argument("--labels-mtx", default=None)
    p.add_argument("--npz", default=None,
                   help="planetoid/ogbn-style .npz snapshot (adj_* CSR + "
                        "attr_* + labels); replaces -a, and supplies "
                        "features/labels unless --features-mtx/--labels-mtx "
                        "explicitly override them")
    p.add_argument("--experiment", default=None, choices=["accuracy"],
                   help="accuracy = the PGCN-Accuracy parity experiment "
                        "(GPU/PGCN-Accuracy.py, README.md:110): train the "
                        "dense oracle + the partitioned trainer(s) on a "
                        "planetoid split and report test accuracy for each")
    p.add_argument("--train-per-class", type=int, default=20,
                   help="planetoid split: train nodes per class")
    p.add_argument("--resume", default=None, metavar="CKPT|auto",
                   help="restore FULL trainer state (params/opt_state plus "
                        "the stale/replica carries, sync counters, "
                        "controller retunes and cumulative comm gauges — "
                        "docs/resilience.md) from a checkpoint .npz before "
                        "training; 'auto' picks the newest INTACT "
                        "checkpoint in --checkpoint-dir, falling back past "
                        "corrupt files with a logged warning, and trains "
                        "only the REMAINING steps of the "
                        "--warmup + --epochs schedule — bit-identical to "
                        "the uninterrupted run for every supported mode")
    p.add_argument("--save-checkpoint", default=None, metavar="CKPT",
                   help="save the full trainer state after training")
    p.add_argument("--checkpoint-dir", default=None, metavar="DIR",
                   help="durable checkpoint directory "
                        "(docs/resilience.md): step-stamped atomic "
                        "checkpoints with keep-last-K rotation — the "
                        "directory --resume auto restores from")
    p.add_argument("--checkpoint-every", type=int, default=0, metavar="N",
                   help="write a durable full-state checkpoint into "
                        "--checkpoint-dir every N optimizer steps "
                        "(full-batch; for the mini-batch trainer N counts "
                        "EPOCHS).  0 = off")
    p.add_argument("--keep-checkpoints", type=int, default=3, metavar="K",
                   help="rotation depth of --checkpoint-dir (keep the "
                        "newest K checkpoints; default 3)")
    p.add_argument("--profile", default=None, metavar="DIR",
                   help="capture a jax.profiler trace of the training run "
                        "into DIR (view with TensorBoard / xprof; the "
                        "reference's analogue is its manual phase timers, "
                        "Cagnet/main.c:35-38 — see utils/timers.py for "
                        "those)")
    p.add_argument("--metrics-out", default=None, metavar="DIR",
                   help="run-telemetry directory (sgcn_tpu.obs): writes a "
                        "run manifest (config, git rev, plan digest) plus a "
                        "per-step JSONL event stream — loss, grad-norm, "
                        "wall time, the hidden/exposed comm split, roofline "
                        "attribution and (stale mode) drift gauges; render "
                        "with scripts/obs_report.py, schema in "
                        "docs/observability.md")
    p.add_argument("--memory-budget", type=_mem_budget, default=None,
                   metavar="BYTES",
                   help="per-chip HBM budget (suffixes K/M/G/T, e.g. 2G): "
                        "the analytic footprint model "
                        "(sgcn_tpu.obs.memory) is checked at PLAN time — "
                        "before any array ships or compile starts — and an "
                        "over-budget (plan, mode) fails with the itemized "
                        "per-family breakdown")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args()

    # pure flag conflicts fail BEFORE any dataset load (minutes at scale)
    if args.halo_dtype and (args.batch_size is not None
                            or args.model != "gcn"
                            or args.experiment == "accuracy"
                            or args.dtype):
        raise SystemExit(
            "--halo-dtype narrows the full-batch GCN exchange only (the "
            "mini-batch trainer and GAT narrow via --dtype bfloat16; the "
            "accuracy-parity harness is defined for the f32-wire config; "
            "under --dtype bfloat16 the wire is already bf16, so the flag "
            "would be a silent no-op)")
    if args.halo_staleness and (args.batch_size is not None
                                or args.model != "gcn"
                                or args.experiment == "accuracy"
                                or args.dtype):
        raise SystemExit(
            "--halo-staleness 1 pipelines the full-batch GCN trainer only "
            "(the mini-batch sweep re-plans per batch, GAT ships per-layer "
            "attention tables, the accuracy-parity harness is defined for "
            "the exact exchange, and the carries are f32 state — drop the "
            "conflicting flag)")
    if args.halo_delta and not args.halo_staleness:
        raise SystemExit(
            "--halo-delta configures the stale pipelined exchange; add "
            "--halo-staleness 1")
    if args.sync_every and not (args.halo_staleness or args.replica_budget):
        raise SystemExit(
            "--sync-every schedules the stale mode's full-sync steps or "
            "the replica mode's refresh steps; add --halo-staleness 1 or "
            "--replica-budget B")
    if args.replica_budget and (args.batch_size is not None
                                or args.model != "gcn"
                                or args.experiment == "accuracy"
                                or args.dtype
                                or args.halo_delta):
        raise SystemExit(
            "--replica-budget replicates rows of the full-batch GCN "
            "exchange only (the mini-batch trainer re-plans per batch, so "
            "replica carries have no stable identity across batch plans; "
            "GAT ships per-layer attention tables; the accuracy-parity "
            "harness is defined for the exact exchange; the carries are "
            "f32 state; composition with --halo-delta is deferred — the "
            "delta baseline and the replica carry would disagree on what "
            "a stale step ships — drop the conflicting flag)")
    if args.refresh_band is not None and (not args.replica_budget
                                          or args.halo_staleness
                                          or args.comm_schedule == "ragged"):
        raise SystemExit(
            "--refresh-band schedules the drift-driven PARTIAL replica "
            "refresh: it requires --replica-budget > 0, rides the dense "
            "a2a transport, and does not compose with --halo-staleness 1 "
            "(the composed mode's replica state lives inside the stale "
            "carry) — drop the conflicting flag")
    # --comm-schedule ragged composes with --halo-staleness 1 since the
    # round-structured stale carry (pspmm_stale_ragged); the remaining
    # genuinely unsupported combo is the accuracy-parity harness, which is
    # defined for the default transport only
    if args.comm_schedule == "ragged" and args.experiment == "accuracy":
        raise SystemExit(
            "--comm-schedule ragged: the accuracy-parity harness is "
            "defined for the default transport — drop the conflicting "
            "flag or use --comm-schedule auto")
    if args.checkpoint_every < 0:
        raise SystemExit(
            f"--checkpoint-every must be >= 0, got {args.checkpoint_every}")
    if (args.checkpoint_every or args.resume == "auto") \
            and not args.checkpoint_dir:
        raise SystemExit(
            "--checkpoint-every / --resume auto operate on the durable "
            "checkpoint directory; add --checkpoint-dir DIR "
            "(docs/resilience.md)")
    if args.checkpoint_dir and args.experiment == "accuracy":
        raise SystemExit(
            "--experiment accuracy trains fresh oracle+partitioned pairs; "
            "durable checkpointing (--checkpoint-dir) is not supported "
            "there")
    if (args.checkpoint_dir and args.batch_size is not None
            and args.resume and args.resume != "auto"):
        raise SystemExit(
            "mini-batch: explicit --resume CKPT does not compose with "
            "--checkpoint-dir (the durable stamps count EPOCHS of THIS "
            "schedule and would collide with the chained run's) — resume "
            "the durable directory with --resume auto, or drop "
            "--checkpoint-dir for a chained run")

    if args.metrics_out:
        # before any heavy import: heartbeat() in the launch/backend layers
        # reads this env var, so rendezvous pings land in the run directory
        import os
        os.environ["SGCN_METRICS_OUT"] = args.metrics_out

    from ..utils.backend import place_compile_cache, use_cpu_devices
    if args.backend == "cpu":
        use_cpu_devices(args.nparts)
    place_compile_cache()

    import jax

    from ..parallel.launch import init_distributed
    ctx = init_distributed()   # no-op single-process; SLURM/TPU-pod rendezvous otherwise

    recorder = None
    if args.metrics_out and ctx.is_coordinator:
        # rank-0-only, like every other end-of-run artifact (the reference
        # prints rank-0 stats; multi-host ranks share the filesystem)
        from ..obs import RunRecorder
        recorder = RunRecorder(args.metrics_out, config=vars(args))
        recorder.set_backend()

    import numpy as np

    from ..io.mtx import read_dense_features, read_mtx, read_onehot_labels
    from ..parallel.plan import build_comm_plan
    from ..partition.emit import read_partvec, read_partvec_pickle
    from ..prep import normalize_adjacency
    from .fullbatch import FullBatchTrainer, make_train_data
    from .minibatch import MiniBatchTrainer

    feats = labels = None
    if args.npz:
        from ..io.datasets import load_npz_dataset
        a, feats, labels = load_npz_dataset(args.npz)
    elif args.adjacency:
        a = read_mtx(args.adjacency)
    else:
        raise SystemExit("need -a/--adjacency or --npz")
    if args.normalize:
        a = normalize_adjacency(a)
    n = a.shape[0]
    try:
        pv = read_partvec(args.partvec)
    except (UnicodeDecodeError, ValueError):
        pv = read_partvec_pickle(args.partvec)
    if len(pv) != n:
        raise SystemExit(f"partvec length {len(pv)} != n {n}")
    k = args.nparts
    if pv.max() >= k:
        raise SystemExit(f"partvec references part {pv.max()} >= k {k}")

    f = args.nfeatures
    if args.features_mtx:
        feats = read_dense_features(args.features_mtx)
    if feats is not None:
        f = feats.shape[1]
    else:
        # synthetic benchmark harness inputs (GPU/PGCN.py:186-192)
        feats = np.tile(np.arange(n, dtype=np.float32)[:, None], (1, f))
    if args.labels_mtx:
        labels = read_onehot_labels(args.labels_mtx)
    if labels is not None:
        nclasses = int(labels.max()) + 1
    else:
        labels = np.arange(n) % f
        nclasses = f
    labels = labels.astype(np.int32)

    hidden = args.hidden or f
    widths = [hidden] * (args.nlayers - 1) + [nclasses]
    # PGAT stacks bare modules: no inter-layer nonlinearity unless asked
    activation = args.activation or {"gat": "none", "mhgat": "elu"}.get(
        args.model, "relu")
    model_args = None
    if args.model == "mhgat":
        # hidden layers concatenate their heads, the last averages them
        widths = [args.heads * hidden] * (args.nlayers - 1) + [nclasses]
        model_args = {"heads": (args.heads,) * args.nlayers}
    if args.model == "deepergcn":
        # -l counts the GENConv layers; the head is one more width
        widths = [hidden] * args.nlayers + [nclasses]
    if args.model == "rgcn":
        if not (args.node_types and args.relations and args.label_type):
            raise SystemExit("--model rgcn needs --node-types, --relations "
                             "and --label-type")
        model_args = {
            "types": [dict(zip(("name", "count", "input"), t.split(":")))
                      for t in args.node_types.split(",")],
            "relations": [tuple(r.split(":"))
                          for r in args.relations.split(",")],
            "label_type": args.label_type}
        for t in model_args["types"]:
            t["count"] = int(t["count"])
    if (args.model in ("mhgat", "deepergcn", "rgcn")
            and args.batch_size is not None):
        raise SystemExit(f"--model {args.model} is full-batch only; drop -n")

    prof = (jax.profiler.trace(args.profile) if args.profile
            else contextlib.nullcontext())

    if args.experiment == "accuracy":
        # the PGCN-Accuracy run (GPU/PGCN-Accuracy.py, README.md:110):
        # planetoid split, oracle vs partitioned trainers, test accuracy each.
        # The parity harness compares against the dense GCN oracle, so it is
        # defined for the gcn/xent/relu/f32 configuration only — reject other
        # flags instead of silently mislabeling a default-config run.
        if (args.model != "gcn" or args.loss != "xent" or args.dtype
                or (args.activation or "relu") != "relu"):
            raise SystemExit(
                "--experiment accuracy compares against the dense GCN oracle "
                "and supports only --model gcn --loss xent --activation relu "
                "(f32); drop the conflicting flags")
        if args.resume or args.save_checkpoint:
            raise SystemExit(
                "--experiment accuracy trains fresh oracle+partitioned pairs "
                "for the parity comparison; --resume/--save-checkpoint are "
                "not supported there")
        from ..io.datasets import planetoid_split
        from .accuracy import run_accuracy_parity
        train_mask, test_mask = planetoid_split(
            labels, per_class=args.train_per_class, seed=args.seed)
        with prof:
            report = run_accuracy_parity(
                a, feats, labels, pv, k, widths, train_mask, test_mask,
                epochs=args.epochs, batch_size=args.batch_size, lr=args.lr,
                seed=args.seed)
        report["experiment"] = "accuracy"
        report["backend"] = args.backend
        if recorder is not None:
            # the parity harness drives its own trainers; record the run's
            # identity + outcome (no per-step stream for this experiment)
            if args.profile:
                # --profile and --metrics-out compose: the manifest records
                # where the trace landed (and its gzip'd size), so
                # obs_report.py parses it from the run directory alone
                recorder.set_profile(args.profile)
            recorder.record_summary(report)
            recorder.close()
        if ctx.is_coordinator:
            print(json.dumps(report), flush=True)
        return

    # durable checkpointing (docs/resilience.md): one manager per run
    # directory; saves are coordinator-only (multi-host ranks share the
    # filesystem), restores run on every rank
    mgr = None
    if args.checkpoint_dir:
        from ..resilience.checkpoint import CheckpointManager
        mgr = CheckpointManager(args.checkpoint_dir,
                                keep_last=args.keep_checkpoints)
    resumed = None

    from ..obs.memory import MemoryBudgetError

    with prof:
        if args.batch_size is not None:
            try:
                tr = MiniBatchTrainer(a, pv, k, fin=f, widths=widths,
                                      batch_size=args.batch_size, lr=args.lr,
                                      model=args.model, loss=args.loss,
                                      activation=activation, seed=args.seed,
                                      compute_dtype=args.dtype,
                                      comm_schedule=args.comm_schedule,
                                      memory_budget=args.memory_budget)
            except MemoryBudgetError as e:
                raise SystemExit(str(e)) from e
            if recorder is not None:
                recorder.set_partitioner({"partvec": args.partvec, "k": k})
                tr.attach_recorder(recorder)
            state = tr.inner          # checkpointable params/opt_state holder
            start_step = 0
            if args.resume == "auto":
                # mini-batch checkpoints count EPOCHS completed
                start_step, resumed = _resume_auto(mgr, state, recorder)
            elif args.resume:
                from ..utils.checkpoint import load_checkpoint
                start_step = load_checkpoint(state, args.resume)
            if mgr is not None:
                report = _fit_minibatch_durable(
                    tr, feats, labels, args, mgr, recorder, ctx,
                    start_ep=start_step if args.resume == "auto" else 0)
            else:
                report = tr.fit(feats, labels, epochs=args.epochs,
                                warmup=args.warmup)
        else:
            plan = build_comm_plan(a, pv, k)
            try:
                tr = FullBatchTrainer(plan, fin=f, widths=widths, lr=args.lr,
                                      model=args.model, loss=args.loss,
                                      activation=activation, seed=args.seed,
                                      compute_dtype=args.dtype,
                                      halo_dtype=args.halo_dtype,
                                      halo_staleness=args.halo_staleness,
                                      halo_delta=args.halo_delta,
                                      sync_every=args.sync_every,
                                      comm_schedule=args.comm_schedule,
                                      replica_budget=args.replica_budget,
                                      refresh_band=args.refresh_band,
                                      memory_budget=args.memory_budget,
                                      model_args=model_args)
            except MemoryBudgetError as e:
                raise SystemExit(str(e)) from e
            if recorder is not None:
                recorder.set_plan(plan, partitioner={"partvec": args.partvec,
                                                     "k": k})
                recorder.set_backend(tr.mesh)
                tr.attach_recorder(recorder)
            state = tr
            start_step = 0
            if args.resume == "auto":
                start_step, resumed = _resume_auto(mgr, tr, recorder)
            elif args.resume:
                from ..utils.checkpoint import load_checkpoint
                start_step = load_checkpoint(state, args.resume)
            data = make_train_data(plan, feats, labels)
            if mgr is not None:
                # the resumable per-step loop: durable checkpoints every N
                # steps + the fault-injection kill point.  --resume auto:
                # --warmup/--epochs name the run's TOTAL step schedule and
                # the resumed process completes the remainder (bit-identity
                # contract, docs/resilience.md).  Explicit --resume CKPT
                # keeps its chained semantics (train warmup+epochs MORE
                # steps) but threads the loaded step through, so the
                # durable stamps continue the trainer's real step count
                # instead of restarting at 1
                from ..resilience.runner import run_resumable
                save_mgr = mgr if ctx.is_coordinator else None
                total = args.warmup + args.epochs
                if args.resume and args.resume != "auto":
                    total += start_step
                report = run_resumable(
                    tr, data, total,
                    manager=save_mgr,
                    checkpoint_every=(args.checkpoint_every
                                      if save_mgr is not None else 0),
                    start_step=(start_step if args.resume else 0))
            else:
                report = tr.fit(data, epochs=args.epochs,
                                warmup=args.warmup)
    if resumed is not None:
        report["resumed"] = resumed
    if recorder is not None and args.profile:
        # --profile and --metrics-out compose: the jax.profiler trace is
        # flushed when the `with prof:` context above exits, so NOW the
        # manifest can record its path and gzip'd size — obs_report.py
        # finds and parses the trace from the run directory alone
        recorder.set_profile(args.profile)
    if args.save_checkpoint and ctx.is_coordinator:
        # coordinator-only write (multi-host ranks share the filesystem);
        # step accumulates across chained resumes.  Warm-up epochs are real
        # optimizer steps (fit() runs them before the timed ones), so they
        # count toward the saved step — chained --resume runs would otherwise
        # silently accumulate unreported parameter updates.
        from ..utils.checkpoint import save_checkpoint
        if args.batch_size is not None and mgr is not None:
            # the mini-batch DURABLE path stamps at EPOCH grain everywhere
            # (the CheckpointManager files count epochs) — the final stamp
            # must agree with them whether or not this run resumed, or two
            # bit-identical end states would carry different step stamps
            final_step = args.epochs
        elif args.resume == "auto":
            # --resume auto completes a FIXED total schedule (the durable
            # path's bit-identity contract): the final step is absolute,
            # not additive
            final_step = args.epochs + args.warmup
        else:
            final_step = start_step + args.epochs + args.warmup
        report["checkpoint"] = save_checkpoint(
            state, args.save_checkpoint, step=final_step)

    # rank-0-style end-of-run line (GPU/PGCN.py:226-238)
    report["backend"] = args.backend
    report["model"] = args.model
    report["activation"] = activation
    report["loss"] = args.loss
    report.pop("loss_history", None)
    if recorder is not None:
        recorder.close()
    if ctx.is_coordinator:
        print(json.dumps(report), flush=True)


if __name__ == "__main__":
    main()
