"""Checkpoint / resume for trainer state, with provenance.

The reference has NO checkpointing (SURVEY.md §5.4): weights are re-randomized
every run and only the offline partition artifacts act as a cache.  For long
TPU runs that is a real gap, so the framework adds a minimal, dependency-free
checkpoint: all pytree leaves of (params, opt_state) plus a step counter in
one ``.npz``, restored into the trainer's existing tree structure (which also
re-applies the mesh sharding via device_put on assignment).

Provenance (PR-8): ``save_checkpoint`` additionally records the comm plan's
digest (``obs.recorder.plan_digest`` — the same 16-hex identity the run
manifest carries) and the model config (model kind, input width, layer dims,
activation/loss, the GAT fused-form mode) when the trainer exposes them.
``load_checkpoint`` and the serve engine (``sgcn_tpu/serve/engine.py``)
verify both and fail with a CLEAR message on mismatch — before provenance, a
wrong-config restore either died deep inside tree-structure shape errors or,
worse, a checkpoint from a DIFFERENT graph/run with coincidentally matching
leaf shapes restored cleanly and served the wrong model.  Weights themselves
are partition-independent (no leaf is vertex-indexed), so a deliberate
same-graph re-partition restore stays possible: ``load_checkpoint(...,
verify=False)``.  The mini-batch trainer suppresses the digest entirely
(its inner plan is a per-batch plan, not a run identity — the
``checkpoint_plan`` sentinel below).  Checkpoints written before this
change carry no provenance and still load (nothing to verify).

Durability + full state (PR-13, ``docs/resilience.md``): checkpoints are
now written ATOMICALLY (temp + fsync + rename — a kill mid-save leaves the
previous checkpoint intact, never a truncated ``.npz``), carry a per-array
CRC32 recorded in the meta block (a bit-flipped or truncated file fails
with a clear ``CheckpointCorruptError``, not a numpy deep-failure), and are
FULL-state: beyond (params, opt_state) they persist the trainer's
algorithmic state — the stale-halo / replica carry leaves, the sync/refresh
step counters, the controller's effective ``sync_every`` + retune log, and
the cumulative CommStats gauges — so a resumed stale/replica run is
f32-bit-identical to the uninterrupted one and its comm totals reconcile
across the seam.  The format is versioned (``CKPT_VERSION``): pre-PR-13
checkpoints (no version key) still load as params-only with a LOUD
"partial state" warning when the trainer carries algorithmic state the file
cannot supply.

Works for any trainer exposing ``params`` / ``opt_state`` / ``mesh``
(FullBatchTrainer, MiniBatchTrainer.inner).
"""

from __future__ import annotations

import json
import warnings
import zlib

import jax
import numpy as np

from ..parallel.mesh import replicate

# non-leaf keys the .npz may carry next to the ``leaf_<i>`` arrays — counting
# leaves as ``len(files) - 1`` broke the moment a second metadata key landed,
# so loaders count ``leaf_`` keys explicitly instead
_META_STEP = "__step__"
_META_DIGEST = "__plan_digest__"
_META_MODEL = "__model_config__"
# full-state format (v2): version stamp, JSON train-state block
# (counters/controller/comm-stats, docs/resilience.md), per-array CRC32 map
_META_VERSION = "__ckpt_version__"
_META_STATE = "__train_state__"
_META_CHECKSUMS = "__checksums__"

# current writer version.  v1 = the pre-PR-13 params-only format (no
# version key); v2 adds carry_<i> arrays + train state + checksums.  A file
# claiming a NEWER version than this reader fails loudly (verify path) —
# silently dropping state a newer writer recorded is exactly the class of
# bug this layer exists to prevent.
CKPT_VERSION = 2


class CheckpointCorruptError(RuntimeError):
    """A checkpoint file failed structural or checksum validation —
    truncated container, unreadable member, or a per-array CRC mismatch.
    Distinct from ``ValueError`` (provenance/shape mismatches of an INTACT
    file) so the durable loader (``resilience.CheckpointManager``) can fall
    back to the previous checkpoint on corruption while still failing fast
    on a genuinely wrong restore."""


def _crc(arr: np.ndarray) -> int:
    """CRC32 over an array's dtype, shape and raw bytes."""
    arr = np.ascontiguousarray(arr)
    h = zlib.crc32(repr((arr.dtype.str, arr.shape)).encode())
    return zlib.crc32(arr.tobytes(), h) & 0xFFFFFFFF


# container/member failure modes of a damaged .npz: zipfile raises
# BadZipFile (incl. its own CRC check), zlib.error on a bad stream, OSError
# on short reads, ValueError/KeyError on mangled headers
_NPZ_DAMAGE = (OSError, ValueError, KeyError, zlib.error)


def _open_guarded(path: str):
    """``np.load`` with container damage mapped to CheckpointCorruptError."""
    import zipfile

    try:
        return np.load(path)
    except zipfile.BadZipFile as e:
        raise CheckpointCorruptError(
            f"checkpoint {path!r} is not a readable .npz (truncated or "
            f"damaged container: {e}) — likely a kill mid-write of a "
            "non-atomic writer, or on-disk corruption; the durable loader "
            "falls back to the previous intact checkpoint") from e
    except _NPZ_DAMAGE as e:
        raise CheckpointCorruptError(
            f"checkpoint {path!r} failed to open: {e}") from e


def _read_arrays(data, keys, path: str, checksums: dict | None) -> dict:
    """Read + checksum-verify the named members of an open npz."""
    import zipfile

    out = {}
    for key in keys:
        try:
            arr = data[key]
        except (zipfile.BadZipFile, *_NPZ_DAMAGE) as e:
            raise CheckpointCorruptError(
                f"checkpoint {path!r}: member {key!r} is unreadable "
                f"({e}) — corrupt checkpoint; the durable loader falls "
                "back to the previous intact one") from e
        if checksums is not None and key in checksums:
            have = _crc(arr)
            if have != int(checksums[key]):
                raise CheckpointCorruptError(
                    f"checkpoint {path!r}: checksum mismatch on {key!r} "
                    f"(recorded {int(checksums[key])}, computed {have}) — "
                    "corrupt checkpoint; the durable loader falls back to "
                    "the previous intact one")
        out[key] = arr
    return out


def _norm(path: str) -> str:
    # np.savez appends .npz itself; normalize so save/load accept the same path
    return path if path.endswith(".npz") else path + ".npz"


def model_config_of(trainer) -> dict | None:
    """The checkpoint's model-identity block, read off a trainer's attrs
    (best-effort: a trainer that predates an attribute simply omits it)."""
    cfg = {}
    for attr, key in (("model", "model"), ("fin", "fin"),
                      ("widths", "widths"), ("activation", "activation"),
                      ("final_activation", "final_activation"),
                      ("loss_name", "loss")):
        v = getattr(trainer, attr, None)
        if v is not None:
            cfg[key] = list(v) if key == "widths" else v
    return cfg or None


def save_checkpoint(trainer, path: str, step: int = 0) -> str:
    """Write one atomic full-state checkpoint (module docstring): the
    (params, opt_state) leaves, the trainer's resume state (carry leaves +
    counters + controller + comm gauges, ``resume_state()``) when it
    exposes one, provenance, the format version, and a per-array CRC map —
    committed via temp + fsync + rename so a kill at ANY byte leaves
    either the previous checkpoint or the complete new one."""
    # (a trainer with parameters owned with the rows hands them over in
    # global row order: ``FullBatchTrainer.host_state``)
    leaves = jax.tree.leaves(trainer.host_state()
                             if hasattr(trainer, "host_state")
                             else (trainer.params, trainer.opt_state))
    arrays = {f"leaf_{i}": np.asarray(x) for i, x in enumerate(leaves)}
    arrays[_META_STEP] = np.asarray(step, dtype=np.int64)
    # ``checkpoint_plan`` (may be explicitly None) overrides ``plan``: the
    # mini-batch trainer checkpoints through its inner trainer, whose plan
    # is a padded per-BATCH plan — its digest varies with batch_size/
    # nbatches/pad envelope, so it is not a stable run identity and
    # recording it would make every cross-batch-shape resume a digest error
    plan = getattr(trainer, "checkpoint_plan", getattr(trainer, "plan", None))
    if plan is not None:
        from ..obs.recorder import plan_digest
        arrays[_META_DIGEST] = np.asarray(plan_digest(plan))
    cfg = model_config_of(trainer)
    if cfg is not None:
        arrays[_META_MODEL] = np.asarray(json.dumps(cfg))
    if hasattr(trainer, "resume_state"):
        state, carry_leaves = trainer.resume_state()
        for i, arr in enumerate(carry_leaves):
            arrays[f"carry_{i}"] = arr
        arrays[_META_STATE] = np.asarray(json.dumps(state))
    arrays[_META_VERSION] = np.asarray(CKPT_VERSION, dtype=np.int64)
    # checksum EVERY array, meta blocks included — a bit flip in __step__
    # or a still-parseable __train_state__ digit would otherwise pass
    # "intact" verification and silently resume at the wrong step.  The
    # checksum map itself is the one uncovered array: any mangling of it
    # either fails to parse (CheckpointCorruptError) or miscompares some
    # covered array (ditto) — both fail safe toward the fallback path.
    arrays[_META_CHECKSUMS] = np.asarray(json.dumps(
        {key: _crc(np.asarray(arr)) for key, arr in arrays.items()}))
    path = _norm(path)
    from ..resilience.atomic import atomic_write
    with atomic_write(path, "wb") as fh:
        np.savez(fh, **arrays)
    return path


def read_checkpoint_meta(path: str) -> dict:
    """Provenance block of a checkpoint file: ``{step, plan_digest,
    model_config, n_leaves, version, state, checksums, n_carry}`` —
    digest/config/state ``None`` for files that predate them, ``version``
    1 for pre-PR-13 params-only files.  Cheap (``np.load`` is lazy; only
    metadata arrays read).  A damaged container raises
    ``CheckpointCorruptError`` with a clear message."""
    with _open_guarded(_norm(path)) as data:
        meta = _read_meta_open(data, path)
    return meta


def _read_meta_open(data, path: str) -> dict:
    import zipfile

    try:
        checksums = (json.loads(str(data[_META_CHECKSUMS].item()))
                     if _META_CHECKSUMS in data.files else None)
        if checksums is not None:
            # verify the META arrays up front (leaves/carries are checked
            # by _read_arrays at their own read): corruption in the step
            # counter or the train-state JSON must fail as loudly as a
            # damaged leaf
            for key in (_META_STEP, _META_DIGEST, _META_MODEL,
                        _META_VERSION, _META_STATE):
                if key in data.files and key in checksums:
                    have = _crc(np.asarray(data[key]))
                    if have != int(checksums[key]):
                        raise CheckpointCorruptError(
                            f"checkpoint {path!r}: checksum mismatch on "
                            f"metadata {key!r} (recorded "
                            f"{int(checksums[key])}, computed {have}) — "
                            "corrupt checkpoint; the durable loader falls "
                            "back to the previous intact one")
        return {
            "step": int(data[_META_STEP]) if _META_STEP in data.files else 0,
            "plan_digest": (str(data[_META_DIGEST].item())
                            if _META_DIGEST in data.files else None),
            "model_config": (json.loads(str(data[_META_MODEL].item()))
                             if _META_MODEL in data.files else None),
            "version": (int(data[_META_VERSION])
                        if _META_VERSION in data.files else 1),
            "state": (json.loads(str(data[_META_STATE].item()))
                      if _META_STATE in data.files else None),
            "checksums": checksums,
            "n_leaves": sum(1 for f in data.files if f.startswith("leaf_")),
            "n_carry": sum(1 for f in data.files if f.startswith("carry_")),
        }
    except (zipfile.BadZipFile, *_NPZ_DAMAGE) as e:
        # json.JSONDecodeError is a ValueError, so a mangled metadata JSON
        # lands here too — every flavor of damage is one exception class
        raise CheckpointCorruptError(
            f"checkpoint {path!r}: metadata block unreadable ({e}) — "
            "corrupt checkpoint") from e


def verify_checkpoint_provenance(meta: dict, plan=None,
                                 model: str | None = None,
                                 fin: int | None = None,
                                 widths=None,
                                 activation: str | None = None,
                                 final_activation: str | None = None,
                                 what: str = "checkpoint") -> None:
    """Raise ``ValueError`` with a CLEAR message when the checkpoint's
    recorded provenance contradicts the given plan / model config.  Fields
    the checkpoint does not record are skipped (pre-provenance files load)."""
    if plan is not None and meta.get("plan_digest") is not None:
        from ..obs.recorder import plan_digest
        have = plan_digest(plan)
        if have != meta["plan_digest"]:
            raise ValueError(
                f"{what}: plan digest mismatch — checkpoint was saved under "
                f"plan {meta['plan_digest']}, this run's plan is {have}: a "
                "different graph, partvec, k or comm layout.  Model weights "
                "are partition-independent, so a same-graph re-partition can "
                "be restored deliberately (load_checkpoint(..., "
                "verify=False)); a different GRAPH cannot — check "
                "read_checkpoint_meta before overriding.")
    cfg = meta.get("model_config") or {}
    # activation is part of the served function, not just bookkeeping: the
    # same param tree under a different activation restores cleanly and
    # computes different logits — exactly the silent-wrong-model class this
    # layer exists to catch
    for key, want in (("model", model), ("fin", fin),
                      ("widths", list(widths) if widths is not None
                       else None),
                      ("activation", activation),
                      ("final_activation", final_activation)):
        if want is not None and cfg.get(key) is not None and cfg[key] != want:
            raise ValueError(
                f"{what}: model config mismatch on {key!r} — checkpoint "
                f"records {cfg[key]!r}, this run asks for {want!r}; "
                "reconstruct the trainer/engine with the checkpoint's "
                "config (read_checkpoint_meta shows it).")


def load_checkpoint_leaves(path: str) -> tuple[list, dict]:
    """``(leaves, meta)`` — every ``leaf_<i>`` array in index order plus the
    provenance block, checksum-verified when the file records checksums
    (corruption raises ``CheckpointCorruptError`` with a clear message,
    never a numpy deep-failure).  The serve engine restores params-only
    trees from this (the leading leaves of the ``(params, opt_state)``
    flattening) — carry arrays are NOT read here, so serving a full-state
    checkpoint pays for the params only."""
    path = _norm(path)
    with _open_guarded(path) as data:
        meta = _read_meta_open(data, path)
        _check_version(meta, path)
        arrays = _read_arrays(
            data, [f"leaf_{i}" for i in range(meta["n_leaves"])],
            path, meta["checksums"])
    return [arrays[f"leaf_{i}"] for i in range(meta["n_leaves"])], meta


def _check_version(meta: dict, path: str) -> None:
    if meta["version"] > CKPT_VERSION:
        raise ValueError(
            f"checkpoint {path!r} is format v{meta['version']}, this "
            f"reader understands up to v{CKPT_VERSION} — written by a "
            "newer sgcn_tpu; silently dropping state a newer writer "
            "recorded is not an option, upgrade the reader")


def verify_checkpoint_file(path: str) -> dict:
    """Full structural + checksum verification of EVERY data array (leaves
    and carries); returns the meta block.  Raises
    ``CheckpointCorruptError`` on any damage.  A standalone integrity
    probe — no trainer needed — for operators auditing a checkpoint
    directory; the resume path itself does NOT call this
    (``CheckpointManager.load_latest`` verifies through
    ``load_checkpoint``, which checks everything before its first
    assignment, in one read pass)."""
    path = _norm(path)
    with _open_guarded(path) as data:
        meta = _read_meta_open(data, path)
        _check_version(meta, path)
        keys = ([f"leaf_{i}" for i in range(meta["n_leaves"])]
                + [f"carry_{i}" for i in range(meta["n_carry"])])
        _read_arrays(data, keys, path, meta["checksums"])
    return meta


def _trainer_is_stateful(trainer) -> bool:
    """Does this trainer hold algorithmic state beyond (params, opt_state)
    — carries or a live controller — that a params-only restore would
    silently reinitialize?"""
    return (getattr(trainer, "halo_carry", None) is not None
            or getattr(trainer, "replica_carry", None) is not None
            or getattr(trainer, "controller", None) is not None)


def load_checkpoint(trainer, path: str, verify: bool = True) -> int:
    """Restore the FULL trainer state in place; returns the saved step
    counter.

    The trainer must have been constructed with the same model config — the
    recorded provenance (plan digest, model kind, dims) is verified FIRST
    with a clear message, then the leaf count and shapes are validated
    against its current trees.  ``verify=False`` skips the provenance check
    (weights are partition-independent, so a deliberate same-graph
    re-partition restore is legitimate); the shape validation always runs.

    Full-state restore (format v2, ``docs/resilience.md``): the stale/
    replica carry leaves, step counters, effective ``sync_every`` +
    controller log and cumulative CommStats gauges are restored through
    ``trainer.restore_resume_state`` — a resumed run is then f32-bit-
    identical to the uninterrupted one.  A PRE-full-state checkpoint (or a
    mode mismatch between the file's carry and the trainer's) loads
    params-only with a LOUD ``RuntimeWarning`` naming exactly which state
    was not restored — never silently."""
    # ONE container open for everything this restore may need: meta,
    # leaves, and the carry arrays when the file has them (re-opening the
    # zip for the carries would double resume I/O on the shared
    # filesystems multi-host runs live on)
    path_n = _norm(path)
    with _open_guarded(path_n) as data:
        meta = _read_meta_open(data, path_n)
        _check_version(meta, path_n)
        keys = ([f"leaf_{i}" for i in range(meta["n_leaves"])]
                + [f"carry_{i}" for i in range(meta["n_carry"])])
        arrays = _read_arrays(data, keys, path_n, meta["checksums"])
    leaves = [arrays[f"leaf_{i}"] for i in range(meta["n_leaves"])]
    file_carry = [arrays[f"carry_{i}"] for i in range(meta["n_carry"])]
    if verify:
        verify_checkpoint_provenance(
            meta, plan=getattr(trainer, "plan", None),
            model=getattr(trainer, "model", None),
            fin=getattr(trainer, "fin", None),
            widths=getattr(trainer, "widths", None),
            activation=getattr(trainer, "activation", None),
            final_activation=getattr(trainer, "final_activation", None),
            what=f"load_checkpoint({path!r})")
    cur = jax.tree.leaves(trainer.host_state()
                          if hasattr(trainer, "host_state")
                          else (trainer.params, trainer.opt_state))
    if len(cur) != len(leaves):
        raise ValueError(
            f"checkpoint has {len(leaves)} leaves, trainer expects {len(cur)}")
    for have, want in zip(leaves, cur):
        want = np.asarray(want)
        if tuple(have.shape) != want.shape:
            raise ValueError(
                f"checkpoint leaf shape {have.shape} != trainer {want.shape}")
        if have.dtype != want.dtype:
            raise ValueError(
                f"checkpoint leaf dtype {have.dtype} != trainer {want.dtype}")
    # ---- full-state validation BEFORE any assignment (a failed load must
    # leave the trainer untouched, not half-restored)
    state, carry_leaves = meta.get("state"), []
    restore_state = state is not None and hasattr(trainer,
                                                  "restore_resume_state")
    if restore_state:
        want_carry = (trainer._carry_attr()
                      if hasattr(trainer, "_carry_attr") else None)
        have_carry = state.get("carry")
        # a carry-MODE mismatch (either direction) downgrades the whole
        # restore to params-only: importing the other mode's step
        # counters, effective sync_every and cumulative comm gauges would
        # publish hidden/replica accounting this trainer's mode never
        # produced (and a foreign sync_every silently reshapes the sync
        # schedule) — all-or-nothing keeps the report internally
        # consistent
        if have_carry is not None and want_carry != have_carry:
            restore_state = False
            warnings.warn(
                f"load_checkpoint({path!r}): checkpoint carries "
                f"{have_carry!r} state but this trainer runs "
                f"{want_carry or 'exact'} mode — full state IGNORED "
                "(params-only restore: carries, step counters, sync "
                "schedule and comm gauges are NOT imported); rebuild the "
                "trainer with the checkpoint's mode flags for a bit-"
                "identical resume", RuntimeWarning, stacklevel=2)
        elif want_carry is not None and have_carry is None:
            restore_state = False
            warnings.warn(
                f"load_checkpoint({path!r}): PARTIAL STATE — this trainer "
                f"carries {want_carry!r} state the checkpoint (saved by "
                "a carry-free mode) does not record; params-only restore "
                "(the carry re-initializes at the next sync step, the "
                "counters and comm gauges restart), so the resumed "
                "trajectory is NOT bit-identical to the uninterrupted "
                "run", RuntimeWarning, stacklevel=2)
        elif have_carry is not None:
            carry_leaves = file_carry
            live = [np.asarray(x) for x in
                    jax.tree.leaves(getattr(trainer, have_carry))]
            if len(carry_leaves) != len(live):
                raise ValueError(
                    f"checkpoint has {len(carry_leaves)} carry leaves, "
                    f"trainer expects {len(live)} — different sync "
                    "schedule/transport flags than the saving run")
            for have, want in zip(carry_leaves, live):
                if tuple(have.shape) != tuple(want.shape):
                    raise ValueError(
                        f"checkpoint carry leaf shape {have.shape} != "
                        f"trainer {want.shape} — different mode/transport "
                        "flags than the saving run")
    elif _trainer_is_stateful(trainer):
        # pre-full-state file (v1) into a stateful trainer: the loud
        # partial-state contract (module docstring)
        warnings.warn(
            f"load_checkpoint({path!r}): PARTIAL STATE — checkpoint "
            f"format v{meta['version']} records params/opt_state only; "
            "this trainer's carry/controller/step-counter state is NOT "
            "restored (carries re-initialize at the next sync step, the "
            "comm gauges restart at zero).  Re-save with this version for "
            "full-state resume", RuntimeWarning, stacklevel=2)
    treedef = jax.tree.structure((trainer.params, trainer.opt_state))
    params, opt_state = jax.tree.unflatten(treedef, leaves)
    if hasattr(trainer, "load_host_state"):
        trainer.load_host_state(params, opt_state)
    else:
        trainer.params = replicate(trainer.mesh, params)
        trainer.opt_state = replicate(trainer.mesh, opt_state)
    if restore_state:
        trainer.restore_resume_state(state, carry_leaves)
    # expose the restore OUTCOME so callers (the CLI's resume event, run
    # reports) can say whether this was a certified full-state resume or a
    # params-only downgrade — the RuntimeWarnings above are for humans,
    # this flag is for the telemetry stream (obs `resume.partial_state`)
    trainer.last_restore_partial = (not restore_state
                                    and _trainer_is_stateful(trainer))
    return meta["step"]
