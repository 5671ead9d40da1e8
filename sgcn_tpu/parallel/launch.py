"""Multi-host launch: process bootstrap + global mesh over ICI/DCN.

Reference equivalents: the SLURM rendezvous plumbing — ``MASTER_ADDR`` /
``MASTER_PORT`` derived from the job id and nodelist, ``WORLD_SIZE`` =
nodes × tasks (``GPU/pytorch.3node.slurm:46-56``), consumed by
``dist.init_process_group`` via ``SLURM_NPROCS``/``SLURM_PROCID``
(``GPU/PGCN.py:241-260``).

TPU-native shape: one Python process per host, ``jax.distributed.initialize``
for the rendezvous (it auto-detects on Cloud TPU pods; SLURM env vars are the
fallback), and a single global 1D vertex mesh over ALL chips of all hosts.
Collectives between co-located chips ride ICI; cross-host hops ride DCN —
the same topology split as the reference's NCCL intra/inter-node rings, but
chosen by XLA's collective scheduler rather than hand-written P2P.

Every sgcn_tpu trainer takes an explicit ``mesh``; launching multi-host is
therefore just::

    ctx = init_distributed()                  # once per process, before use
    mesh = global_mesh_1d()                   # k = total chips in the job
    trainer = FullBatchTrainer(plan, fin, widths, mesh=mesh)
    data = make_train_data_multihost(plan, mesh, features, labels)

``make_train_data_multihost`` builds blocks only for this process's chips
and assembles global arrays via ``jax.make_array_from_process_local_data``
— the supported multi-process placement path (a plain ``device_put`` of
host-local arrays to a global sharding is NOT, and the plan-array /
parameter placement in ``parallel.mesh`` takes the same route when
``jax.process_count() > 1``).  Exercised end-to-end by the 2-process × 4
virtual-device integration test (``tests/test_multihost.py``).  See
``launch/tpu.slurm`` for the batch-script equivalent of the reference's
``pytorch.3node.slurm``.
"""

from __future__ import annotations

import os
import time
from dataclasses import dataclass

import jax

from .mesh import AXIS, make_mesh_1d

# rendezvous robustness (docs/resilience.md): how long ONE initialize
# attempt may wait for all peers before it is declared stalled, and the
# backoff before the single retry.  A transiently late peer (a host still
# booting, a container being rescheduled) is routine on preemptible pods —
# one retry absorbs it; a peer that misses BOTH attempts is genuinely gone
# and the clear error beats an unbounded hang.
RENDEZVOUS_TIMEOUT_S = 300.0
RENDEZVOUS_BACKOFF_S = 5.0


def _initialize_with_retry(heartbeat, detail: str, **kwargs) -> None:
    """``jax.distributed.initialize`` under an explicit stalled-peer
    timeout with ONE retry + backoff.  Heartbeats mark every transition
    (start/stalled/retry/done/failed), so an operator watching the run
    directory sees WHICH attempt is in flight."""
    import inspect

    timeout = float(os.environ.get("SGCN_RENDEZVOUS_TIMEOUT",
                                   str(RENDEZVOUS_TIMEOUT_S)))
    backoff = float(os.environ.get("SGCN_RENDEZVOUS_BACKOFF",
                                   str(RENDEZVOUS_BACKOFF_S)))
    try:
        params = inspect.signature(jax.distributed.initialize).parameters
        if "initialization_timeout" in params:
            kwargs["initialization_timeout"] = int(timeout)
    except (TypeError, ValueError):
        pass                    # older jax: no per-attempt timeout knob
    for attempt in (1, 2):
        heartbeat("rendezvous:start", phase="init_distributed",
                  detail=f"attempt {attempt}/2, {detail}, "
                         f"timeout {timeout:.0f}s")
        try:
            jax.distributed.initialize(**kwargs)
            heartbeat("rendezvous:done", phase="init_distributed",
                      detail=f"attempt {attempt}/2")
            return
        except Exception as e:           # noqa: BLE001 — classified below
            # classify before diagnosing: only a timeout-shaped failure is
            # evidence of a STALLED peer — blaming a dead peer for a bad
            # coordinator address / bound port / auth error sends the
            # operator hunting in exactly the wrong place
            text = str(e).lower()
            stall_like = any(t in text for t in
                             ("timed out", "timeout", "deadline",
                              "unavailable"))
            if attempt == 2:
                heartbeat("rendezvous:failed", phase="init_distributed",
                          detail=str(e)[-200:])
                cause = (
                    f"a peer stalled past the {timeout:.0f}s timeout on "
                    "both attempts, or the coordinator is unreachable — "
                    "check that every host in the job is up and can reach "
                    f"{kwargs.get('coordinator_address') or 'the pod'} "
                    "($SGCN_RENDEZVOUS_TIMEOUT / _BACKOFF tune the "
                    "attempt budget)" if stall_like else
                    "NOT a timeout — likely local configuration (bad "
                    "coordinator address, port already bound, auth)")
                raise RuntimeError(
                    f"rendezvous failed twice ({detail}): {cause}; "
                    f"underlying error: {e}") from e
            heartbeat("rendezvous:stalled" if stall_like
                      else "rendezvous:error",
                      phase="init_distributed",
                      detail=f"attempt 1 failed ({str(e)[-120:]}); "
                             f"retrying in {backoff:.0f}s")
            # a timed-out initialize leaves the distributed client SET
            # (jax assigns global_state.client before connect()), and a
            # second initialize then refuses with "should only be called
            # once" — shut the half-initialized state down or the retry
            # can never actually re-attempt the rendezvous
            try:
                jax.distributed.shutdown()
            except Exception:           # noqa: BLE001 — nothing to shut down
                pass
            time.sleep(backoff)


@dataclass
class DistributedContext:
    process_id: int
    num_processes: int
    coordinator: str | None
    local_devices: int
    global_devices: int

    @property
    def is_coordinator(self) -> bool:
        """Rank-0 check — all end-of-run printing is rank-0-only in the
        reference (``GPU/PGCN.py:230-238``)."""
        return self.process_id == 0


def slurm_rendezvous_env() -> tuple[str, int, int] | None:
    """Derive (coordinator, num_processes, process_id) from SLURM variables,
    mirroring the reference's launcher arithmetic
    (``GPU/pytorch.3node.slurm:46-56``: port = 10000 + last 4 digits of the
    job id; master = first node of the nodelist — here the caller passes the
    resolved hostname via ``SGCN_COORDINATOR`` or ``MASTER_ADDR``)."""
    nprocs = os.environ.get("SLURM_NPROCS")
    procid = os.environ.get("SLURM_PROCID")
    if nprocs is None or procid is None:
        return None
    addr = (os.environ.get("SGCN_COORDINATOR")
            or os.environ.get("MASTER_ADDR"))
    if addr is None:
        return None
    port = os.environ.get("MASTER_PORT")
    if port is None:
        # array/het job ids like "1234_5" contain non-digits; keep the
        # digits so the port stays derivable instead of crashing startup
        jobid = "".join(c for c in os.environ.get("SLURM_JOBID", "0")
                        if c.isdigit())
        port = str(10000 + int(jobid[-4:] or "0"))
    return f"{addr}:{port}", int(nprocs), int(procid)


def init_distributed(coordinator: str | None = None,
                     num_processes: int | None = None,
                     process_id: int | None = None) -> DistributedContext:
    """Bootstrap multi-process JAX.  Single-process (the common dev case and
    the one-chip bench) is a no-op that still returns a valid context.

    Resolution order: explicit args → Cloud TPU autodetection (no env needed)
    → SLURM env (reference-style cluster).
    """
    from ..obs.recorder import heartbeat   # no-op unless SGCN_METRICS_OUT

    if num_processes is None:
        env = slurm_rendezvous_env()
        if env is not None:
            coordinator, num_processes, process_id = env
    if num_processes is not None and num_processes > 1:
        # heartbeats bracket the rendezvous: a pod whose coordinator never
        # comes up looks IDENTICAL to a slow compile from the driver's seat
        # — the last heartbeat's phase tells them apart
        # (docs/observability.md); a stalled peer times out per attempt
        # and gets ONE retry + backoff before the clear failure
        _initialize_with_retry(
            heartbeat, f"{num_processes} processes @ {coordinator}",
            coordinator_address=coordinator,
            num_processes=num_processes,
            process_id=process_id,
        )
    elif num_processes is None:
        # Cloud TPU pod: fully autodetected — only when there genuinely are
        # multiple workers (single-worker boxes also set TPU_WORKER_HOSTNAMES)
        hosts = [h for h in os.environ.get(
            "TPU_WORKER_HOSTNAMES", "").split(",") if h]
        if len(hosts) > 1:
            _initialize_with_retry(
                heartbeat, f"TPU pod autodetect, {len(hosts)} hosts")
    return DistributedContext(
        process_id=jax.process_index(),
        num_processes=jax.process_count(),
        coordinator=coordinator,
        local_devices=jax.local_device_count(),
        global_devices=jax.device_count(),
    )


def global_mesh_1d(k: int | None = None):
    """1D vertex mesh over every chip in the job (all hosts).

    Device order follows ``jax.devices()`` — co-located chips are adjacent,
    so neighboring parts land on ICI-connected chips and only part-boundary
    traffic that crosses hosts rides DCN.
    """
    devs = jax.devices()
    return make_mesh_1d(k if k is not None else len(devs), devices=devs)


__all__ = ["DistributedContext", "init_distributed", "global_mesh_1d",
           "slurm_rendezvous_env", "AXIS"]
