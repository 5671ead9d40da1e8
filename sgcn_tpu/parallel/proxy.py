"""Single-chip shard proxy: run ONE chip's share of a k-way plan on one device.

Purpose: the north-star config is an 8-chip ogbn-products epoch, more
chips than the chip tool's one host offers (one chip, or four).  Every
per-chip array in a ``CommPlan`` is padded to identical shapes across chips
(``pad_comm_plan``), so chip ``c``'s per-device program — send-side gather,
halo gather, bucketed local SpMM, dense matmuls, loss, backward, Adam — is
the SAME compiled program on every chip; only gather index *contents* differ.
Measuring that program on the real chip therefore measures the compute half
of the k-chip epoch directly; the collectives (halo ``all_to_all``, grad
``psum``) are the only parts a single device cannot time (the four-chip
benchmark cell does; at the deployment's k = 8 they are not measured).

Mechanism: ``dataclasses.replace`` the plan with ``k=1`` and every stacked
``(k, ...)`` array sliced to ``[chip:chip+1]``, then train normally on a
1-device mesh.  The mesh axis still exists, so the per-chip code is
UNCHANGED: ``all_to_all``/``psum`` over a size-1 axis are identities (the
halo buffer still materializes — ``ops.pspmm.halo_exchange`` pins it with an
``optimization_barrier`` on size-1 axes), and the halo table the proxy
gathers from has the real halo's shape; its *contents* are the chip's own
sent rows instead of its peers' rows, which changes no shape, no gather
count, and no flop — only the numerical values flowing through the (value-
independent-cost) program.

The reference has no analogue: its per-rank cost is only observable on a
full MPI/NCCL job (``Parallel-GCN/main.c:441-445`` times MAX over live
ranks).  Here the padded-uniform plan makes one rank's program a faithful
stand-in, MAX over ranks included (all ranks run the same-shape program).
"""

from __future__ import annotations

import dataclasses

import numpy as np

from .plan import _GLOBAL_ARRAY_FIELDS, PER_CHIP_ARRAY_FIELDS, CommPlan


def shard_proxy_plan(plan: CommPlan, chip: int = 0) -> CommPlan:
    """A ``k=1`` view of ``plan`` carrying only chip ``chip``'s arrays.

    Slicing is driven by the plan's EXPLICIT per-chip field classification
    (``plan.PER_CHIP_ARRAY_FIELDS``): each listed field is verified to carry
    the stacked leading ``k`` axis and sliced to ``[chip:chip+1]``;
    global-vertex arrays (``owner``, ``local_idx``) and scalars pass
    through.  Any UNclassified dataclass field that happens to look
    per-chip-stacked fails loudly instead of being silently sliced (or
    silently passed through whole) — the old ``shape[0] == plan.k``
    inference mis-slices exactly those cases (round-5 advisor finding).

    The result trains on a 1-device mesh with the chip's exact padded
    shapes: ``send_idx`` stays ``(1, k, S)`` (per-chip view ``(k, S)``), so
    the send buffer and the ``(k*S, f)`` receive window are full-size.
    """
    if not 0 <= chip < plan.k:
        raise ValueError(f"chip {chip} out of range for k={plan.k}")
    # record the true chip identity: sliced send_counts row 0 self-sends at
    # column `chip`, which the comm-stat properties must zero (not [0, 0])
    repl: dict = {"k": 1, "chip_ids": np.array([chip])}
    for name in PER_CHIP_ARRAY_FIELDS:
        v = getattr(plan, name)
        if v is None:              # lazy layout (cell/pallas) not built
            continue
        if not (isinstance(v, np.ndarray) and v.ndim >= 1
                and v.shape[0] == plan.k):
            raise ValueError(
                f"CommPlan.{name} is classified per-chip-stacked but has "
                f"shape {getattr(v, 'shape', None)} (k={plan.k}) — "
                "PER_CHIP_ARRAY_FIELDS is out of sync with the dataclass")
        repl[name] = v[chip: chip + 1]
    for fld in dataclasses.fields(plan):
        if fld.name in PER_CHIP_ARRAY_FIELDS or fld.name in _GLOBAL_ARRAY_FIELDS:
            continue
        v = getattr(plan, fld.name)
        if isinstance(v, np.ndarray) and v.ndim >= 1 and v.shape[0] == plan.k:
            raise ValueError(
                f"CommPlan.{fld.name} looks per-chip-stacked (leading axis "
                f"{plan.k}) but is not classified in PER_CHIP_ARRAY_FIELDS — "
                "add it there (sliced) or to _GLOBAL_ARRAY_FIELDS "
                "(passed through) before proxying")
    return dataclasses.replace(plan, **repl)


def shard_proxy_data(plan: CommPlan, chip: int, features: np.ndarray,
                     labels: np.ndarray):
    """Chip ``chip``'s ``TrainData`` block under the ORIGINAL k-way plan.

    Built with ``plan.scatter_rows(..., chips=[chip])`` so only the chip's
    owned rows are materialized (the multi-host placement path).
    """
    from ..train.fullbatch import TrainData

    n = plan.n
    h0 = plan.scatter_rows(features.astype(np.float32), chips=[chip])
    lab = plan.scatter_rows(
        labels.reshape(n, 1).astype(np.int32), chips=[chip])[..., 0]
    rv = plan.row_valid[chip: chip + 1]
    return TrainData(h0=h0, labels=lab, train_valid=rv, eval_valid=rv)
