"""Communication statistics — the reference's fixed observability vocabulary.

Both reference stacks count, per rank, ``send/recv_comm_volume`` (feature rows
shipped) and ``send/recv_message_count``, then aggregate SUM and MAX across
ranks into one end-of-run line (``Parallel-GCN/main.c:61-64,506-524``;
``GPU/PGCN.py:78-83,230-238``).

Under the static all_to_all plan the per-exchange volume is known exactly at
plan time (it equals the plan's predicted connectivity volume — the invariant
the reference checks empirically), so counters advance deterministically per
step instead of being tallied inside the hot loop.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np


@dataclass
class CommStats:
    k: int
    send_volume_per_exchange: np.ndarray   # (k,) boundary rows per halo exchange
    send_msgs_per_exchange: np.ndarray     # (k,) non-empty peer messages
    recv_volume_per_exchange: np.ndarray   # (k,)
    recv_msgs_per_exchange: np.ndarray     # (k,)
    exchanges: int = 0                     # cumulative halo exchanges performed
    # Subset of ``exchanges`` issued OFF the critical path (the pipelined
    # stale-halo mode: the a2a has no same-step consumer, so its latency is
    # hidden behind local compute).  The volume still crosses the wire —
    # hence one total and a hidden/exposed split, never two totals.
    hidden_exchanges: int = 0
    # Padded-vs-true accounting of the SELECTED exchange schedule
    # (docs/comm_schedule.md): the send/recv volumes above count TRUE
    # boundary rows (Σ(λ−1), what the partitioner minimizes); the schedule
    # ships a statically padded superset — k²·S rows for the dense a2a,
    # Σ_d k·S_d for the ragged ppermute ring.  One true count, one wire
    # count, never a blended number.
    schedule: str = "a2a"
    wire_rows_per_exchange: int = 0        # padded rows on the wire (global
    #                                        over the chips in view)
    padding_efficiency: float = 1.0        # true / wire of the SELECTED
    #                                        schedule
    # Per-layer wire LANE widths (f32-lane equivalents) of one step's
    # exchange sequence — the real table widths the model ships: GCN's
    # project-first ``exchange_widths``, GAT's attention-table lanes (fused
    # fout+1, packed fout/2+1, split pair = fout+1 across its buffers;
    # ``models.gat.gat_exchange_lane_widths``).  With them set, ``report()``
    # carries byte gauges (halo_bytes_true/halo_bytes_wire per step) that
    # must reconcile EXACTLY with the obs roofline's attribution
    # (tests/test_metrics_cli.py, tests/test_gat_ragged.py).  Empty = rows
    # only (pre-PR-5 reports).
    lane_widths: tuple = ()
    # Gradient-direction lanes where they differ from the forward's (the
    # multi-head attention layer ships [Z ‖ t], K·C + K lanes, forward and
    # [g ‖ s, m, 1/D, c], K·C + 4K, backward:
    # ``models.mhgat.mhgat_exchange_lane_widths``).  Empty = the same.
    lane_widths_bwd: tuple = ()
    wire_itemsize: int = 4                 # bytes per f32-equivalent lane,
    #                                        FORWARD (feature) direction
    # Gradient-direction wire itemsize (None = same as wire_itemsize): the
    # halo-delta cache narrows ONLY the feature wire, so a delta run ships
    # bf16 forward and (by default) f32 backward — one blended number would
    # misstate both directions (docs/observability.md, per-step split).
    wire_itemsize_bwd: int | None = None
    # Cumulative byte gauges with PER-STEP itemsize resolution: a delta
    # run's sync steps re-base on an f32 feature wire while its stale steps
    # ship bf16, so the cumulative bytes are accumulated step by step
    # (count_step's wire_itemsize override) rather than derived per_step ×
    # steps.  Zero until lane_widths is set.
    halo_bytes_true_total: int = 0
    halo_bytes_wire_total: int = 0
    # Hot-halo replication (``--replica-budget``, docs/replication.md):
    # replica steps ship the SHRUNKEN no-replica exchange — fewer true rows
    # (replicated rows leave the volume, not just the pad) AND fewer wire
    # rows — while refresh (sync) steps ship the full exchange.  One
    # full-exchange figure, one replica figure, per-step booking; set by
    # ``set_replica`` (None = no replication, every step full).
    replica_send_volume_per_exchange: np.ndarray | None = None  # (k,)
    replica_recv_volume_per_exchange: np.ndarray | None = None  # (k,)
    replica_send_msgs_per_exchange: np.ndarray | None = None    # (k,)
    replica_recv_msgs_per_exchange: np.ndarray | None = None    # (k,)
    replica_wire_rows_per_exchange: int | None = None
    replica_rows: int = 0                 # plan.replica_rows (gauge only)
    replica_exchanges: int = 0            # exchanges that rode the shrunken
    #                                       wire (subset of ``exchanges``)
    # COMPOSED replica × stale booking: replica-booked exchanges that were
    # ALSO latency-hidden (subset of both ``replica_exchanges`` and
    # ``hidden_exchanges``) — the pure replica mode keeps every shrunken
    # exchange synchronous, the composed mode hides all of them, and the
    # exposed/hidden volume split must price each subset at its own
    # per-exchange figure or the hidden + exposed == total contract breaks.
    hidden_replica_exchanges: int = 0
    # Drift-banded PARTIAL refresh (``--refresh-band``,
    # docs/replication.md): the refresh side channel's cumulative booking,
    # at the ACTUAL per-step shipped rows the program reported (these ride
    # ON TOP of the shrunken base exchange the step is replica-booked at;
    # the per-step face is the step event's ``replica.refresh_rows`` — the
    # two must reconcile exactly).
    partial_refresh_steps: int = 0
    partial_refresh_rows_total: int = 0        # true rows, fwd + bwd
    partial_refresh_wire_rows_total: int = 0   # padded side-channel rows

    @classmethod
    def from_plan(cls, plan, schedule: str = "a2a",
                  lane_widths: tuple = (),
                  wire_itemsize: int = 4,
                  wire_itemsize_bwd: int | None = None,
                  lane_widths_bwd: tuple = ()) -> "CommStats":
        off = plan.offwire_send_counts()
        send_vol = plan.predicted_send_volume.astype(np.int64)
        send_msg = plan.predicted_message_count.astype(np.int64)
        if off.shape[0] == off.shape[1]:
            recv_vol, recv_msg = off.sum(axis=0), (off > 0).sum(axis=0)
        else:
            # shard-proxy slice (rows != k): peers' sends are not in view.
            # Per-chip recv == send holds ONLY for a symmetric exchange
            # pattern — for anything else the reuse below would FABRICATE
            # recv counters, so fail loudly instead (round-5 advisor
            # finding).
            if not getattr(plan, "symmetric", False):
                raise ValueError(
                    "CommStats.from_plan: shard-proxy slice of an ASYMMETRIC "
                    "plan — peers' sends are out of view and per-chip recv "
                    "!= send, so recv counters cannot be derived; proxy a "
                    "symmetric plan or build stats from the full plan")
            recv_vol, recv_msg = send_vol, send_msg
        wire = int(plan.wire_rows_per_exchange(schedule))
        true = int(send_vol.sum())
        return cls(
            k=plan.k,
            send_volume_per_exchange=send_vol,
            send_msgs_per_exchange=send_msg,
            recv_volume_per_exchange=recv_vol,
            recv_msgs_per_exchange=recv_msg,
            schedule=schedule,
            wire_rows_per_exchange=wire,
            padding_efficiency=(true / wire if wire else 1.0),
            lane_widths=tuple(int(w) for w in lane_widths),
            lane_widths_bwd=tuple(int(w) for w in lane_widths_bwd),
            wire_itemsize=int(wire_itemsize),
            wire_itemsize_bwd=(None if wire_itemsize_bwd is None
                               else int(wire_itemsize_bwd)),
        )

    def set_replica(self, plan) -> None:
        """Record the shrunken no-replica exchange's figures from a plan
        with the replication layout built (``CommPlan.ensure_replicas``) —
        ``count_step(replica=True)`` then books replica steps at these.
        The replica counts are symmetric-exchange figures like the full
        ones (recv = column sums)."""
        if plan.nrep_send_counts is None:
            raise ValueError(
                "CommStats.set_replica needs the plan's replication layout "
                "(ensure_replicas)")
        counts = plan.nrep_send_counts.astype(np.int64)
        self.replica_send_volume_per_exchange = counts.sum(axis=1)
        self.replica_recv_volume_per_exchange = counts.sum(axis=0)
        self.replica_send_msgs_per_exchange = (counts > 0).sum(axis=1)
        self.replica_recv_msgs_per_exchange = (counts > 0).sum(axis=0)
        self.replica_wire_rows_per_exchange = int(
            plan.wire_rows_per_exchange(self.schedule, replica=True))
        self.replica_rows = int(plan.replica_rows)

    def _accumulate_bytes(self, fwd_sweeps: int, bwd_sweeps: int,
                          fwd_itemsize: int | None = None,
                          replica: bool = False) -> None:
        """Advance the cumulative byte gauges by ``fwd_sweeps`` forward +
        ``bwd_sweeps`` backward exchange SWEEPS (one sweep = one exchange
        per layer, at that layer's lane width — ``lane_widths`` already
        sums over layers), at this step's wire itemsizes (``fwd_itemsize``
        overrides the forward default — the delta-mode sync step's f32
        re-base).  ``replica=True`` books the step at the SHRUNKEN
        no-replica volumes (``set_replica``)."""
        if not self.lane_widths:
            return
        fwd = self.wire_itemsize if fwd_itemsize is None else fwd_itemsize
        bwd = (self.wire_itemsize if self.wire_itemsize_bwd is None
               else self.wire_itemsize_bwd)
        lane = sum(self.lane_widths)
        lane_bwd = sum(self.lane_widths_bwd or self.lane_widths)
        if replica:
            per_true = int(self.replica_send_volume_per_exchange.sum())
            wire = self.replica_wire_rows_per_exchange
        else:
            per_true = int(self.send_volume_per_exchange.sum())
            wire = self.wire_rows_per_exchange
        factor = lane * fwd * fwd_sweeps + lane_bwd * bwd * bwd_sweeps
        self.halo_bytes_true_total += per_true * factor
        self.halo_bytes_wire_total += wire * factor

    def count_step(self, nlayers: int, hidden: bool = False,
                   wire_itemsize: int | None = None,
                   replica: bool = False) -> None:
        """One training step = nlayers forward + nlayers backward exchanges
        (the backward halo exchange mirrors the forward —
        ``Parallel-GCN/main.c:340-372``).  ``hidden=True`` marks the step's
        exchanges as latency-hidden (stale pipelined mode).
        ``wire_itemsize`` overrides this step's FORWARD wire itemsize in
        the cumulative byte gauges (the delta cache's f32 re-base syncs).
        ``replica=True`` books the step's exchanges at the shrunken
        no-replica volumes (``set_replica`` first) — the replica mode's
        non-refresh steps."""
        if replica and self.replica_send_volume_per_exchange is None:
            raise ValueError(
                "count_step(replica=True) before set_replica()")
        self.exchanges += 2 * nlayers
        if hidden:
            self.hidden_exchanges += 2 * nlayers
        if replica:
            self.replica_exchanges += 2 * nlayers
        if hidden and replica:
            # composed replica × stale: the shrunken exchange is ALSO off
            # the critical path — the split volumes price it accordingly
            self.hidden_replica_exchanges += 2 * nlayers
        self._accumulate_bytes(1, 1, fwd_itemsize=wire_itemsize,
                               replica=replica)

    def count_partial_refresh_step(self, nlayers: int, refresh_rows,
                                   wire_rows: int) -> None:
        """One ``--refresh-band`` PARTIAL refresh step: the shrunken
        replica-step exchange (booked exactly like
        ``count_step(replica=True)``) plus the replica-only side channel —
        one extra a2a per layer per direction shipping ``wire_rows``
        padded rows, of which ``refresh_rows[ℓ]`` (the per-layer count the
        program measured and reported) actually carried a drifted row.
        The gradient side channel ships the same masked rows plus a 0/1
        indicator lane (one extra f32-equivalent lane in the byte gauge).
        """
        refresh_rows = [int(x) for x in refresh_rows]
        if len(refresh_rows) != nlayers:
            raise ValueError(
                f"count_partial_refresh_step: {len(refresh_rows)} per-layer "
                f"row counts for {nlayers} layers")
        self.count_step(nlayers=nlayers, replica=True)
        self.partial_refresh_steps += 1
        self.partial_refresh_rows_total += 2 * sum(refresh_rows)
        self.partial_refresh_wire_rows_total += 2 * nlayers * int(wire_rows)
        if self.lane_widths:
            fwd = self.wire_itemsize
            bwd = (self.wire_itemsize if self.wire_itemsize_bwd is None
                   else self.wire_itemsize_bwd)
            for rows, lane in zip(refresh_rows, self.lane_widths):
                self.halo_bytes_true_total += rows * lane * (fwd + bwd)
                self.halo_bytes_wire_total += int(wire_rows) * (
                    lane * fwd + (lane + 1) * bwd)

    def count_forward(self, nlayers: int) -> None:
        self.exchanges += nlayers
        self._accumulate_bytes(1, 0)

    # ----------------------------------------------------- checkpoint state
    # the CUMULATIVE counters a resume must carry over so the end-of-run
    # comm report of a resumed run reconciles exactly with the
    # uninterrupted one (docs/resilience.md).  Per-exchange figures are NOT
    # here: they are plan-derived and rebuilt by from_plan on every start.
    _CUMULATIVE_ATTRS = (
        "exchanges", "hidden_exchanges", "replica_exchanges",
        "hidden_replica_exchanges", "halo_bytes_true_total",
        "halo_bytes_wire_total", "partial_refresh_steps",
        "partial_refresh_rows_total", "partial_refresh_wire_rows_total")

    def state(self) -> dict:
        """JSON-able snapshot of the cumulative gauges."""
        return {a: int(getattr(self, a)) for a in self._CUMULATIVE_ATTRS}

    def load_state(self, state: dict) -> None:
        """Restore ``state()`` onto a freshly-built counter (``from_plan``
        + ``set_replica`` already re-derived the per-exchange figures)."""
        for a in self._CUMULATIVE_ATTRS:
            if a in state:
                setattr(self, a, int(state[a]))

    def cumulative(self) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
        """Per-rank cumulative (send_vol, send_msgs, recv_vol, recv_msgs).
        Replica-booked exchanges (``count_step(replica=True)``) advance at
        the shrunken per-exchange volumes — replicated rows genuinely left
        the exchange, so the reference's 8-number line must not claim
        them."""
        per = (self.send_volume_per_exchange, self.send_msgs_per_exchange,
               self.recv_volume_per_exchange, self.recv_msgs_per_exchange)
        if not self.replica_exchanges:
            return tuple(p * self.exchanges for p in per)
        rep = (self.replica_send_volume_per_exchange,
               self.replica_send_msgs_per_exchange,
               self.replica_recv_volume_per_exchange,
               self.replica_recv_msgs_per_exchange)
        full = self.exchanges - self.replica_exchanges
        return tuple(p * full + rp * self.replica_exchanges
                     for p, rp in zip(per, rep))

    @staticmethod
    def report_from_cumulative(sv, sm, rv, rm) -> dict:
        # the reference's 8-number line: SUM and MAX over ranks of each counter
        return {
            "total_send_volume": int(sv.sum()),
            "max_send_volume": int(sv.max()) if sv.size else 0,
            "total_send_msgs": int(sm.sum()),
            "max_send_msgs": int(sm.max()) if sm.size else 0,
            "total_recv_volume": int(rv.sum()),
            "max_recv_volume": int(rv.max()) if rv.size else 0,
            "total_recv_msgs": int(rm.sum()),
            "max_recv_msgs": int(rm.max()) if rm.size else 0,
        }

    def report(self) -> dict:
        """The reference's 8-number line plus the exposed/hidden split:
        exchanges whose latency sits ON the step's critical path (exposed —
        every exact-mode exchange) vs exchanges issued with no same-step
        consumer (hidden — the stale pipelined mode's), with the wire volume
        attributed to each.  Total keys keep their reference meaning (all
        bytes cross the wire either way)."""
        rep = self.report_from_cumulative(*self.cumulative())
        exposed = self.exchanges - self.hidden_exchanges
        hidden = self.hidden_exchanges
        per_ex = int(self.send_volume_per_exchange.sum())
        rex = self.replica_exchanges
        hrex = self.hidden_replica_exchanges   # composed replica × stale
        erex = rex - hrex                      # exposed replica-booked
        per_ex_rep = (int(self.replica_send_volume_per_exchange.sum())
                      if rex else per_ex)
        rep_wire = (self.replica_wire_rows_per_exchange
                    if rex else self.wire_rows_per_exchange)
        wire = self.wire_rows_per_exchange
        # the --refresh-band side channel's padded rows ride on (exposed)
        # refresh steps — they join every wire total below
        pwire = self.partial_refresh_wire_rows_total
        rep.update(
            exchanges=self.exchanges,
            exposed_exchanges=exposed,
            hidden_exchanges=hidden,
            # each (exposed/hidden) × (full/replica-booked) subset prices
            # at its own per-exchange volume, so hidden + exposed == total
            # holds in every mode (pure replica: all shrunken exchanges
            # exposed; composed replica × stale: all of them hidden)
            exposed_send_volume=(per_ex * (exposed - erex)
                                 + per_ex_rep * erex),
            hidden_send_volume=(per_ex * (hidden - hrex)
                                + per_ex_rep * hrex),
            # per-schedule padded-vs-true accounting: true rows are what the
            # partitioner optimizes, wire rows what the schedule ships; the
            # obs roofline must agree with these EXACTLY
            # (tests/test_metrics_cli.py)
            comm_schedule=self.schedule,
            true_rows_per_exchange=per_ex,
            wire_rows_per_exchange=wire,
            wire_rows_total=(wire * (self.exchanges - rex)
                             + rep_wire * rex + pwire),
            # the exposed/hidden WIRE-row split — the controller A/B's
            # acceptance figure (exposed wire rows/step, never epoch time)
            exposed_wire_rows_total=(wire * (exposed - erex)
                                     + rep_wire * erex + pwire),
            hidden_wire_rows_total=(wire * (hidden - hrex)
                                    + rep_wire * hrex),
            padding_efficiency=self.padding_efficiency,
        )
        if self.replica_wire_rows_per_exchange is not None:
            # hot-halo replication gauges (docs/replication.md): the
            # shrunken exchange's figures next to the full ones, plus how
            # many exchanges rode it
            rep.update(
                replica_exchanges=rex,
                hidden_replica_exchanges=hrex,
                replica_rows=self.replica_rows,
                true_rows_per_exchange_replica=int(
                    self.replica_send_volume_per_exchange.sum()),
                wire_rows_per_exchange_replica=
                self.replica_wire_rows_per_exchange,
            )
        if self.partial_refresh_steps:
            # partial-refresh booking at the ACTUAL shipped rows — the
            # cumulative face of the step events' replica.refresh_rows
            rep.update(
                partial_refresh_steps=self.partial_refresh_steps,
                partial_refresh_rows_total=self.partial_refresh_rows_total,
                partial_refresh_wire_rows_total=
                self.partial_refresh_wire_rows_total,
            )
        if self.lane_widths:
            # lane-weighted byte gauges: one fwd + one bwd exchange per
            # layer per step, each at that layer's true wire width and its
            # DIRECTION's itemsize — the CommStats side of the attribution
            # reconciliation contract.  The *_per_step keys describe the
            # steady-state (stale/default) step; the *_total keys are
            # cumulative with per-step itemsize resolution (delta-mode sync
            # steps book their f32 re-base wire at 4 bytes).
            bwd = (self.wire_itemsize if self.wire_itemsize_bwd is None
                   else self.wire_itemsize_bwd)
            lane_b = (sum(self.lane_widths) * self.wire_itemsize
                      + sum(self.lane_widths_bwd or self.lane_widths) * bwd)
            rep.update(
                halo_bytes_true_per_step=per_ex * lane_b,
                halo_bytes_wire_per_step=self.wire_rows_per_exchange
                * lane_b,
                halo_bytes_true_total=self.halo_bytes_true_total,
                halo_bytes_wire_total=self.halo_bytes_wire_total,
            )
        return rep

    @staticmethod
    def merged_report(stats_list) -> dict:
        """Aggregate many counters (e.g. one per mini-batch plan) the way one
        rank accumulates across batches in the reference: per-rank sums first,
        SUM/MAX over ranks second (``GPU/PGCN-Mini-batch.py`` shares the
        counter dict across batches; ``Parallel-GCN/main.c:506-524``).

        Carries the hidden/exposed split through the merge (each counter's
        per-exchange volume is its OWN plan's, so the split volumes sum per
        counter, never from the merged totals) — the merged report satisfies
        the same ``hidden + exposed == total`` reconciliation contract as a
        single ``report()`` (``sgcn_tpu.obs.schema.COMM_SPLIT_KEYS``)."""
        parts = [s.cumulative() for s in stats_list]
        sums = [np.sum([p[i] for p in parts], axis=0) for i in range(4)]
        rep = CommStats.report_from_cumulative(*sums)
        exchanges = sum(s.exchanges for s in stats_list)
        hidden = sum(s.hidden_exchanges for s in stats_list)
        schedules = {s.schedule for s in stats_list} or {"a2a"}
        wire_total = sum(
            s.wire_rows_per_exchange * (s.exchanges - s.replica_exchanges)
            + (s.replica_wire_rows_per_exchange or 0) * s.replica_exchanges
            + s.partial_refresh_wire_rows_total
            for s in stats_list)

        def _split_vol(s, hidden_side: bool) -> int:
            # same subset pricing as a single report(): (exposed/hidden) ×
            # (full/replica-booked), each at its own per-exchange volume —
            # the composed replica × stale mode hides shrunken exchanges,
            # so the old "replica implies exposed" shortcut would misprice
            # exactly the mode this split exists to describe
            per = int(s.send_volume_per_exchange.sum())
            per_rep = (int(s.replica_send_volume_per_exchange.sum())
                       if s.replica_exchanges else per)
            hrex = s.hidden_replica_exchanges
            if hidden_side:
                return (per * (s.hidden_exchanges - hrex) + per_rep * hrex)
            erex = s.replica_exchanges - hrex
            exp = s.exchanges - s.hidden_exchanges
            return per * (exp - erex) + per_rep * erex

        rep.update(
            exchanges=exchanges,
            exposed_exchanges=exchanges - hidden,
            hidden_exchanges=hidden,
            exposed_send_volume=sum(_split_vol(s, False)
                                    for s in stats_list),
            hidden_send_volume=sum(_split_vol(s, True)
                                   for s in stats_list),
            # cross-counter wire accounting: each counter's wire rows are
            # its OWN plan's (per-batch envelopes differ), so totals sum per
            # counter; efficiency is the cumulative true/wire ratio
            comm_schedule=(schedules.pop() if len(schedules) == 1
                           else "mixed"),
            wire_rows_total=wire_total,
            padding_efficiency=(rep["total_send_volume"] / wire_total
                                if wire_total else 1.0),
        )
        if any(s.lane_widths for s in stats_list):
            # cumulative byte gauges sum per counter (each counter's lane
            # widths and per-step itemsizes are its own plan's/config's)
            rep.update(
                halo_bytes_true_total=sum(
                    s.halo_bytes_true_total for s in stats_list),
                halo_bytes_wire_total=sum(
                    s.halo_bytes_wire_total for s in stats_list),
            )
        return rep
