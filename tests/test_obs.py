"""Unit tests for the observability vocabulary (SURVEY.md §5.1/§5.5):
CommStats' 8-number SUM/MAX report and its conservation invariants, and the
PhaseTimer phase breakdown (the CAGNET baseline's
data_comm/local_spmm/... accounting, Cagnet/main.c:35-38,395-413).

The deeper invariant — measured trainer volume == partitioner-predicted
connectivity — is covered end-to-end in test_minibatch/test_cli; these pin
the counter algebra itself.
"""

import numpy as np

from sgcn_tpu.io.datasets import er_graph
from sgcn_tpu.parallel import build_comm_plan
from sgcn_tpu.partition import balanced_random_partition
from sgcn_tpu.prep import normalize_adjacency
from sgcn_tpu.utils.stats import CommStats
from sgcn_tpu.utils.timers import PhaseTimer


def _plan(n=200, k=4, seed=0):
    ahat = normalize_adjacency(er_graph(n, 6, seed))
    pv = balanced_random_partition(n, k, seed=seed + 1)
    return build_comm_plan(ahat, pv, k)


def test_commstats_conservation_and_report():
    plan = _plan()
    st = CommStats.from_plan(plan)
    # every row some rank sends, exactly one rank receives (and vice versa):
    # global send volume == global recv volume, same for message counts
    assert st.send_volume_per_exchange.sum() == st.recv_volume_per_exchange.sum()
    assert st.send_msgs_per_exchange.sum() == st.recv_msgs_per_exchange.sum()

    st.count_step(nlayers=3)       # 3 fwd + 3 bwd exchanges
    st.count_forward(nlayers=2)    # inference adds fwd-only exchanges
    st.count_step(nlayers=3, hidden=True)   # a pipelined (stale) step
    assert st.exchanges == 14
    assert st.hidden_exchanges == 6
    rep = st.report()
    per_ex = int(st.send_volume_per_exchange.sum())
    assert rep["total_send_volume"] == 14 * per_ex
    assert rep["total_recv_volume"] == rep["total_send_volume"]
    assert rep["max_send_volume"] == 14 * int(st.send_volume_per_exchange.max())
    # hidden/exposed split: totals keep the reference meaning (all bytes
    # cross the wire); the split attributes them to the critical path or not
    assert rep["exposed_exchanges"] == 8
    assert rep["hidden_exchanges"] == 6
    assert rep["exposed_send_volume"] == 8 * per_ex
    assert rep["hidden_send_volume"] == 6 * per_ex
    assert set(rep) == {
        "total_send_volume", "max_send_volume", "total_send_msgs",
        "max_send_msgs", "total_recv_volume", "max_recv_volume",
        "total_recv_msgs", "max_recv_msgs", "exchanges",
        "exposed_exchanges", "hidden_exchanges", "exposed_send_volume",
        "hidden_send_volume",
        # the padded-vs-true wire split of the selected exchange schedule
        # (docs/comm_schedule.md), including the exposed/hidden wire-row
        # split the controller A/B judges on (PR-12)
        "comm_schedule", "true_rows_per_exchange", "wire_rows_per_exchange",
        "wire_rows_total", "exposed_wire_rows_total",
        "hidden_wire_rows_total", "padding_efficiency"}
    # wire accounting defaults to the dense a2a schedule and reconciles
    assert rep["comm_schedule"] == "a2a"
    assert rep["true_rows_per_exchange"] == per_ex
    assert rep["wire_rows_per_exchange"] >= per_ex
    assert rep["wire_rows_total"] == 14 * rep["wire_rows_per_exchange"]


def test_commstats_merged_report_matches_manual_sum():
    """merged_report = per-rank sums across batch plans first, SUM/MAX over
    ranks second (the reference shares one counter dict across batches)."""
    plans = [_plan(seed=s) for s in (0, 1)]
    stats = [CommStats.from_plan(p) for p in plans]
    stats[0].count_step(nlayers=2)
    stats[1].count_step(nlayers=2)
    stats[1].count_step(nlayers=2)
    merged = CommStats.merged_report(stats)
    sv = (stats[0].send_volume_per_exchange * stats[0].exchanges
          + stats[1].send_volume_per_exchange * stats[1].exchanges)
    assert merged["total_send_volume"] == int(sv.sum())
    assert merged["max_send_volume"] == int(sv.max())


def test_phase_timer_breakdown():
    t = PhaseTimer()
    for _ in range(3):
        with t.phase("data_comm"):
            pass
    with t.phase("local_spmm", sync=lambda: np.zeros(1)):
        pass
    rep = t.report()
    assert rep["data_comm"]["count"] == 3
    assert rep["local_spmm"]["count"] == 1
    assert rep["local_spmm"]["total_s"] >= 0
    np.testing.assert_allclose(
        rep["data_comm"]["avg_s"], rep["data_comm"]["total_s"] / 3)


def test_merged_report_mixed_hidden_exposed_multichip():
    """merged_report over a MIXED stats list — one counter trained stale
    (hidden exchanges), one exact, one inference-only — must carry the
    hidden/exposed split through the merge with each counter's OWN
    per-exchange volume, and still reconcile (hidden + exposed == total)."""
    plans = [_plan(seed=s) for s in (0, 1, 2)]
    stats = [CommStats.from_plan(p) for p in plans]
    stats[0].count_step(nlayers=2, hidden=True)      # pipelined steps
    stats[0].count_step(nlayers=2, hidden=True)
    stats[0].count_step(nlayers=2)                   # one full-sync step
    stats[1].count_step(nlayers=2)                   # exact-mode trainer
    stats[2].count_forward(nlayers=2)                # inference only
    merged = CommStats.merged_report(stats)

    assert merged["exchanges"] == 12 + 4 + 2
    assert merged["hidden_exchanges"] == 8
    assert merged["exposed_exchanges"] == merged["exchanges"] - 8
    # volumes: each counter's split uses ITS plan's per-exchange volume
    per = [int(s.send_volume_per_exchange.sum()) for s in stats]
    assert merged["hidden_send_volume"] == 8 * per[0]
    assert merged["exposed_send_volume"] == (4 * per[0] + 4 * per[1]
                                             + 2 * per[2])
    assert (merged["hidden_send_volume"] + merged["exposed_send_volume"]
            == merged["total_send_volume"])
    # the 8-number half still matches the manual per-rank sum
    sv = sum(s.send_volume_per_exchange * s.exchanges for s in stats)
    assert merged["total_send_volume"] == int(sv.sum())
    assert merged["max_send_volume"] == int(sv.max())


def test_shard_proxy_asymmetric_plan_raises():
    """The asymmetric-plan shard-proxy path: CommStats.from_plan on a proxy
    slice must REFUSE to fabricate recv counters (per-chip recv == send only
    holds for a symmetric exchange pattern) — previously only the happy
    path was pinned."""
    import pytest
    import scipy.sparse as sp

    from sgcn_tpu.parallel.proxy import shard_proxy_plan

    # a genuinely asymmetric adjacency (directed edges)
    rng = np.random.default_rng(3)
    dense = (rng.random((60, 60)) < 0.1).astype(np.float32)
    np.fill_diagonal(dense, 0)
    a = sp.csr_matrix(dense)
    pv = balanced_random_partition(60, 4, seed=5)
    plan = build_comm_plan(a, pv, 4)
    assert not plan.symmetric

    proxy = shard_proxy_plan(plan, chip=1)
    with pytest.raises(ValueError, match="ASYMMETRIC"):
        CommStats.from_plan(proxy)

    # the symmetric proxy stays the happy path (recv derived from send)
    splan = _plan(n=60, k=4, seed=9)
    st = CommStats.from_plan(shard_proxy_plan(splan, chip=2))
    assert st.k == 1
    assert (st.recv_volume_per_exchange == st.send_volume_per_exchange).all()


# ---------------------------------------------------------------------------
# run-telemetry subsystem (sgcn_tpu.obs): schema, recorder, attribution
# ---------------------------------------------------------------------------

def test_schema_validates_and_rejects():
    import pytest

    from sgcn_tpu.obs import SCHEMA_VERSION, validate_event

    ok = {"v": SCHEMA_VERSION, "ts": 1.0, "kind": "step", "step": 3,
          "loss": 0.5, "wall_s": 0.01,
          "comm": {"exchanges": 4, "exposed_exchanges": 2,
                   "hidden_exchanges": 2, "exposed_send_volume": 10,
                   "hidden_send_volume": 10, "total_send_volume": 20}}
    validate_event(ok)
    with pytest.raises(ValueError, match="kind"):
        validate_event({"v": SCHEMA_VERSION, "ts": 1.0, "kind": "nope"})
    with pytest.raises(ValueError, match="version"):
        validate_event({**ok, "v": 999})
    with pytest.raises(ValueError, match="missing required"):
        validate_event({"v": SCHEMA_VERSION, "ts": 1.0, "kind": "step",
                        "step": 1})
    with pytest.raises(ValueError, match="non-finite"):
        validate_event({**ok, "wall_s": float("nan")})
    # the split reconciliation is part of the schema itself
    bad = dict(ok, comm=dict(ok["comm"], hidden_exchanges=3))
    with pytest.raises(ValueError, match="hidden/exposed"):
        validate_event(bad)


def test_schema_v4_checkpoint_resume_events():
    """PR-13 resilience kinds: checkpoint/resume validate under v4, are
    rejected for older stream versions (a v3 stream must not carry them),
    and the serve ``shed`` key is typed + non-negative when present."""
    import pytest

    from sgcn_tpu.obs import SCHEMA_VERSION, validate_event

    ck = {"v": SCHEMA_VERSION, "ts": 1.0, "kind": "checkpoint", "step": 4,
          "path": "/runs/ckpt_00000004.npz", "bytes": 1234, "wall_s": 0.1}
    validate_event(ck)
    rs = {"v": SCHEMA_VERSION, "ts": 1.0, "kind": "resume", "step": 2,
          "path": "/runs/ckpt_00000002.npz", "fallback": True,
          "skipped": ["/runs/ckpt_00000004.npz"]}
    validate_event(rs)
    with pytest.raises(ValueError, match="kind"):
        validate_event({**ck, "v": 3})      # v3 stream may not carry v4 kind
    with pytest.raises(ValueError, match="missing required"):
        validate_event({"v": SCHEMA_VERSION, "ts": 1.0, "kind": "checkpoint",
                        "step": 4})
    with pytest.raises(ValueError, match="negative"):
        validate_event({**ck, "bytes": -1})
    sv = {"v": SCHEMA_VERSION, "ts": 1.0, "kind": "serve", "queries": 10,
          "achieved_qps": 5.0, "latency_p50_ms": 1.0, "latency_p95_ms": 2.0,
          "latency_p99_ms": 3.0, "shed": 2, "shed_factor": 2.0}
    validate_event(sv)
    with pytest.raises(ValueError, match="shed"):
        validate_event({**sv, "shed": -1})


def test_recorder_checkpoint_resume_roundtrip(tmp_path):
    from sgcn_tpu.obs import RunRecorder, load_run

    d = str(tmp_path / "run")
    with RunRecorder(d, config={}, run_kind="train") as rec:
        rec.record_checkpoint(step=2, path="/x/ckpt_00000002.npz",
                              wall_s=0.05, bytes=100)
        rec.record_resume(step=2, path="/x/ckpt_00000002.npz",
                          fallback=True, skipped=["/x/ckpt_00000004.npz"])
    log = load_run(d)
    assert [e["kind"] for e in log.events] == ["checkpoint", "resume"]
    assert log.checkpoints()[0]["bytes"] == 100
    assert log.resumes()[0]["fallback"] is True


def test_recorder_roundtrip(tmp_path):
    from sgcn_tpu.obs import RunRecorder, load_run

    plan = _plan()
    d = str(tmp_path / "run")
    with RunRecorder(d, config={"epochs": 2}, run_kind="train") as rec:
        rec.set_plan(plan, partitioner={"kind": "rp", "k": plan.k})
        rec.record_step(step=1, loss=1.5, wall_s=0.25, grad_norm=2.0)
        rec.record_eval(step=1, loss=1.4, acc=0.5)
        rec.record_heartbeat("unit:ping", detail="from test")
        rec.record_summary({"epochs": 2, "value": np.float32(1.25)})
    log = load_run(d)
    assert log.manifest["config"]["epochs"] == 2
    assert log.manifest["plan"]["n"] == plan.n
    assert log.manifest["partitioner"]["kind"] == "rp"
    assert len(log.manifest["plan"]["digest"]) == 16
    assert [e["kind"] for e in log.events] == ["step", "eval", "heartbeat",
                                               "summary"]
    assert log.summaries()[0]["report"]["value"] == 1.25  # numpy coerced
    # digest is stable for the same plan, different for a different one
    from sgcn_tpu.obs import plan_digest
    assert plan_digest(plan) == log.manifest["plan"]["digest"]
    assert plan_digest(_plan(seed=7)) != log.manifest["plan"]["digest"]


def test_recorder_refuses_invalid_event(tmp_path):
    import pytest

    from sgcn_tpu.obs import RunRecorder

    with RunRecorder(str(tmp_path / "r"), config={}) as rec:
        with pytest.raises(ValueError):
            rec.record_step(step=1, loss=1.0, wall_s=float("nan"))


def test_heartbeat_env_gated(tmp_path, monkeypatch):
    import json
    import os

    from sgcn_tpu.obs import heartbeat, load_run

    d = str(tmp_path / "hb")
    monkeypatch.delenv("SGCN_METRICS_OUT", raising=False)
    heartbeat("should:not:write")
    assert not os.path.exists(os.path.join(d, "heartbeat.jsonl"))
    monkeypatch.setenv("SGCN_METRICS_OUT", d)
    heartbeat("phase:start", phase="unit", detail="x")
    heartbeat("phase:done", phase="unit")
    path = os.path.join(d, "heartbeat.jsonl")
    assert os.path.exists(path)
    recs = [json.loads(line) for line in open(path)]
    assert [r["event"] for r in recs] == ["phase:start", "phase:done"]
    # a heartbeat-ONLY directory (the launch/dryrun workflow — no recorder,
    # no manifest) must still load; manifest comes back empty
    log = load_run(d)
    assert log.manifest == {} and len(log.heartbeats) == 2


def test_step_cost_model_and_roofline():
    from sgcn_tpu.models.gcn import exchange_widths
    from sgcn_tpu.obs import (STREAM_CEILING_GBS, gather_bytes_per_epoch,
                              roofline_fields, step_cost)
    from sgcn_tpu.obs.attribution import STREAM_CEILING_DEVICE_KIND

    plan = _plan()
    fin, widths = 16, [32, 8]
    cost = step_cost(plan, fin, widths)
    assert cost.nlayers == 2
    assert cost.widths == exchange_widths(fin, widths)
    # the gather-byte model is the roofline numerator
    assert cost.gather_bytes == gather_bytes_per_epoch(plan, fin, widths)
    # per-layer blocks reconcile with the totals
    assert sum(pl["spmm_flops"] for pl in cost.per_layer) == cost.spmm_flops
    assert sum(pl["dense_flops"] for pl in cost.per_layer) == cost.dense_flops
    assert cost.step_flops == 2 * cost.spmm_flops + 3 * cost.dense_flops
    # halo bytes: global send rows at f32, 2L exchanges per step
    send_rows = int(plan.predicted_send_volume.sum())
    assert cost.halo_send_rows == send_rows
    assert cost.halo_bytes_per_step == 2 * sum(
        send_rows * w * 4 for w in cost.widths)
    # bf16 compute halves both streams
    bf = step_cost(plan, fin, widths, compute_dtype="bfloat16")
    assert bf.gather_bytes == gather_bytes_per_epoch(plan, fin, widths,
                                                     itemsize=2)
    assert bf.halo_bytes_per_step == cost.halo_bytes_per_step // 2

    roof = roofline_fields(cost, wall_s=0.01, exchanges=4,
                           exposed_exchanges=1)
    assert roof["achieved_gather_GBs"] == float(
        f"{cost.gather_bytes / 0.01 / 1e9:.4g}")
    # the stream ceiling is a figure for ONE device kind: the fraction is
    # emitted for that kind and for no other (CPU included)
    assert "stream_ceiling_frac" not in roof
    assert "stream_ceiling_frac" not in roofline_fields(
        cost, wall_s=0.01, device_kind="cpu")
    on_kind = roofline_fields(cost, wall_s=0.01,
                              device_kind=STREAM_CEILING_DEVICE_KIND)
    assert on_kind["stream_ceiling_frac"] == float(
        f"{cost.gather_bytes / 0.01 / 1e9 / STREAM_CEILING_GBS:.4g}")
    assert roof["exposed_comm_frac"] == 0.25
    # exposed bytes charge the WIRE volume of the selected schedule (the
    # padded slots cross ICI too — docs/comm_schedule.md), not the Σ(λ−1)
    # true volume the pre-split model under-counted with
    assert roof["exposed_halo_bytes"] == cost.halo_bytes_wire_per_step // 4
    assert roof["halo_bytes_true_per_step"] == cost.halo_bytes_per_step
    assert roof["halo_bytes_wire_per_step"] >= roof["halo_bytes_true_per_step"]
    assert roof["comm_schedule"] == "a2a"
