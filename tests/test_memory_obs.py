"""Tier-1 gate for the memory-observability subsystem (``sgcn_tpu/obs/
memory.py`` + ``analysis/hlo_audit.py::run_memory_audit`` — ISSUE 18).

Four layers of assurance:

  * **reconciliation at HEAD** — a representative slice of the supported
    matrix (one mode per array family the model itemizes: dense-a2a halo
    tables, ragged+stale carries, replica carries, the GAT packed wire,
    Pallas tiles, the minibatch envelope, serve buckets, the sub-graph
    forward) compiles its REAL program and XLA's ``memory_analysis()``
    figures reconcile against the analytic model within ``MEM_MODEL_TOL``
    under the one-sided contract the module docstring states;
  * **mutation check** — a seeded ``donate_argnums`` strip provably trips
    the ``memory-model`` rule's alias floor (a lint that cannot fail is
    decoration);
  * **budget gate** — ``check_memory_budget`` rejects an over-budget
    (plan, mode) with the itemized per-family table, at plan time;
  * **gauge reconciliation** — the manifest ``memory`` block a real
    recorded run writes equals the model recomputed from the same
    (plan, config), and round-trips ``validate_manifest``.

The module-scoped ``rep_report`` fixture compiles the representative
programs ONCE (~60 s at HEAD — inside the tier-1 per-test budget, charged
to the first test that uses it).  The FULL 48-mode compile sweep is the
slow-marked ``test_full_matrix_memory_audit`` (~3 min).
"""

import os

import numpy as np
import pytest

from sgcn_tpu.analysis.hlo_audit import (AUDIT_FIN, AUDIT_WIDTHS, audit_plan,
                                         memory_audit_mode, run_memory_audit)
from sgcn_tpu.analysis.modes import Mode
from sgcn_tpu.obs.memory import (MEM_MODEL_TOL, MemoryBudgetError,
                                 MemoryModel, check_memory_budget,
                                 memory_model, parse_bytes, reconcile)

# one mode per array family the analytic model itemizes — the calibration
# set MEM_MODEL_TOL was derived on (worst observed peak/total ratio: the
# packed-wire GAT ragged mode at ~1.8 on CPU-compiled programs)
REP_MODES = (
    Mode("train", "gcn", "a2a"),                                 # halo_tables
    Mode("train", "gcn", "ragged", staleness=1,
         halo_dtype="bfloat16"),                                 # halo_carries
    Mode("train", "gcn", "a2a", replica=True),                   # replica_carries
    Mode("train", "gat", "ragged", gat_form="packed"),           # gat wire
    Mode("train", "gcn", "ragged", pallas=True),                 # pallas_tiles
    Mode("minibatch", "gcn", "a2a"),                             # envelope
    Mode("serve", "gcn", "ragged"),                              # bucket fwd
    Mode("serve_subgraph", "gcn", "a2a"),                        # subgraph fwd
)


@pytest.fixture(scope="module")
def rep_report():
    return {m.mode_id: memory_audit_mode(m) for m in REP_MODES}


def _violations(entry):
    return [v for prog in entry["programs"].values()
            for v in prog["violations"]]


# -------------------------------------------------- reconciliation at HEAD
def test_representative_modes_reconcile(rep_report):
    """Acceptance criterion: every representative program's measured peak /
    arguments / alias reconcile against the analytic model at HEAD."""
    bad = {mid: _violations(e) for mid, e in rep_report.items()
           if not e["ok"]}
    assert not bad, f"memory-model violations at HEAD: {bad}"


def test_measured_join_present_and_banded(rep_report):
    """The CPU backend exposes memory_analysis, so the join must actually
    be there (a sweep of skipped=True entries would pass vacuously), and
    every measured peak sits inside the calibrated band."""
    for mid, entry in rep_report.items():
        assert entry["model_bytes"] > 0, mid
        for label, prog in entry["programs"].items():
            assert not prog.get("skipped"), (mid, label)
            assert prog["measured"] is not None, (mid, label)
            assert 0.0 < prog["ratio"] <= MEM_MODEL_TOL, (
                f"{mid}/{label}: peak/model ratio {prog['ratio']:.2f} "
                f"outside (0, {MEM_MODEL_TOL}]")


def test_family_itemization_per_mode(rep_report):
    """Each representative mode's model itemizes the family it was picked
    for — the per-family lines of the budget table cannot silently
    collapse into 'workspace'."""
    plan = audit_plan()

    def fams(workload, **kw):
        return memory_model(plan, AUDIT_FIN, AUDIT_WIDTHS,
                            workload=workload, **kw).families

    assert fams("train", comm_schedule="a2a")["halo_tables"] > 0
    assert fams("train", comm_schedule="ragged")["halo_tables"] == 0
    assert fams("train", comm_schedule="ragged",
                halo_staleness=1)["halo_carries"] > 0
    assert fams("train", comm_schedule="a2a",
                replica_budget=12)["replica_carries"] > 0
    assert fams("serve", comm_schedule="ragged")["opt_state"] == 0
    # the audit entries carry the same totals the standalone model computes
    a2a = rep_report["train/gcn/a2a/s0/f32"]
    assert a2a["model_bytes"] == memory_model(
        plan, AUDIT_FIN, AUDIT_WIDTHS, workload="train",
        comm_schedule="a2a").total_bytes


# --------------------------------------------------------- mutation check
def test_donation_strip_trips_alias_floor(monkeypatch):
    """Seeded mutation: stripping ``donate_argnums`` from every jit zeroes
    XLA's alias bytes, and the memory-model rule's alias floor must fail
    DETERMINISTICALLY (this is the no-vacuous-lint criterion for the
    reconciliation contract's donation leg)."""
    import jax

    real_jit = jax.jit

    def stripped_jit(*args, **kwargs):
        kwargs.pop("donate_argnums", None)
        return real_jit(*args, **kwargs)

    monkeypatch.setattr(jax, "jit", stripped_jit)
    entry = memory_audit_mode(Mode("train", "gcn", "a2a"))
    assert not entry["ok"]
    viols = _violations(entry)
    assert viols and all(v["rule"] == "memory-model" for v in viols)
    assert any("alias" in v["detail"] for v in viols), viols


# ------------------------------------------------- reconcile() unit checks
def _toy_model(workload="train"):
    return MemoryModel(workload=workload,
                       families={"params": 1000, "opt_state": 2000,
                                 "workspace": 7000})


def test_reconcile_upper_envelope_and_argument_subset():
    m = _toy_model()
    ok = reconcile(m, {"argument_bytes": 3000, "output_bytes": 1000,
                       "temp_bytes": 2000, "alias_bytes": 3000,
                       "generated_code_bytes": 1, "peak_bytes": 3000})
    assert ok["ok"] and ok["block"]["total"]["ratio"] == 0.3
    # peak above model x tol — the envelope violation
    bad = reconcile(m, {"argument_bytes": 3000, "output_bytes": 1000,
                        "temp_bytes": 50_000, "alias_bytes": 3000,
                        "generated_code_bytes": 1, "peak_bytes": 51_000})
    assert not bad["ok"] and "exceeds the analytic total" in \
        bad["violations"][0]
    # arguments beyond the modeled resident set (jit never invents inputs)
    bad = reconcile(m, {"argument_bytes": 5000, "output_bytes": 0,
                        "temp_bytes": 0, "alias_bytes": 3000,
                        "generated_code_bytes": 1, "peak_bytes": 2000})
    assert not bad["ok"] and "resident arguments" in bad["violations"][0]


def test_reconcile_serve_must_not_alias():
    bad = reconcile(_toy_model("serve"),
                    {"argument_bytes": 1000, "output_bytes": 100,
                     "temp_bytes": 100, "alias_bytes": 64,
                     "generated_code_bytes": 1, "peak_bytes": 1136})
    assert not bad["ok"] and "must not be donated" in bad["violations"][0]


def test_reconcile_absent_join_is_ok():
    out = reconcile(_toy_model(), None)
    assert out["ok"] and out["block"]["total"]["measured_bytes"] is None


# ------------------------------------------------------------ budget gate
def test_budget_gate_rejects_with_itemized_table():
    plan = audit_plan()
    model = memory_model(plan, AUDIT_FIN, AUDIT_WIDTHS, workload="train",
                         comm_schedule="a2a")
    with pytest.raises(MemoryBudgetError) as ei:
        check_memory_budget(model, 1024, what="test trainer")
    msg = str(ei.value)
    assert "exceeds --memory-budget 1,024 B" in msg
    assert "per-family breakdown" in msg and "TOTAL" in msg
    for fam in ("params", "opt_state", "workspace"):
        assert fam in msg, f"budget table misses the {fam} line"
    # under budget (and no budget at all) pass silently
    check_memory_budget(model, model.total_bytes)
    check_memory_budget(model, None)
    with pytest.raises(ValueError, match="> 0"):
        check_memory_budget(model, 0)


def test_parse_bytes():
    assert parse_bytes("1024") == 1024
    assert parse_bytes("2K") == 2048
    assert parse_bytes("16G") == 16 * 1024 ** 3
    assert parse_bytes("1.5M") == int(1.5 * 1024 ** 2)
    assert parse_bytes("2KB") == 2048          # trailing B tolerated
    for bad in ("", "abc", "-1", "0", "nan"):
        with pytest.raises(ValueError):
            parse_bytes(bad)


# ------------------------------------------------------- model vs real init
def test_param_bytes_pin_real_init():
    """The hooks' ``param_count`` prices exactly what the init functions
    allocate — the params line of the budget table cannot drift from the
    real weight trees."""
    import jax

    from sgcn_tpu.models.gat import init_gat_params
    from sgcn_tpu.models.gcn import init_gcn_params
    from sgcn_tpu.train.fullbatch import resolve_forward_setup

    dims = list(zip([AUDIT_FIN] + list(AUDIT_WIDTHS)[:-1],
                    list(AUDIT_WIDTHS)))
    rng = jax.random.PRNGKey(0)

    def priced(model):
        setup = resolve_forward_setup(audit_plan(), AUDIT_FIN, AUDIT_WIDTHS,
                                      model=model)
        assert memory_model(audit_plan(), AUDIT_FIN, AUDIT_WIDTHS,
                            model=model, setup=setup).families["params"] \
            == 4 * setup.custom.param_count
        return 4 * setup.custom.param_count

    gcn = sum(int(np.prod(w.shape)) * 4 for w in init_gcn_params(rng, dims))
    assert priced("gcn") == gcn
    gat = sum(int(np.prod(leaf.shape)) * 4
              for layer in init_gat_params(rng, dims)
              for leaf in layer.values())
    assert priced("gat") == gat


# ------------------------------------------------- gauge reconciliation
def test_manifest_memory_block_reconciles(tmp_path):
    """A real recorded run's manifest ``memory`` block equals the model
    recomputed from the same (plan, config), validates through
    ``validate_manifest``, and a measured-join memory EVENT round-trips
    ``validate_event`` with the ``measured_peak_bytes`` vocabulary."""
    from sgcn_tpu.obs import (RunRecorder, load_run, validate_event,
                              validate_manifest)
    from sgcn_tpu.obs.memory import measure_compiled
    from sgcn_tpu.train import FullBatchTrainer

    plan = audit_plan()
    tr = FullBatchTrainer(plan, fin=AUDIT_FIN, widths=list(AUDIT_WIDTHS))
    with RunRecorder(str(tmp_path), config={"model": "gcn"}) as rec:
        tr.attach_recorder(rec)
        measured = measure_compiled(tr.lower_step().compile())
        assert measured is not None       # CPU exposes memory_analysis
        rec.record_memory("step", tr.memory, measured=measured,
                          budget_bytes=1 << 30)

    log = load_run(str(tmp_path))
    validate_manifest(log.manifest)
    blk = log.manifest["memory"]
    want = memory_model(plan, AUDIT_FIN, AUDIT_WIDTHS, workload="train",
                        comm_schedule=tr.comm_schedule)
    assert {k: v["model_bytes"] for k, v in blk["families"].items()} == \
        {k: int(v) for k, v in want.families.items()}
    assert blk["total"]["model_bytes"] == want.total_bytes

    mems = [e for e in log.events if e["kind"] == "memory"]
    assert len(mems) == 1
    ev = mems[0]
    validate_event(ev)
    assert ev["measured_peak_bytes"] == measured["peak_bytes"]
    assert ev["alias_bytes"] >= want.donated_floor_bytes
    assert abs(ev["ratio"] - measured["peak_bytes"] / want.total_bytes) \
        < 1e-9
    assert ev["budget_bytes"] == 1 << 30


# ------------------------------------------------------- full sweep (slow)
@pytest.mark.slow
def test_full_matrix_memory_audit():
    """The full 48-mode compile sweep: every supported mode's every program
    reconciles (the tier-1 slice above covers one mode per family; this is
    the exhaustive nightly face of the same contract)."""
    report = run_memory_audit()
    bad = {mid: _violations(e) for mid, e in report["modes"].items()
           if not e["ok"]}
    assert not bad, f"memory-model violations: {bad}"
    assert report["n_modes"] >= 40
