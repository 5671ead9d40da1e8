"""Tier-1 gate for the static-analysis subsystem (``sgcn_tpu/analysis``).

Three layers of assurance, in one module:

  * the **matrix audit at HEAD** — every supported mode's real program
    lowers clean against its plan-derived expectation (collective census,
    wire dtype/shape, no host callbacks, donation), including the banded
    fixture that pins empty-round ELISION and the bf16-wire contract
    across every schedule × staleness combination (the PR-9 satellite:
    previously only numerically implied);
  * **mutation checks** — each rule class provably FAILS on a seeded
    violation (an f32 wire under a bf16 config, a doubled collective, a
    smuggled host callback, dropped donation, host time in traced code,
    an unregistered consumer tuple, an unenumerated mode flag).  A lint
    that cannot fail is decoration; these tests are the no-vacuous-lint
    acceptance criterion;
  * **parser units** — the shared HLO parser (``analysis.hlo``) against
    synthetic StableHLO / scheduled-HLO snippets, since both the auditor
    and ``tests/test_overlap_hlo.py`` ride it.

The module-scoped ``full_report`` fixture runs the whole matrix ONCE
(~75 s at HEAD — inside the tier-1 per-test budget, charged to the first
test that uses it); everything else asserts against that one report.
"""

import importlib

import pytest

from sgcn_tpu.analysis import hlo
from sgcn_tpu.analysis.ast_rules import (RULES, rule_consumer_registered,
                                         rule_mode_flag_enumerated,
                                         rule_model_name_free,
                                         rule_sanctioned_sync_only,
                                         rule_traced_host_free,
                                         run_ast_pass)
from sgcn_tpu.analysis.hlo_audit import (BANDED_MODES, audit_mode,
                                          audit_plan, run_audit)
from sgcn_tpu.analysis.modes import (Mode, is_supported, supported_modes,
                                     train_matrix_verdicts)


@pytest.fixture(scope="module")
def full_report():
    return run_audit()


def _violations(entry):
    return [v for prog in entry["programs"].values()
            for v in prog["violations"]]


def _rules_hit(entry):
    return {v["rule"] for v in _violations(entry)}


# ------------------------------------------------------------ matrix @ HEAD
# a pure enumeration (no lowering at collection): every supported mode plus
# the banded fixture's ragged modes — one case each, so a red mode is named
AUDITED_MODE_IDS = ([m.mode_id for m in supported_modes()]
                    + [m.mode_id + "@banded" for m in BANDED_MODES])


@pytest.mark.parametrize("mode_id", AUDITED_MODE_IDS)
def test_mode_clean_at_head(full_report, mode_id):
    """Acceptance criterion, mode by mode: every census/dtype/shape/
    donation check of this mode's programs passes at HEAD."""
    entry = full_report["modes"][mode_id]
    assert entry["ok"], _violations(entry)


def test_full_matrix_covered_at_head(full_report):
    """The auditor covers the full supported mode matrix — exactly the
    enumerated ids — and its one verdict is green."""
    assert set(full_report["modes"]) == set(AUDITED_MODE_IDS)
    assert full_report["n_modes"] == len(full_report["modes"])
    assert full_report["ok"]


def test_matrix_covers_the_advertised_axes(full_report):
    """gcn/gat × a2a/ragged × staleness 0/1 × f32/bf16, plus serve buckets
    and the mini-batch envelope — the coverage the issue names, pinned as
    specific mode ids so a silently narrowed enumerator fails here."""
    ids = set(full_report["modes"])
    for required in (
            "train/gcn/a2a/s0/f32", "train/gcn/a2a/s0/bf16",
            "train/gcn/ragged/s0/f32", "train/gcn/ragged/s0/bf16",
            "train/gcn/a2a/s1/f32", "train/gcn/a2a/s1/bf16",
            "train/gcn/ragged/s1/f32", "train/gcn/ragged/s1/bf16",
            "train/gcn/a2a/s1/f32/delta", "train/gcn/ragged/s1/bf16/delta",
            "train/gat/a2a/fused", "train/gat/a2a/split",
            "train/gat/a2a/packed", "train/gat/ragged/fused",
            "train/gat/ragged/split", "train/gat/ragged/packed",
            "serve/gcn/a2a/s0/f32", "serve/gcn/ragged/s0/bf16",
            "serve/gat/a2a/fused", "serve/gat/ragged/fused",
            "minibatch/gcn/ragged/s0/f32",
            "train/gcn/a2a/s0/f32/rep", "train/gcn/a2a/s0/bf16/rep",
            "train/gcn/ragged/s0/f32/rep", "train/gcn/ragged/s0/bf16/rep",
            "train/gcn/a2a/s1/f32/rep", "train/gcn/a2a/s1/bf16/rep",
            "train/gcn/ragged/s1/f32/rep", "train/gcn/ragged/s1/bf16/rep",
            "train/gcn/ragged/s0/f32@banded",
            "train/gcn/ragged/s1/f32@banded",
            "train/gcn/ragged/s1/f32/rep@banded",
            # the schedule-/model-agnostic Pallas kernel family (ISSUE 15)
            "train/gcn/a2a/s0/f32/pallas", "train/gcn/a2a/s0/bf16/pallas",
            "train/gcn/ragged/s0/f32/pallas",
            "train/gcn/ragged/s0/bf16/pallas",
            "train/gat/a2a/fused/pallas", "train/gat/a2a/split/pallas",
            "train/gat/ragged/fused/pallas",
            "train/gat/ragged/split/pallas",
            "train/gcn/ragged/s0/f32/pallas@banded"):
        assert required in ids, f"mode {required} missing from the audit"


def test_stale_modes_audit_both_programs(full_report):
    """Every pipelined mode lowers BOTH its stale and full-sync programs —
    the f32 delta re-base is a sync-step-only wire contract."""
    for mid, entry in full_report["modes"].items():
        if "/s1/" in mid:
            assert set(entry["programs"]) == {"stale", "sync"}, mid


def test_replica_modes_audit_both_programs_and_shrink_the_wire(full_report):
    """Every replica mode lowers BOTH its replica and refresh programs,
    and the replica program's compiled wire is STRICTLY smaller than the
    refresh program's (the acceptance contract: replicated rows excluded
    from the send buckets show up as smaller static wire shapes, via
    CommPlan.wire_buffer_shapes(replica=True)).  The clean matrix entry
    already pins the exact shapes; this pins the strict shrink so a
    degenerate fixture (replicas that shrink nothing) cannot make the
    rule vacuous."""
    plan = audit_plan()
    plan.ensure_ragged()
    from sgcn_tpu.analysis.hlo_audit import AUDIT_REPLICA_B
    plan.ensure_replicas(AUDIT_REPLICA_B)
    assert plan.nrep_s < plan.s
    assert sum(plan.nrep_rr_sizes) < sum(plan.rr_sizes)
    for mid, entry in full_report["modes"].items():
        if mid.endswith("/rep") and "/s1/" in mid:
            # the COMPOSED replica × stale modes lower the stale/sync
            # program pair (the stale carry subsumes the replica tables);
            # the shrunken-wire contract is the stale program's census
            assert set(entry["programs"]) == {"stale", "sync"}, mid
            continue
        if mid.endswith("/rep"):
            assert set(entry["programs"]) == {"rep", "sync"}, mid
            # same dispatch COUNTS (no round became empty at this budget),
            # strictly smaller buffers — the shape check inside the census
            # asserted the exact values already
            c_rep = entry["programs"]["rep"]["census"]
            c_sync = entry["programs"]["sync"]["census"]
            kind = ("collective_permute" if "/ragged/" in mid
                    else "all_to_all")
            assert c_rep[kind] > 0 and c_sync[kind] > 0, mid


def test_empty_rounds_elided_in_census(full_report):
    """The banded fixture keeps 2 of k−1 ring rounds; the compiled ragged
    program must carry collective_permutes for EXACTLY the live rounds.
    Exact mode: 2 exchanges (layer 1 forward + backward — aggregate-first
    layer 0's backward exchange is dead code, and since PR 26 its forward
    exchange is hoisted out of the step: ``gcn``'s ``Hoist``; 3 exchanges × 2
    before) × 2 live rounds; stale mode: 4 exchanges × 2."""
    from sgcn_tpu.ops.pspmm import ragged_live_rounds

    live = ragged_live_rounds(audit_plan("banded").ragged_round_sizes())
    assert len(live) == 2
    exact = full_report["modes"]["train/gcn/ragged/s0/f32@banded"]
    assert exact["programs"]["step"]["census"]["collective_permute"] == 4
    stale = full_report["modes"]["train/gcn/ragged/s1/f32@banded"]
    for prog in stale["programs"].values():
        assert prog["census"]["collective_permute"] == 8


def test_bf16_wire_contract_every_mode(full_report):
    """The PR-9 satellite: ``--halo-dtype bfloat16`` puts bf16 on EVERY
    ppermute/all_to_all wire operand for a2a/ragged × staleness 0/1 —
    pinned from the audit census (previously only numerically implied by
    loss-tolerance tests).  The one documented exception: a delta-mode
    SYNC step re-bases the feature wire at full f32."""
    for sched in ("a2a", "ragged"):
        for sid in ("s0", "s1"):
            entry = full_report["modes"][f"train/gcn/{sched}/{sid}/bf16"]
            assert entry["ok"]
            for label, prog in entry["programs"].items():
                assert prog["census"]["wire_dtypes"] == ["bf16"], \
                    (sched, sid, label)
        # delta mode: stale steps ship the bf16 increment, the sync step's
        # re-base is the full f32 row — while the grad wire stays bf16
        entry = full_report["modes"][f"train/gcn/{sched}/s1/bf16/delta"]
        assert entry["programs"]["stale"]["census"]["wire_dtypes"] == \
            ["bf16"]
        assert entry["programs"]["sync"]["census"]["wire_dtypes"] == \
            ["bf16", "f32"]
    # serve inherits the same wire lever forward-only
    for sched in ("a2a", "ragged"):
        prog, = full_report["modes"][
            f"serve/gcn/{sched}/s0/bf16"]["programs"].values()
        assert prog["census"]["wire_dtypes"] == ["bf16"]


def test_gat_packed_wire_narrows(full_report):
    """The GAT bf16 wire contract: the packed form ships fout/2+1 f32
    lanes (bit-paired bf16) on EVERY layer — the audit's shape check pins
    it, and the matrix entry being clean means the forward actually does
    it (the audit caught HEAD⁻¹ shipping full-width f32 tables on every
    layer past the first; see models/gat.py gat_forward_local)."""
    for sched in ("a2a", "ragged"):
        assert full_report["modes"][f"train/gat/{sched}/packed"]["ok"]
    from sgcn_tpu.models.gat import gat_table_form
    assert gat_table_form(8, "bfloat16") == "packed"
    assert gat_table_form(8, None) == "fused"


def test_serve_programs_donate_nothing(full_report):
    for mid, entry in full_report["modes"].items():
        if mid.startswith("serve/"):
            for prog in entry["programs"].values():
                assert prog["census"]["donated_args"] == 0, mid


def test_train_programs_donate_params_and_state(full_report):
    """params + opt state (+ stale carries) carry jax.buffer_donor — the
    donation side of the satellite, pinned so it cannot regress."""
    e = full_report["modes"]["train/gcn/a2a/s0/f32"]
    # 2 weight leaves + adam (count, 2×mu, 2×nu)
    assert e["programs"]["step"]["census"]["donated_args"] == 7
    s = full_report["modes"]["train/gcn/a2a/s1/f32"]
    # + carries (2 halos, 2 ghalos minus the dead layer-0 one, 2 bases)
    assert s["programs"]["stale"]["census"]["donated_args"] >= 12


def test_composition_matrix_matches_doc():
    """The enumerator is the machine face of docs/comm_schedule.md's
    composition matrix — these literals ARE that table's support column
    (schedule × staleness × delta × replicas × model); a drift in either
    direction fails here."""
    v = train_matrix_verdicts()
    doc_rows = {
        ("a2a", 0, False, False, "gcn"): True,
        ("a2a", 0, False, False, "gat"): True,
        ("a2a", 1, False, False, "gcn"): True,
        ("a2a", 1, False, False, "gat"): False,
        ("a2a", 1, True, False, "gcn"): True,
        ("a2a", 1, True, False, "gat"): False,
        ("ragged", 0, False, False, "gcn"): True,
        ("ragged", 0, False, False, "gat"): True,
        ("ragged", 1, False, False, "gcn"): True,
        ("ragged", 1, False, False, "gat"): False,
        ("ragged", 1, True, False, "gcn"): True,
        ("ragged", 1, True, False, "gat"): False,
        # delta without staleness is a construction-time error everywhere
        ("a2a", 0, True, False, "gcn"): False,
        ("a2a", 0, True, False, "gat"): False,
        ("ragged", 0, True, False, "gcn"): False,
        ("ragged", 0, True, False, "gat"): False,
        # hot-halo replication: GCN-only; composes with the stale
        # pipeline (PR-12: the stale carry subsumes the replica tables),
        # but not with the delta cache (docs/replication.md)
        ("a2a", 0, False, True, "gcn"): True,
        ("ragged", 0, False, True, "gcn"): True,
        ("a2a", 0, False, True, "gat"): False,
        ("ragged", 0, False, True, "gat"): False,
        ("a2a", 1, False, True, "gcn"): True,
        ("ragged", 1, False, True, "gcn"): True,
        ("a2a", 1, True, True, "gcn"): False,
        ("ragged", 1, True, True, "gcn"): False,
    }
    for key, supported in doc_rows.items():
        assert v[key][0] is supported, (key, v[key])


def test_supported_modes_all_self_consistent():
    for m in supported_modes():
        ok, reason = is_supported(m)
        assert ok, (m, reason)
    ids = [m.mode_id for m in supported_modes()]
    assert len(ids) == len(set(ids)), "duplicate mode ids"


# ------------------------------------------------------------- mutations
def test_mutation_f32_wire_under_bf16_config(monkeypatch):
    """Seeded violation: the exchange silently drops the requested bf16
    wire cast.  The auditor must flag wire-dtype — this is the regression
    class the subsystem exists for."""
    pspmm = importlib.import_module("sgcn_tpu.ops.pspmm")

    real = pspmm.halo_exchange

    def no_narrow(h, send_idx, halo_src, axis_name=pspmm.AXIS,
                  halo_dtype=None):
        return real(h, send_idx, halo_src, axis_name, None)

    monkeypatch.setattr(pspmm, "halo_exchange", no_narrow)
    entry = audit_mode(Mode("train", "gcn", "a2a",
                            halo_dtype="bfloat16"))
    assert not entry["ok"]
    assert "wire-dtype" in _rules_hit(entry)


def test_mutation_extra_collective(monkeypatch):
    """Seeded violation: a doubled all_to_all per exchange (the 'extra
    hidden synchronization' class) must fail the collective census."""
    pspmm = importlib.import_module("sgcn_tpu.ops.pspmm")

    real = pspmm.a2a_or_identity

    def doubled(buf, axis_name):
        return real(real(buf, axis_name), axis_name)

    monkeypatch.setattr(pspmm, "a2a_or_identity", doubled)
    entry = audit_mode(Mode("train", "gcn", "a2a"))
    assert not entry["ok"]
    assert "collective-census" in _rules_hit(entry)


def test_mutation_missing_ragged_round(monkeypatch):
    """Seeded violation: a live ring round's ppermute silently replaced by
    a local identity (rows never cross the wire — shapes and downstream
    folds unchanged, so nothing else notices) — strictly fewer
    collective_permutes than the plan's live rounds must fail the census.
    Note the seeding is in the PROGRAM, not in ``ragged_live_rounds``:
    the elision rule is deliberately single-sourced, so patching the
    helper would move the expectation along with the op."""
    import jax

    pspmm = importlib.import_module("sgcn_tpu.ops.pspmm")

    real = pspmm.ppermute_or_identity

    def dropped(buf, axis_name, d):
        if d == 1:
            (recv,) = jax.lax.optimization_barrier((buf,))
            return recv
        return real(buf, axis_name, d)

    monkeypatch.setattr(pspmm, "ppermute_or_identity", dropped)
    entry = audit_mode(Mode("train", "gcn", "ragged"))
    assert not entry["ok"]
    assert "collective-census" in _rules_hit(entry)


def test_mutation_replica_rows_still_shipped(monkeypatch):
    """Seeded violation for the replica wire rule: the replica step
    silently keeps shipping the FULL buckets (replicated rows never leave
    the wire — numerically indistinguishable because the carry overwrite
    lands the same rows, so only the compiled wire shapes betray it).
    The auditor must flag wire-shape on the 'rep' program — the mutation
    that proves the shrunken-wire expectation is not vacuous."""
    pspmm = importlib.import_module("sgcn_tpu.ops.pspmm")

    real = pspmm._replica_halo

    def full_wire(x, rep, send_idx, halo_src, nrep_send_idx, nrep_halo_src,
                  rep_slots, axis_name, halo_dtype, fresh):
        if not fresh:
            # ship the full exchange, then overwrite replica slots anyway —
            # same halo table bits, un-shrunken wire
            halo = pspmm.halo_exchange(x, send_idx, halo_src, axis_name,
                                       halo_dtype)
            halo = halo.at[rep_slots].set(rep.astype(halo.dtype),
                                          mode="drop")
            return halo, rep
        return real(x, rep, send_idx, halo_src, nrep_send_idx,
                    nrep_halo_src, rep_slots, axis_name, halo_dtype, fresh)

    monkeypatch.setattr(pspmm, "_replica_halo", full_wire)
    entry = audit_mode(Mode("train", "gcn", "a2a", replica=True))
    assert not entry["ok"]
    assert not entry["programs"]["rep"]["ok"]
    assert "wire-shape" in _rules_hit(entry)


def test_mutation_host_callback_in_step(monkeypatch):
    """Seeded violation: a jax.debug.print smuggled into the forward —
    the python-callback custom call must be flagged."""
    import jax

    import sgcn_tpu.models.gcn as gcn

    real = gcn.get_activation

    def chatty(name):
        act = real(name)

        def wrapped(x):
            jax.debug.print("step {}", x.sum())
            return act(x)

        return wrapped

    monkeypatch.setattr(gcn, "get_activation", chatty)
    entry = audit_mode(Mode("train", "gcn", "a2a"))
    assert not entry["ok"]
    assert "host-callback" in _rules_hit(entry)


def test_mutation_dropped_donation(monkeypatch):
    """Seeded violation: donate_argnums stripped from the step compile —
    every params/opt-state argument loses its jax.buffer_donor marker and
    the donation rule must fail (the 'dropped donation' class: the step
    double-buffers every update and nobody notices on a small graph)."""
    import jax

    real_jit = jax.jit

    def undonated_jit(f, *a, **kw):
        kw.pop("donate_argnums", None)
        return real_jit(f, *a, **kw)

    monkeypatch.setattr(jax, "jit", undonated_jit)
    entry = audit_mode(Mode("train", "gcn", "a2a"))
    assert not entry["ok"]
    assert "donation" in _rules_hit(entry)


def test_mutation_ast_host_time_in_traced_module():
    src = "import time\n\ndef f(x):\n    return x * time.time()\n"
    v = rule_traced_host_free("sgcn_tpu/ops/custom.py", src)
    assert v and "time.time" in v[0]
    src = ("import numpy as np\n\ndef f(x):\n"
           "    return x + np.random.default_rng(0).random()\n")
    v = rule_traced_host_free("sgcn_tpu/models/custom.py", src)
    assert v and "np.random" in v[0]
    # aliased spellings — the natural forms of the violation must not slip
    v = rule_traced_host_free(
        "sgcn_tpu/ops/custom.py",
        "import time as t\n\ndef f(x):\n    return x * t.time()\n")
    assert v and "time.time" in v[0]
    v = rule_traced_host_free(
        "sgcn_tpu/models/custom.py",
        "from numpy.random import default_rng\n\ndef f(x):\n"
        "    return x + default_rng(0).random()\n")
    assert v and "numpy.random.default_rng" in v[0]
    # jax.random is traced-safe and must stay clean, aliased or not
    assert not rule_traced_host_free(
        "sgcn_tpu/ops/custom.py",
        "import jax\n\ndef f(k):\n    return jax.random.normal(k, (2,))\n")
    assert not rule_traced_host_free(
        "sgcn_tpu/ops/custom.py",
        "from jax import random\n\ndef f(k):\n"
        "    return random.normal(k, (2,))\n")


def test_mutation_ast_raw_sync_in_step():
    src = ("import jax\n\ndef step(x):\n"
           "    jax.block_until_ready(x)\n    return x\n")
    v = rule_sanctioned_sync_only("sgcn_tpu/train/custom.py", src)
    assert v and "block_until_ready" in v[0]
    v = rule_sanctioned_sync_only(
        "sgcn_tpu/serve/custom.py",
        "import jax\n\ndef g(x):\n    return jax.device_get(x)\n")
    assert v and "device_get" in v[0]


def test_mutation_ast_unregistered_consumer_tuple():
    src = 'MY_NEW_PLAN_FIELDS = ("send_idx", "halo_src")\n'
    v = rule_consumer_registered("sgcn_tpu/models/custom.py", src)
    assert v and "CONSUMER_TUPLE_SOURCES" in v[0]
    # registered names and non-string tuples pass
    assert not rule_consumer_registered(
        "sgcn_tpu/models/custom.py", 'SHAPES = (1, 2)\n')


def test_mutation_ast_unenumerated_mode_flag():
    src = ('import argparse\np = argparse.ArgumentParser()\n'
           'p.add_argument("--halo-compression", default=None)\n')
    v = rule_mode_flag_enumerated({"sgcn_tpu/train/__main__.py": src})
    assert any("--halo-compression" in x for x in v)
    # a trainer CLI missing an enumerated axis is the reverse drift
    assert any("dead matrix axis" in x for x in v)


def test_mutation_ast_model_name_compared():
    src = ('def f(model, setup):\n'
           '    if model == "gat":\n        return 1\n'
           '    return 2 if model not in ("gcn", "rgcn") else 3\n')
    v = rule_model_name_free("sgcn_tpu/train/custom.py", src)
    assert len(v) == 2 and "'gat'" in v[0] and "'gcn'" in v[1]
    # a flag's value or a name that is no model's passes; the rule's scope
    # leaves the CLI's input validation and the models themselves alone
    assert not rule_model_name_free(
        "sgcn_tpu/train/custom.py", 'def f(s):\n    return s == "auto"\n')
    rule = next(r for r in RULES if r.name == "model-name-free")
    assert rule.applies("sgcn_tpu/parallel/plan.py")
    assert rule.applies("sgcn_tpu/obs/memory.py")
    assert not rule.applies("sgcn_tpu/train/__main__.py")
    assert not rule.applies("sgcn_tpu/models/gcn.py")


def test_ast_pass_clean_at_head():
    rep = run_ast_pass()
    assert rep["ok"], rep


# ---------------------------------------------------------------- parsers
_SYNTH_STABLEHLO = """\
module @jit_step attributes {mhlo.num_partitions = 8 : i32} {
  func.func public @main(%arg0: tensor<8x8xf32> {jax.buffer_donor = true, mhlo.sharding = "{replicated}"}, %arg1: tensor<8x10x4xbf16> {mhlo.sharding = "{devices=[8,1,1]<=[8]}"}) -> (tensor<8x8xf32>) {
    %0 = "stablehlo.all_to_all"(%arg1) <{channel_handle = #stablehlo.channel_handle<handle = 1, type = 1>, concat_dimension = 0 : i64, replica_groups = dense<[[0, 1, 2, 3, 4, 5, 6, 7]]> : tensor<1x8xi64>, split_count = 8 : i64, split_dimension = 0 : i64}> : (tensor<8x10x4xbf16>) -> tensor<8x10x4xbf16>
    %1 = "stablehlo.all_reduce"(%arg0) <{channel_handle = #stablehlo.channel_handle<handle = 2, type = 1>, replica_groups = dense<[[0, 1, 2, 3, 4, 5, 6, 7]]> : tensor<1x8xi64>, use_global_device_ids}> ({
    ^bb0(%a: tensor<f32>, %b: tensor<f32>):
      %s = stablehlo.add %a, %b : tensor<f32>
      stablehlo.return %s : tensor<f32>
    }) : (tensor<8x8xf32>) -> tensor<8x8xf32>
    %2 = stablehlo.custom_call @Sharding(%1) : (tensor<8x8xf32>) -> tensor<8x8xf32>
    %3 = stablehlo.custom_call @xla_python_cpu_callback(%2) : (tensor<8x8xf32>) -> tensor<8x8xf32>
    return %3 : tensor<8x8xf32>
  }
}
"""


def test_collective_op_parser_units():
    ops = hlo.collective_ops(_SYNTH_STABLEHLO)
    kinds = [op.kind for op in ops]
    assert kinds == ["all_to_all", "all_reduce"]
    a2a, ar = ops
    assert a2a.wire == ((8, 10, 4), "bf16")
    assert ar.wire == ((8, 8), "f32") and ar.reducer == "add"
    assert hlo.host_callback_targets(_SYNTH_STABLEHLO) == \
        ["xla_python_cpu_callback"]
    assert hlo.unknown_custom_calls(_SYNTH_STABLEHLO) == []
    args = hlo.main_args(_SYNTH_STABLEHLO)
    assert [a.donated for a in args] == [True, False]
    assert args[1].type == ((8, 10, 4), "bf16")
    assert hlo.parse_tensor_type("i32") == ((), "i32")


_SYNTH_SCHEDULED = """\
  %all-to-all-start.1 = ((f32[]), f32[]) all-to-all-start(%x)
  %fusion.1 = f32[] fusion(%y), kind=kLoop
  %fusion.2 = f32[] fusion(%z), kind=kLoop
  %all-to-all-done.1 = f32[] all-to-all-done(%all-to-all-start.1)
  %all-to-all-start.2 = ((f32[]), f32[]) all-to-all-start(%w)
  %all-to-all-done.2 = f32[] all-to-all-done(%all-to-all-start.2)
"""


def test_full_mesh_groups_flags_sub_mesh():
    """The sub-mesh psum census: a reduction over multiple replica groups
    (the realistic printed form of a half-mesh psum, every device still
    named) must fail the full-mesh check; the real single-group form over
    all k devices must pass."""
    from sgcn_tpu.analysis.hlo_audit import _full_mesh_groups

    full = hlo.HloOp(kind="all_reduce", line=0, text=(
        'replica_groups = dense<[[0, 1, 2, 3, 4, 5, 6, 7]]> : '
        'tensor<1x8xi64>, use_global_device_ids'))
    assert _full_mesh_groups(full, 8)
    half = hlo.HloOp(kind="all_reduce", line=0, text=(
        'replica_groups = dense<[[0, 1, 2, 3], [4, 5, 6, 7]]> : '
        'tensor<2x4xi64>, use_global_device_ids'))
    assert not _full_mesh_groups(half, 8)
    small = hlo.HloOp(kind="all_reduce", line=0, text=(
        'replica_groups = dense<[[0, 1, 2, 3]]> : tensor<1x4xi64>'))
    assert not _full_mesh_groups(small, 8)


def test_async_window_parser_units():
    assert hlo.count_async_starts(_SYNTH_SCHEDULED) == 2
    assert hlo.async_windows(_SYNTH_SCHEDULED) == [2, 0]
    with pytest.raises(ValueError, match="unknown start"):
        hlo.async_windows(
            "  %all-to-all-done.9 = f32[] all-to-all-done(%all-to-all-start.9)\n")
    with pytest.raises(ValueError, match="unmatched"):
        hlo.async_windows(
            "  %all-to-all-start.3 = ((f32[]), f32[]) all-to-all-start(%q)\n")


def test_wire_buffer_shapes_helper():
    plan = audit_plan()
    (a2a,) = plan.wire_buffer_shapes("a2a")
    assert a2a == (plan.k, plan.s)
    ragged = plan.wire_buffer_shapes("ragged")
    assert all(len(s) == 1 and s[0] > 0 for s in ragged)
    assert len(ragged) == len([x for x in plan.ragged_round_sizes()
                               if x > 0])
    banded = audit_plan("banded")
    assert len(banded.wire_buffer_shapes("ragged")) == 2
    with pytest.raises(ValueError, match="unknown comm schedule"):
        plan.wire_buffer_shapes("p2p")


def test_live_rounds_helper():
    from sgcn_tpu.ops.pspmm import ragged_live_rounds

    assert ragged_live_rounds((3, 0, 2)) == (1, 3)
    assert ragged_live_rounds(()) == ()
    banded = audit_plan("banded")
    k = banded.k
    assert ragged_live_rounds(banded.ragged_round_sizes()) == (1, k - 1)
