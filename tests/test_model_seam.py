"""Every model enters through one seam (``models/setup.py``).

``MODELS`` maps each name to a ``Model`` record whose hook returns a
``ModelSetup``; the shared code reads those and compares no names
(``analysis/ast_rules.py::model-name-free``):

  * (a) for every model the hook returns a ``ModelSetup``, refuses a
    foreign ``model_args`` key and refuses the carried-halo request it has
    no form for (``gcn``, which has one, resolves it);
  * (b) ``gcn`` and ``gat`` resolve, mode by mode, the plan fields and
    forward statics they resolved before their hooks existed, and refuse
    what their trainer refused.

CPU, tiny graphs; nothing is compiled.
"""

import numpy as np
import pytest

from sgcn_tpu.analysis.ast_rules import MODEL_NAMES
from sgcn_tpu.models.setup import ModelSetup, Request
from sgcn_tpu.parallel import build_comm_plan
from sgcn_tpu.partition import balanced_random_partition
from sgcn_tpu.train.fullbatch import MODELS, resolve_forward_setup
from test_slot_names import FIN, MODELS as SPECS, TYPED, plans  # noqa: F401

SPECS = {"gat": {"widths": [8, 5]}, **SPECS}


# ------------------------------------------------------- (a) every model
@pytest.mark.parametrize("model", sorted(MODELS))
def test_every_model_has_a_hook_that_refuses_for_itself(plans, model):
    assert set(MODELS) == set(MODEL_NAMES)
    spec = SPECS[model]
    plan = plans["typed" if model in TYPED else "plain", 4]
    args, widths = spec.get("model_args"), spec["widths"]

    def hook(model_args=args, **request):
        return MODELS[model].hook(plan, FIN, widths, model_args,
                                  Request(**{"comm_schedule": "a2a",
                                             **request}))

    setup = hook()
    assert isinstance(setup, ModelSetup)
    assert setup.plan_fields and setup.lane_widths and setup.param_count
    with pytest.raises(ValueError, match="model_args"):
        hook(dict(args or {}, dropout=0.5))
    if model == "gcn":
        stale = hook(halo_staleness=1)
        assert "ltail_src" in stale.plan_fields and stale.hoist is None
    else:
        with pytest.raises(ValueError, match="staleness is not supported"):
            hook(halo_staleness=1)


# ------------------------------------------ (b) gcn and gat, mode by mode
CASES = {"a2a": {}, "ragged": {"comm_schedule": "ragged"},
         "pallas": {}, "replica": {"replica_budget": 4},
         "stale": {"halo_staleness": 1},
         "serve_subgraph": {"serve_subgraph": True},
         "shared_envelope": {"shared_envelope": True}}
_SYM = ("send_idx", "halo_src", "ell_idx", "ell_w", "ltail_dst", "ltail_src",
        "ltail_w", "hedge_dst", "hedge_src", "hedge_w")
_CELL = ("send_idx", "halo_src", "cell_idx", "cell_w", "ctail_dst",
         "ctail_src", "ctail_w", "row_valid")
# (plan fields, forward statics' keys) as resolve_forward_setup gave them
# before the hooks; a string: the refusal the trainer gave
PINNED = {
    ("gcn", "a2a"): (("send_idx", "halo_src", "ell_idx", "ell_w", "ft_idx",
                      "ft_w", "ft_row", "fh_idx", "fh_w", "fh_row"),
                     ("ell_buckets", "fold_classes")),
    ("gcn", "ragged"): (("rsend_idx", "ell_idx", "ell_w", "ltail_dst",
                         "ltail_src", "ltail_w", "redge_dst", "redge_src",
                         "redge_w"),
                        ("ell_buckets", "comm_schedule", "rr_sizes",
                         "rr_edge_sizes")),
    ("gcn", "pallas"): (("send_idx", "halo_src", "ptile_lsrc", "ptile_lld",
                         "ptile_lw", "ptile_hsrc", "ptile_hld", "ptile_hw"),
                        ("pallas_tb", "pallas_emulate", "pallas_lclasses",
                         "pallas_hclasses")),
    ("gcn", "replica"): (("send_idx", "halo_src", "nrep_send_idx",
                          "nrep_halo_src", "rep_slots") + _SYM[2:],
                         ("ell_buckets",)),
    ("gcn", "stale"): (_SYM, ("ell_buckets",)),
    ("gcn", "serve_subgraph"): (_SYM, ("ell_buckets",)),
    ("gcn", "shared_envelope"): (_SYM, ("ell_buckets",)),
    ("gat", "a2a"): (_CELL, ("cell_buckets",)),
    ("gat", "ragged"): (("rsend_idx", "rhalo_dst") + _CELL[2:],
                        ("cell_buckets", "comm_schedule", "rr_sizes",
                         "halo_r")),
    ("gat", "pallas"): (("send_idx", "halo_src", "ptile_csrc", "ptile_cld",
                         "ptile_cw", "row_valid"),
                        ("cell_buckets", "pallas_tb", "pallas_emulate",
                         "pallas_cclasses")),
    ("gat", "replica"): "replication is not supported",
    ("gat", "stale"): "staleness is not supported",
    ("gat", "serve_subgraph"): (_CELL, ("cell_buckets",)),
    ("gat", "shared_envelope"): (_CELL, ("cell_buckets",)),
}


@pytest.mark.parametrize("model,case", sorted(PINNED))
def test_gcn_and_gat_resolve_what_they_resolved(ahat, monkeypatch, model,
                                                case):
    monkeypatch.setenv("SGCN_PALLAS_SPMM", "1" if case == "pallas" else "0")
    n = ahat.shape[0]
    plan = build_comm_plan(ahat, balanced_random_partition(n, 4, seed=1), 4)
    kw = dict({"comm_schedule": "a2a"}, **CASES[case])
    want = PINNED[model, case]
    if isinstance(want, str):
        with pytest.raises(ValueError, match=want):
            resolve_forward_setup(plan, 16, [8, 4], model=model, **kw)
        return
    setup = resolve_forward_setup(plan, 16, [8, 4], model=model, **kw)
    assert (tuple(setup.plan_fields), tuple(setup.fwd_static)) == want
    # what ships is those fields, GAT's edge weights narrowed to masks
    shipped = setup.ship_arrays(plan)
    assert tuple(shipped) == want[0]
    masks = {f for f, a in shipped.items() if a.dtype == np.int8}
    assert masks == ({"cell_w", "ctail_w", "ptile_cw"} & set(want[0])
                     if model == "gat" else set())
