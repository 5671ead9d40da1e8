"""The cost models' shared project-first rule must match the compiled
program: ``models/gcn.py::exchange_widths`` (used by the bench roofline and
the 8-chip epoch model) vs the actual all_to_all lane widths in the lowered
train step."""

import re

import numpy as np
import pytest

from sgcn_tpu.io.datasets import er_graph
from sgcn_tpu.models.gcn import exchange_widths
from sgcn_tpu.parallel import build_comm_plan
from sgcn_tpu.parallel.mesh import shard_stacked
from sgcn_tpu.partition import balanced_random_partition
from sgcn_tpu.prep import normalize_adjacency
from sgcn_tpu.train import FullBatchTrainer, make_train_data


def _lowered_a2a_widths(fin, widths):
    n, k = 1200, 4
    ahat = normalize_adjacency(er_graph(n, 6, seed=0))
    pv = balanced_random_partition(n, k, seed=1)
    plan = build_comm_plan(ahat, pv, k)
    tr = FullBatchTrainer(plan, fin=fin, widths=widths, seed=0)
    rng = np.random.default_rng(0)
    feats = rng.standard_normal((n, fin)).astype(np.float32)
    labels = rng.integers(0, widths[-1], n).astype(np.int32)
    data = make_train_data(plan, feats, labels)
    data = type(data)(**shard_stacked(tr.mesh, vars(data)))
    txt = tr._step.lower(
        tr.params, tr.opt_state, tr.pa, data.h0, data.labels,
        data.train_valid).as_text()
    # all_to_all operands are (k, S, f) buffers — the trailing dim is the
    # exchanged lane width
    dims = [int(m.group(1)) for m in re.finditer(
        r'stablehlo\.all_to_all.*?->\s*tensor<\d+x\d+x(\d+)xf32>', txt)]
    assert dims, "no all_to_all in lowered step"
    return sorted(set(dims)), tr.hoist is not None


@pytest.mark.parametrize("fin,widths", [
    (12, [8, 4]),          # aggregate-first everywhere (narrow inputs)
    (300, [8, 4]),         # wide input: layer 1 projects first, ships 8
])
def test_exchange_widths_match_lowered_program(fin, widths):
    want = exchange_widths(fin, widths)
    got, hoisted = _lowered_a2a_widths(fin, widths)
    # an aggregate-first layer 0 is hoisted out of the exact step (PR 26):
    # its entry is paid once per data set, the step ships layers 1.. only
    assert hoisted == (want[0] == fin)
    assert got == sorted(set(want[1:] if hoisted else want)), (got, want)
