"""Exchange-only bf16 (``halo_dtype``): numerics parity + narrowed wire.

The multi-chip win of bf16 is ICI bytes, which only the
a2a buffer sees — cast exactly the send buffer, upcast after the halo
gather, leave tables/activations f32.
"""

import numpy as np
import pytest

from sgcn_tpu.io.datasets import er_graph
from sgcn_tpu.parallel import build_comm_plan
from sgcn_tpu.partition import balanced_random_partition
from sgcn_tpu.prep import normalize_adjacency
from sgcn_tpu.train import FullBatchTrainer, make_train_data


@pytest.fixture(scope="module")
def setup():
    n, k = 4000, 8
    ahat = normalize_adjacency(er_graph(n, 8, seed=0))
    pv = balanced_random_partition(n, k, seed=1)
    plan = build_comm_plan(ahat, pv, k)
    rng = np.random.default_rng(0)
    feats = rng.standard_normal((n, 16)).astype(np.float32)
    labels = rng.integers(0, 4, n).astype(np.int32)
    return plan, feats, labels


def _fit(plan, feats, labels, **kw):
    tr = FullBatchTrainer(plan, fin=16, widths=[8, 4], seed=2, **kw)
    data = make_train_data(plan, feats, labels)
    r = tr.fit(data, epochs=4, verbose=False)
    return tr, r["loss_history"]


def test_halo_bf16_numerics_parity(setup):
    """Training under the bf16 wire tracks f32 training to bf16 tolerance —
    only boundary rows are quantized, local rows not at all."""
    plan, feats, labels = setup
    _, ref = _fit(plan, feats, labels)
    _, bf = _fit(plan, feats, labels, halo_dtype="bfloat16")
    np.testing.assert_allclose(bf, ref, rtol=5e-3, atol=5e-3)
    assert not np.allclose(bf, ref, rtol=0, atol=0), \
        "bf16 wire changed nothing — cast not applied?"


def test_halo_bf16_wire_is_narrow(setup):
    """The lowered step carries bf16 all_to_alls and NO f32 ones — both
    directions (forward halo + backward gradient exchange)."""
    plan, feats, labels = setup
    tr = FullBatchTrainer(plan, fin=16, widths=[8, 4], seed=2,
                          halo_dtype="bfloat16")
    data = make_train_data(plan, feats, labels)
    from sgcn_tpu.parallel.mesh import shard_stacked
    data = type(data)(**shard_stacked(tr.mesh, vars(data)))
    txt = tr._step.lower(
        tr.params, tr.opt_state, tr.pa, data.h0, data.labels,
        data.train_valid).as_text()
    import re
    a2a_types = re.findall(r'"?stablehlo\.all_to_all"?.*?->\s*tensor<[0-9x]*(f32|bf16)>', txt)
    assert a2a_types, "no all_to_all in lowered step?"
    assert set(a2a_types) == {"bf16"}, a2a_types


def test_gat_rejects_halo_dtype(setup):
    plan, *_ = setup
    with pytest.raises(ValueError, match="GCN-trainer lever"):
        FullBatchTrainer(plan, fin=16, widths=[8, 4], model="gat",
                         halo_dtype="bfloat16")
