"""The kernel-selection rule against the REAL TPU compiler, without a chip.

Off the chip the trainers emulate the Pallas kernel, so a shape Mosaic
refuses is invisible to every CPU test.  This module AOT-compiles the real
train step for a ``v5e:2x2`` topology (``jax.experimental.topologies`` —
compile-only, the installed libtpu, no devices) with the selection rule
deciding for that topology's device, and pins the two refusals the
bring-up found (PERF.md): a tile class whose prefetch operands overflow
SMEM, and a bf16 table.  Both must now compile because the rule no longer
selects them.  ``chip_smoke.py``'s kernel leg is the on-chip counterpart.

libtpu admits ONE process at a time, compile-only included — never run
this module concurrently with another process that initialises libtpu.
"""

import numpy as np
import pytest

from sgcn_tpu.io.datasets import ba_graph
from sgcn_tpu.ops import pallas_spmm
from sgcn_tpu.parallel import build_comm_plan, make_mesh_1d
from sgcn_tpu.prep import normalize_adjacency
from sgcn_tpu.train import FullBatchTrainer

# the TPU compiler takes seconds per step program and needs a jaxlib whose
# TPU AOT path works at all — outside the tier-1 budget
pytestmark = pytest.mark.slow

FIN, WIDTHS = 128, [128, 128, 40]


@pytest.fixture(scope="module")
def v5e_device():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                       # noqa: BLE001
        pytest.skip(f"v5e topology AOT unavailable: {e!r}")
    return topo.devices[0]


def _compiled_step(v5e_device, monkeypatch, n, avg_deg, compute_dtype=None):
    """(trainer, compiled HLO text) of the k=1 step, selected for and
    compiled against the v5e topology device."""
    from jax.sharding import Mesh

    monkeypatch.delenv("SGCN_PALLAS_SPMM", raising=False)    # auto rule
    monkeypatch.setattr(pallas_spmm, "kernel_device", lambda: v5e_device)
    ahat = normalize_adjacency(ba_graph(n, avg_deg // 2, seed=0))
    plan = build_comm_plan(ahat, np.zeros(n, np.int64), 1)
    tr = FullBatchTrainer(plan, fin=FIN, widths=WIDTHS, mesh=make_mesh_1d(1),
                          compute_dtype=compute_dtype)
    mesh = Mesh(np.array([v5e_device]), ("v",))
    return tr, tr.lower_step(mesh).compile().as_text()


def test_selected_kernel_compiles_for_v5e(v5e_device, monkeypatch):
    """chip_smoke's kernel-leg shape: the rule fires on its own and the
    compiled step carries the Mosaic kernel."""
    tr, text = _compiled_step(v5e_device, monkeypatch, 8_000, 14)
    st = tr._fwd_static
    assert st["pallas_emulate"] is False
    assert any(k == "vmem" for _, _, k in st["pallas_lclasses"])
    assert "tpu_custom_call" in text


def test_over_smem_class_compiles_as_ell(v5e_device, monkeypatch):
    """avg-deg 50 (products-like): the VMEM rule accepts the table, but a
    10-tile × Emax-6912 class would ask 1.27 MB of the 1 MiB SMEM — it
    must take the 'ell' form while the step still uses the kernel."""
    tr, text = _compiled_step(v5e_device, monkeypatch, 8_000, 50)
    smem = pallas_spmm.SMEM_BYTES["TPU v5 lite"]
    cap = pallas_spmm.pallas_emax_cap()
    classes = tr._fwd_static["pallas_lclasses"]
    over = [(t, e, k) for t, e, k in classes
            if e <= cap and pallas_spmm.prefetch_smem_bytes(t, e) > smem]
    assert over and all(k == "ell" for _, _, k in over), classes
    assert "tpu_custom_call" in text


def test_bf16_compute_compiles_on_the_ell_path(v5e_device, monkeypatch):
    tr, text = _compiled_step(v5e_device, monkeypatch, 4_000, 14,
                              compute_dtype="bfloat16")
    assert "pallas_tb" not in tr._fwd_static
    assert "tpu_custom_call" not in text
