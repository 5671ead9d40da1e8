"""Comm/compute overlap: evidence from the COMPILED 8-chip TPU schedule.

The reference hides halo-exchange latency with Irecv → local SpMM → Waitany
(``Parallel-GCN/main.c:238-299``).  Round 3 proved our split-edge structure
gives XLA the same freedom (the local-src slot passes have no data dependence
on the all_to_all) but could not show actual concurrency: the virtual CPU
mesh serializes collectives.

This test extracts the evidence that does NOT need 8 chips: AOT-compile the real ``FullBatchTrainer`` train step against an 8-chip
v5e TOPOLOGY (``jax.experimental.topologies`` — compile-only, no devices) and
assert, in the scheduled HLO, that the halo ``all-to-all`` compiles to async
``-start``/``-done`` pairs with real compute (fusions — the local slot
passes) scheduled inside the start→done window.  That is the compiled-program
form of "communication overlaps local aggregation".

HLO parsing rides the repo's ONE parser (``sgcn_tpu.analysis.hlo`` — the
same module the mode-matrix auditor uses on lowered StableHLO), so the
start/done pairing logic cannot drift between this test and the audit.
"""

import re

import numpy as np
import pytest

from sgcn_tpu.analysis import hlo
from sgcn_tpu.parallel import build_comm_plan
from sgcn_tpu.partition import balanced_random_partition
from sgcn_tpu.train import FullBatchTrainer

# AOT-compiling the 8-chip v5e train step runs the real TPU compiler (and
# needs a jaxlib whose TPU AOT path works at all) — outside the tier-1
# budget, so it runs only in the unfiltered suite.  libtpu admits one
# process at a time: never run it beside another process that loads libtpu
pytestmark = pytest.mark.slow


K = 8


@pytest.fixture(scope="module")
def v5e_mesh():
    import jax
    from jax.experimental import topologies
    from jax.sharding import Mesh

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x4")
    except Exception as e:                       # noqa: BLE001
        pytest.skip(f"v5e topology AOT unavailable: {e!r}")
    return Mesh(np.array(topo.devices).reshape(K), ("v",))


@pytest.fixture(scope="module")
def step_text(v5e_mesh, n=4096, avg_deg=12, f=64):
    """Compile one real train step for the v5e slice; return scheduled HLO.

    Compiled with ``xla_tpu_enable_async_all_to_all`` as a COMPILE OPTION:
    v5e's default schedule is a synchronous all-to-all (on the chip too —
    PERF.md bring-up), no shipped entry point turns the option on yet
    (ROADMAP A5), and as an ``XLA_FLAGS`` entry this jaxlib aborts on it."""
    from sgcn_tpu.io.datasets import ba_graph
    from sgcn_tpu.prep import normalize_adjacency

    ahat = normalize_adjacency(ba_graph(n, avg_deg // 2, seed=1))
    pv = balanced_random_partition(n, K, seed=2)
    plan = build_comm_plan(ahat, pv, K)
    tr = FullBatchTrainer(plan, fin=f, widths=[f, 8])
    lowered = tr.lower_step(v5e_mesh, fin=f)
    return lowered.compile(compiler_options={
        "xla_tpu_enable_async_all_to_all": "true"}).as_text()


def test_halo_all_to_all_is_async_and_overlapped(step_text):
    # pair each async start with ITS done via the SSA value name:
    #   %all-to-all-start.N = ... all-to-all-start(...)
    #   %all-to-all-done.M  = ... all-to-all-done(%all-to-all-start.N)
    # (hlo.async_windows raises on an unknown-start done or an unmatched
    # start — a malformed schedule must fail loudly, not read as zero)
    assert hlo.count_async_starts(step_text) >= 2, (
        "no async all-to-all pairs in schedule — was the program compiled "
        "with xla_tpu_enable_async_all_to_all?")
    windows = hlo.async_windows(step_text)
    # Every layer's local-src slot pass is independent of its own exchange
    # by construction (ops/pspmm.py::pspmm_overlap), so the latency-hiding
    # scheduler must put real compute inside every real exchange window.
    # Measured on this program: 3 windows, 83-192 fusions each.
    assert len(windows) >= 2 and all(w > 0 for w in windows), (
        f"async windows carry no compute: fusions-in-window={windows}")


def test_grad_allreduce_present(step_text):
    """The dense-grad psum (GPU/PGCN.py:150-154 role) must appear in the same
    compiled program — all-reduce over all 8 chips."""
    assert re.search(r"all-reduce", step_text), \
        "no all-reduce in compiled step"
