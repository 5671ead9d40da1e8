"""Multi-process (multi-host) integration: 2 processes × 4 virtual CPU
devices each, rendezvous over local TCP — the working equivalent of the
reference's 3-node SLURM launch (``GPU/pytorch.3node.slurm:46-56`` +
``GPU/PGCN.py:241-260``, ``dist.init_process_group`` over MASTER_ADDR).

Each subprocess: ``jax.distributed.initialize`` → 8-device global mesh →
identical plan from the same seeds → ``make_train_data_multihost`` (each
process materializes ONLY its chips' blocks) → 3 training steps.  The
parent runs the same problem single-process on its own 8 virtual devices
and asserts the loss trajectories match exactly — data placement must not
change the math.
"""

import json
import socket
import subprocess
import sys
import os

import numpy as np

_WORKER = r"""
import json, sys
import numpy as np

import jax
jax.config.update("jax_platforms", "cpu")

coord, pid = sys.argv[1], int(sys.argv[2])
jax.distributed.initialize(coordinator_address=coord, num_processes=2,
                           process_id=pid)
assert jax.process_count() == 2, jax.process_count()
assert jax.device_count() == 8, jax.device_count()
assert jax.local_device_count() == 4

sys.path.insert(0, {repo!r})
from sgcn_tpu.parallel import build_comm_plan
from sgcn_tpu.parallel.launch import global_mesh_1d
from sgcn_tpu.partition import balanced_random_partition
from sgcn_tpu.prep import normalize_adjacency
from sgcn_tpu.train import FullBatchTrainer, make_train_data_multihost
import scipy.sparse as sp

rng = np.random.default_rng(1)
n = 48
dense = rng.random((n, n)) < 0.15
dense = np.triu(dense, 1); dense = dense | dense.T
ahat = normalize_adjacency(sp.csr_matrix(dense.astype(np.float32)))
pv = balanced_random_partition(n, 8, seed=3)
plan = build_comm_plan(ahat, pv, 8)
mesh = global_mesh_1d(8)
feats = np.random.default_rng(7).standard_normal((n, 6)).astype(np.float32)
labels = (np.arange(n) % 3).astype(np.int32)

# each process only needs ITS chips' rows: blank out everything else to
# prove remote rows are never read
from sgcn_tpu.parallel.mesh import local_chip_slice
sl = local_chip_slice(mesh)
mine = np.isin(pv, np.arange(8)[sl])
feats_local = np.where(mine[:, None], feats, 0.0).astype(np.float32)
labels_local = np.where(mine, labels, 0).astype(np.int32)

tr = FullBatchTrainer(plan, fin=6, widths=[5, 3], mesh=mesh, seed=11)
data = make_train_data_multihost(plan, mesh, feats_local, labels_local)
losses = [float(tr.step(data)) for _ in range(3)]
if jax.process_index() == 0:
    print("LOSSES " + json.dumps(losses), flush=True)
"""


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_two_process_training_matches_single(tmp_path):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    worker = _WORKER.replace("{repo!r}", repr(repo))
    script = tmp_path / "worker.py"
    script.write_text(worker)
    coord = f"127.0.0.1:{_free_port()}"
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("PYTEST_CURRENT_TEST", None)
    procs = [subprocess.Popen(
        [sys.executable, str(script), coord, str(i)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        for i in range(2)]
    outs = []
    for p in procs:
        try:
            out, err = p.communicate(timeout=420)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outs.append((p.returncode, out, err))
    for rc, out, err in outs:
        assert rc == 0, f"worker failed rc={rc}\nstdout:{out}\nstderr:{err}"
    line = [ln for ln in outs[0][1].splitlines() if ln.startswith("LOSSES ")]
    assert line, outs[0][1]
    losses_mp = json.loads(line[0][len("LOSSES "):])

    # single-process reference on this process's own 8 virtual devices,
    # same seeds → identical trajectory expected
    import scipy.sparse as sp
    from sgcn_tpu.parallel import build_comm_plan
    from sgcn_tpu.partition import balanced_random_partition
    from sgcn_tpu.prep import normalize_adjacency
    from sgcn_tpu.train import FullBatchTrainer, make_train_data

    rng = np.random.default_rng(1)
    n = 48
    dense = rng.random((n, n)) < 0.15
    dense = np.triu(dense, 1)
    dense = dense | dense.T
    ahat2 = normalize_adjacency(sp.csr_matrix(dense.astype(np.float32)))
    pv = balanced_random_partition(n, 8, seed=3)
    plan = build_comm_plan(ahat2, pv, 8)
    feats = np.random.default_rng(7).standard_normal((n, 6)).astype(np.float32)
    labels = (np.arange(n) % 3).astype(np.int32)
    tr = FullBatchTrainer(plan, fin=6, widths=[5, 3], seed=11)
    data = make_train_data(plan, feats, labels)
    losses_sp = [float(tr.step(data)) for _ in range(3)]
    np.testing.assert_allclose(losses_mp, losses_sp, rtol=1e-5, atol=1e-6)
