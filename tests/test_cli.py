"""End-to-end CLI integration: preprocess → partition → SHP → train.

Exercises the same file-pipeline layering as the reference (SURVEY.md §1):
stages communicate only through files on disk.  Subprocesses run on forced
CPU with k virtual devices (the trainer CLI's ``-b cpu`` backend does this
itself); module CLIs are invoked via ``python -m``.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_cli(args, **kw):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)          # let -b cpu set its own device count
    env["PYTHONPATH"] = REPO
    return subprocess.run([sys.executable, "-m", *args], capture_output=True,
                          text=True, cwd=REPO, env=env, timeout=600, **kw)


@pytest.fixture(scope="module")
def pipeline(tmp_path_factory):
    """prep + partition once for all CLI tests."""
    d = tmp_path_factory.mktemp("cli")
    from sgcn_tpu.io.datasets import er_graph
    from sgcn_tpu.io.mtx import write_mtx
    write_mtx(str(d / "g.mtx"), er_graph(150, 8, seed=3))

    r = run_cli(["sgcn_tpu.prep", "-a", str(d / "g.mtx"), "-o", str(d),
                 "-n", "g", "-l", "2", "-f", "8", "-c", "3"])
    assert r.returncode == 0, r.stderr
    r = run_cli(["sgcn_tpu.partition", "-a", str(d / "g.A.mtx"), "-k", "4",
                 "-m", "hp,rp"])
    assert r.returncode == 0, r.stderr
    return d


def test_prep_outputs(pipeline):
    d = pipeline
    for f in ("g.A.mtx", "g.H.mtx", "g.Y.mtx", "config"):
        assert (d / f).exists(), f
    toks = (d / "config").read_text().split()
    assert toks[0] == "2" and toks[1] == "150"


def test_partition_outputs(pipeline):
    d = pipeline
    from sgcn_tpu.partition import read_partvec
    for suf in ("hp", "rp"):
        pv = read_partvec(str(d / f"g.A.mtx.4.{suf}"))
        assert pv.shape == (150,)
        assert pv.max() < 4


def test_train_cli_fullbatch(pipeline):
    d = pipeline
    r = run_cli(["sgcn_tpu.train", "-a", str(d / "g.A.mtx"),
                 "-p", str(d / "g.A.mtx.4.hp"), "-b", "cpu", "-s", "4",
                 "-l", "2", "-f", "6", "--epochs", "2"])
    assert r.returncode == 0, r.stderr
    report = json.loads(r.stdout.strip().splitlines()[-1])
    assert report["epochs"] == 2
    assert report["total_send_volume"] > 0


def test_shp_to_minibatch_train(pipeline):
    """SHP pickles feed the mini-batch trainer (the reference's coupling:
    GPU/SHP/main.py:131-140 → PGCN-Mini-batch.py:217-218)."""
    d = pipeline
    r = run_cli(["sgcn_tpu.shp", "-p", str(d / "g.A.mtx"), "-k", "3",
                 "-s", "4", "-b", "30", "-m", "3", "-o", str(d)])
    assert r.returncode == 0, r.stderr
    assert (d / "partvec.stchp.3").exists()
    r = run_cli(["sgcn_tpu.train", "-a", str(d / "g.A.mtx"),
                 "-p", str(d / "partvec.stchp.3"), "-b", "cpu", "-s", "3",
                 "-l", "2", "-f", "6", "-n", "40", "--epochs", "1"])
    assert r.returncode == 0, r.stderr
    report = json.loads(r.stdout.strip().splitlines()[-1])
    assert report["nbatches"] > 0


def test_train_cli_gat_default_activation_none(pipeline):
    """PGAT semantic fidelity: the reference stacks bare PGAT modules with no
    inter-layer nonlinearity (GPU/PGAT.py:202-213), so --model gat must not
    silently apply relu; --activation overrides."""
    d = pipeline
    r = run_cli(["sgcn_tpu.train", "-a", str(d / "g.A.mtx"),
                 "-p", str(d / "g.A.mtx.4.hp"), "-b", "cpu", "-s", "4",
                 "-l", "2", "-f", "6", "--model", "gat", "--epochs", "1"])
    assert r.returncode == 0, r.stderr
    report = json.loads(r.stdout.strip().splitlines()[-1])
    assert report["activation"] == "none"
    r = run_cli(["sgcn_tpu.train", "-a", str(d / "g.A.mtx"),
                 "-p", str(d / "g.A.mtx.4.hp"), "-b", "cpu", "-s", "4",
                 "-l", "2", "-f", "6", "--model", "gat", "--epochs", "1",
                 "--activation", "elu"])
    assert r.returncode == 0, r.stderr
    report = json.loads(r.stdout.strip().splitlines()[-1])
    assert report["activation"] == "elu"


def test_train_cli_bce_loss_reports_err(pipeline):
    """The MPI stack's loss flavor: sigmoid+BCE training with the `err`
    metric in the rank-0 report (Parallel-GCN/main.c:70-90,318-335)."""
    d = pipeline
    r = run_cli(["sgcn_tpu.train", "-a", str(d / "g.A.mtx"),
                 "-p", str(d / "g.A.mtx.4.hp"), "-b", "cpu", "-s", "4",
                 "-l", "2", "-f", "6", "--loss", "bce",
                 "--activation", "sigmoid", "--epochs", "2"])
    assert r.returncode == 0, r.stderr
    report = json.loads(r.stdout.strip().splitlines()[-1])
    assert report["loss"] == "bce"
    assert report["err"] > 0


def test_train_cli_rejects_bad_partvec(pipeline):
    d = pipeline
    (d / "bad.part").write_text("0 1 2\n")
    r = run_cli(["sgcn_tpu.train", "-a", str(d / "g.A.mtx"),
                 "-p", str(d / "bad.part"), "-b", "cpu", "-s", "4",
                 "-l", "2", "-f", "4"])
    assert r.returncode != 0
    assert "partvec length" in r.stderr


def test_train_cli_profile_writes_trace(pipeline, tmp_path):
    """--profile DIR captures a jax.profiler trace of the run (the tracing
    half of SURVEY.md §5.1; the phase-timer half is utils/timers.py)."""
    d = pipeline
    prof_dir = tmp_path / "prof"
    r = run_cli(["sgcn_tpu.train", "-a", str(d / "g.A.mtx"),
                 "-p", str(d / "g.A.mtx.4.hp"), "-b", "cpu", "-s", "4",
                 "-l", "2", "-f", "8", "--epochs", "2",
                 "--profile", str(prof_dir)])
    assert r.returncode == 0, r.stderr
    report = json.loads(r.stdout.strip().splitlines()[-1])
    assert report["epochs"] == 2
    traces = list(prof_dir.rglob("*.xplane.pb")) + \
        list(prof_dir.rglob("*.trace.json.gz"))
    assert traces, f"no trace files under {prof_dir}"


def test_baseline_cli_oracle(pipeline):
    """python -m sgcn_tpu.baselines oracle = the DGL/gcn.py role: dense
    single-process training on the preprocessor outputs (README.md:150-166)."""
    d = pipeline
    r = run_cli(["sgcn_tpu.baselines", "oracle", "-a", str(d / "g.A.mtx"),
                 "-f", str(d / "g.H.mtx"), "-y", str(d / "g.Y.mtx"),
                 "-c", str(d / "config"), "--epochs", "3"])
    assert r.returncode == 0, r.stderr
    rep = json.loads(r.stdout.strip().splitlines()[-1])
    assert rep["baseline"] == "oracle" and rep["epochs"] == 3
    assert np.isfinite(rep["final_loss"])
    assert "epoch 2" in r.stderr                   # per-epoch loss lines


def test_baseline_cli_cagnet(pipeline):
    """python -m sgcn_tpu.baselines cagnet = the Cagnet/main.c role:
    uniform-block 1D broadcast inference with the phase-time breakdown
    (Cagnet/main.c:35-38,395-413)."""
    d = pipeline
    r = run_cli(["sgcn_tpu.baselines", "cagnet", "-a", str(d / "g.A.mtx"),
                 "-c", str(d / "config"), "-s", "4", "--epochs", "2"])
    assert r.returncode == 0, r.stderr
    rep = json.loads(r.stdout.strip().splitlines()[-1])
    assert rep["baseline"] == "cagnet1d" and rep["epochs"] == 2
    assert {"data_comm", "local_spmm"} <= set(rep["phases"])
    assert rep["send_volume_per_exchange"] > 0


def test_train_cli_checkpoint_resume(pipeline, tmp_path):
    """--save-checkpoint / --resume: training continues from saved state
    (capability beyond the reference, which re-randomizes every run —
    SURVEY.md §5.4)."""
    d = pipeline
    ckpt = str(tmp_path / "state")
    base = ["sgcn_tpu.train", "-a", str(d / "g.A.mtx"),
            "-p", str(d / "g.A.mtx.4.hp"), "-b", "cpu", "-s", "4",
            "-l", "2", "-f", "8", "--warmup", "0"]
    r = run_cli(base + ["--epochs", "3", "--save-checkpoint", ckpt])
    assert r.returncode == 0, r.stderr
    rep1 = json.loads(r.stdout.strip().splitlines()[-1])
    assert rep1["checkpoint"].endswith(".npz")

    r = run_cli(base + ["--epochs", "2", "--resume", ckpt])
    assert r.returncode == 0, r.stderr
    # resumed optimization must start from the trained state, not re-init:
    # per-epoch loss lines print as "epoch 0: loss X"
    def first_epoch_loss(res):
        lines = (res.stdout + res.stderr).splitlines()
        return float([l for l in lines if l.startswith("epoch 0")][0]
                     .split()[-1])

    first_resumed = first_epoch_loss(r)
    first_fresh = first_epoch_loss(run_cli(base + ["--epochs", "1"]))
    assert first_resumed < first_fresh


def test_analysis_cli_fast_smoke():
    """``python -m sgcn_tpu.analysis --fast --json``: the AST hygiene pass
    plus the 2-mode HLO smoke subset, emitting the schema-validated JSON
    report on stdout with rc 0 — the CI face of the static-analysis
    subsystem (the full matrix runs in tests/test_analysis.py)."""
    r = run_cli(["sgcn_tpu.analysis", "--fast", "--json"])
    assert r.returncode == 0, r.stderr
    rep = json.loads(r.stdout.strip().splitlines()[-1])
    assert rep["schema"] == "sgcn_analysis_report" and rep["ok"] is True
    assert rep["fast"] is True
    assert rep["hlo"]["n_modes"] == 2 and rep["hlo"]["ok"] is True
    assert set(rep["ast"]["rules"]) == {
        "traced-host-free", "sanctioned-sync-only", "consumer-registered",
        "mode-flag-enumerated", "model-name-free"}
    assert all(e["ok"] for e in rep["ast"]["rules"].values())


def test_package_dispatcher_lists_tools():
    r = run_cli(["sgcn_tpu"])
    assert r.returncode == 0, r.stderr
    for mod in ("sgcn_tpu.prep", "sgcn_tpu.partition", "sgcn_tpu.train",
                "sgcn_tpu.shp", "sgcn_tpu.baselines"):
        assert mod in r.stdout


def test_package_dispatcher_rejects_arguments():
    r = run_cli(["sgcn_tpu", "train", "-a", "x.mtx"])
    assert r.returncode == 2
    assert "sgcn_tpu.train" in r.stderr      # points at the real module


def test_train_cli_memory_budget_gate(pipeline):
    """ISSUE 18 acceptance shape: an over-budget (plan, mode) is rejected
    AT PLAN TIME — nonzero exit, the itemized per-family breakdown on
    stderr, no traceback (a clean SystemExit, not an OOM mid-compile);
    a generous budget trains normally."""
    d = pipeline
    base = ["sgcn_tpu.train", "-a", str(d / "g.A.mtx"),
            "-p", str(d / "g.A.mtx.4.hp"), "-b", "cpu", "-s", "4",
            "-l", "2", "-f", "6", "--epochs", "1"]
    r = run_cli([*base, "--memory-budget", "1K"])
    assert r.returncode == 1, r.stdout
    assert "exceeds --memory-budget 1,024 B" in r.stderr
    assert "per-family breakdown" in r.stderr
    assert "params" in r.stderr and "TOTAL" in r.stderr
    assert "Traceback" not in r.stderr
    r = run_cli([*base, "--memory-budget", "1G"])
    assert r.returncode == 0, r.stderr
    # a malformed size is an argparse error (exit 2), naming the flag
    r = run_cli([*base, "--memory-budget", "lots"])
    assert r.returncode == 2
    assert "--memory-budget" in r.stderr


def test_chip_smoke_refuses_without_a_chip(tmp_path):
    """``chip_smoke.py`` is a chip check: on the CPU it exits non-zero and
    prints no success line — and so it does alone in a directory, without
    the program it is meant to drive."""
    import shutil

    script = os.path.join(REPO, "chip_smoke.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, script], capture_output=True,
                       text=True, cwd=REPO, env=env, timeout=300)
    assert r.returncode != 0, r.stdout
    assert "'platform': 'cpu'" in r.stdout          # it says what it found
    assert '"ok"' not in r.stdout
    assert "not 'tpu'" in r.stderr

    shutil.copy(script, tmp_path / "chip_smoke.py")
    env.pop("PYTHONPATH", None)
    r = subprocess.run([sys.executable, "chip_smoke.py"], capture_output=True,
                       text=True, cwd=tmp_path, env=env, timeout=300)
    assert r.returncode != 0
    assert r.stdout == ""
    assert "No module named 'sgcn_tpu'" in r.stderr


def test_train_cli_deepergcn(pipeline):
    """``--model deepergcn``: -l GENConv layers of --hidden as one scanned
    body, an encoder and a head; full-batch only."""
    d = pipeline
    base = ["sgcn_tpu.train", "-a", str(d / "g.A.mtx"),
            "-p", str(d / "g.A.mtx.4.hp"), "-b", "cpu", "-s", "4",
            "-l", "3", "--hidden", "8", "-f", "6", "--model", "deepergcn"]
    r = run_cli(base + ["--epochs", "2"])
    assert r.returncode == 0, r.stderr
    report = json.loads(r.stdout.strip().splitlines()[-1])
    assert report["model"] == "deepergcn" and report["epochs"] == 2
    assert report["activation"] == "relu"
    # warm-up + two epochs, three layers, both directions
    assert report["exchanges"] == 3 * 2 * 3
    r = run_cli(base + ["-n", "40"])
    assert r.returncode != 0 and "full-batch only" in r.stderr


def test_train_cli_rgcn(pipeline):
    """``--model rgcn``: the type table and the relations on the command
    line; typed rows train end to end on 4 virtual devices; full-batch
    only."""
    d = pipeline
    import scipy.io

    n = scipy.io.mmread(str(d / "g.A.mtx")).shape[0]
    first = n // 2
    base = ["sgcn_tpu.train", "-a", str(d / "g.A.mtx"),
            "-p", str(d / "g.A.mtx.4.hp"), "-b", "cpu", "-s", "4",
            "-l", "2", "--hidden", "8", "-f", "6", "--model", "rgcn",
            "--node-types",
            f"doc:{first}:features,tag:{n - first}:embedding",
            "--relations", "doc:links:doc,doc:has:tag,tag:of:doc",
            "--label-type", "doc"]
    r = run_cli(base + ["--epochs", "2"])
    assert r.returncode == 0, r.stderr
    report = json.loads(r.stdout.strip().splitlines()[-1])
    assert report["model"] == "rgcn" and report["epochs"] == 2
    history = [float(ln.split("loss")[1]) for ln in r.stdout.splitlines()
               if ln.startswith("epoch ")]
    assert len(history) == 2 and history[1] < history[0]
    # warm-up + two epochs, two layers, both directions (layer 0's backward
    # too: the tags' embeddings are trainable)
    assert report["exchanges"] == 3 * 2 * 2
    r = run_cli(base + ["-n", "40"])
    assert r.returncode != 0 and "full-batch only" in r.stderr
    r = run_cli(base[:-6] + ["--epochs", "1"])
    assert r.returncode != 0 and "--node-types" in r.stderr
