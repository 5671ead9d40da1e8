"""Chip smoke test: the partitioned GCN trainer's main path, once, on the TPU.

    python chip_smoke.py             # one-chip leg + kernel leg
    python chip_smoke.py --chips 4   # + the four-chip leg (needs 4 devices)

One process, the first to touch JAX.  Drives the library pipeline the
trainer CLI calls after flag parsing (``sgcn_tpu/train/__main__.py``):
``normalize_adjacency → build_comm_plan → FullBatchTrainer →
make_train_data → shard_stacked → step()/run_epochs()`` at BASELINE.json's
config #2 (arxiv shape: BA graph, n=169,343, avg-deg 14, f=128, widths
[128,128,40], f32, fixed seed).  Exits non-zero unless the platform is
``tpu`` and every leg passed.  A pass ends with two stdout lines: ``chip_smoke
legs: {...}`` (each leg's numbers — smoke observations, not benchmark
metrics) and, last, the verdict ``{"ok": true, "device": {"platform", "kind",
"count"}}`` with exactly those keys, the device as JAX reports it.  Nothing
is caught and reported as degraded; a failure prints no verdict.

``--rehearse`` is the sandbox switch: tiny shapes on virtual CPU devices,
chip-only checks reported instead of asserted.  It names platform ``cpu``,
prints no success line and exits non-zero.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import statistics
import subprocess
import sys
import time

import numpy as np

import sgcn_tpu  # noqa: F401 — alone in a directory, the script must fail
from sgcn_tpu.utils.backend import place_compile_cache, use_cpu_devices

ROOT = os.path.dirname(os.path.abspath(__file__))
FIN, WIDTHS = 128, [128, 128, 40]
STEPS, EPOCHS = 5, 5          # timed step() calls; epochs per run_epochs()
SEED = 0


class Counters:
    """Compile requests and persistent-cache hits, from jax.monitoring."""

    def __init__(self):
        import jax.monitoring as mon

        self.compiles = 0
        self.cache_hits = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, _secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _on_event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def synth_inputs(n: int, avg_deg: int):
    """BA graph + seeded features; labels are a fixed random projection of
    the features, so the loss has something to learn and must fall."""
    from sgcn_tpu.io.datasets import ba_graph
    from sgcn_tpu.prep import normalize_adjacency

    ahat = normalize_adjacency(ba_graph(n, avg_deg // 2, seed=SEED))
    rng = np.random.default_rng(SEED)
    feats = rng.standard_normal((n, FIN)).astype(np.float32)
    proj = rng.standard_normal((FIN, WIDTHS[-1])).astype(np.float32)
    labels = (feats @ proj).argmax(axis=1).astype(np.int32)
    return ahat, feats, labels


def check(cond, msg) -> None:
    """A failed check fails the run (and survives ``python -O``)."""
    if not cond:
        raise AssertionError(msg)


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def trainer_and_data(plan, mesh, feats, labels, **kw):
    from sgcn_tpu.parallel import shard_stacked
    from sgcn_tpu.train import FullBatchTrainer, TrainData, make_train_data

    tr = FullBatchTrainer(plan, fin=FIN, widths=WIDTHS, mesh=mesh, seed=SEED,
                          **kw)
    data = make_train_data(plan, feats, labels)
    return tr, TrainData(**shard_stacked(mesh, vars(data)))


def train_leg(ahat, feats, labels, pv, devices, counters, chip: bool) -> dict:
    """≥5 step() calls then run_epochs(data, 5) on an explicit mesh."""
    import jax

    from sgcn_tpu.parallel import build_comm_plan, make_mesh_1d

    k = len(devices)
    mesh = make_mesh_1d(k, devices=devices)
    plan, plan_s = timed(lambda: build_comm_plan(ahat, pv, k))
    tr, data = trainer_and_data(plan, mesh, feats, labels)

    want = set(devices)
    for name, x in [("h0", data.h0), *tr.pa.items()]:
        held = {s.device for s in x.addressable_shards}
        check(len(x.addressable_shards) == k and held == want,
              f"{name}: shards on {held}, want one on each of {want}")
    for w in jax.tree.leaves(tr.params):
        check(w.sharding.is_fully_replicated
              and w.sharding.device_set == want,
              f"params on {w.sharding.device_set}, want replicated on {want}")

    c0, h0 = counters.compiles, counters.cache_hits
    first, warm_s = timed(lambda: tr.step(data))
    c1 = counters.compiles
    losses, step_ts = [first], []
    for _ in range(STEPS):
        loss, dt = timed(lambda: tr.step(data))    # float readback = sync
        losses.append(loss)
        step_ts.append(dt)
    check(counters.compiles == c1, "step() compiled after its warm-up call")
    ep0, fused_first_s = timed(lambda: tr.run_epochs(data, EPOCHS))
    c2 = counters.compiles
    ep1, fused_s = timed(lambda: tr.run_epochs(data, EPOCHS))
    check(counters.compiles == c2,
          "run_epochs() compiled after its warm-up call")
    losses += [float(x) for x in ep0] + [float(x) for x in ep1]
    check(np.all(np.isfinite(losses)), f"non-finite loss: {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")

    step_s = statistics.median(step_ts)
    out = {
        "k": k, "n": int(plan.n), "b_per_chip": int(plan.b),
        "plan_build_s": round(plan_s, 2),
        "comm_schedule": tr.comm_schedule,
        "kernel": "pallas" if "pallas_tb" in tr._fwd_static else "ell",
        "step_compile_s": round(warm_s - step_s, 2),
        "step_s": round(step_s, 5),
        "step_s_min_max": [round(min(step_ts), 5), round(max(step_ts), 5)],
        "fused_compile_s": round(fused_first_s - fused_s, 2),
        "fused_epoch_s": round(fused_s / EPOCHS, 5),
        "compile_requests": c2 - c0,
        "cache_hits": counters.cache_hits - h0,
        "losses": [round(x, 6) for x in losses],
    }
    stats = [d.memory_stats() for d in devices]
    if chip:
        used = [s["bytes_in_use"] for s in stats]
        check(min(used) > 0 and max(used) <= 2 * min(used),
              f"bytes_in_use uneven across chips: {used}")
        out["bytes_in_use"] = used
        out["peak_bytes_in_use"] = [s["peak_bytes_in_use"] for s in stats]
    if k > 1:
        text = tr.lower_step().compile().as_text()
        check("all-to-all" in text, "compiled step has no all-to-all")
        out["all_to_all_sync"] = text.count(" all-to-all(")
        out["all_to_all_start"] = text.count(" all-to-all-start(")
    return out


def kernel_leg(device, counters, chip: bool, n: int) -> dict:
    """A shape at which ``use_pallas_spmm`` fires on its own on the chip
    (one f32 table of n·128·4 B ≤ the 4 MiB budget): the compiled step must
    carry the Mosaic kernel and train like the ELL path on the same plan."""
    from sgcn_tpu.parallel import build_comm_plan, make_mesh_1d

    ahat, feats, labels = synth_inputs(n, 14)
    mesh = make_mesh_1d(1, devices=[device])
    plan = build_comm_plan(ahat, np.zeros(n, np.int64), 1)

    def run(allow_pallas):
        tr, data = trainer_and_data(plan, mesh, feats, labels,
                                    allow_pallas=allow_pallas)
        return tr, [tr.step(data) for _ in range(STEPS)]

    c0 = counters.compiles
    ell, ell_losses = run(False)
    check("pallas_tb" not in ell._fwd_static, "allow_pallas=False ignored")
    out = {"n": n, "ell_losses": [round(x, 6) for x in ell_losses]}
    if not chip:
        # off the chip the rule selects nothing unless forced, and a forced
        # selection is emulated — nothing here would test the kernel
        out["pallas"] = "not_run: no chip"
        return out
    pal, pal_losses = run(True)
    st = pal._fwd_static
    check("pallas_tb" in st, "use_pallas_spmm did not fire on the chip")
    check(st["pallas_emulate"] is False, "kernel emulated on the chip")
    text = pal.lower_step().compile().as_text()
    check("tpu_custom_call" in text, "no Mosaic kernel in the compiled step")
    np.testing.assert_allclose(pal_losses, ell_losses, rtol=1e-4, atol=1e-6)
    out.update(
        pallas_losses=[round(x, 6) for x in pal_losses],
        max_rel_gap=float(np.max(np.abs(
            np.array(pal_losses) / np.array(ell_losses) - 1))),
        tpu_custom_calls=text.count("tpu_custom_call"),
        classes={"local": st["pallas_lclasses"],
                 "halo": st["pallas_hclasses"]},
        compile_requests=counters.compiles - c0)
    return out


def rebuild_native() -> float:
    """Build native/libsgcnpart.so from native/sgcnpart.cpp NOW (-B), so a
    stale library left on disk is never what the partitioner loads."""
    _, dt = timed(lambda: subprocess.run(
        ["make", "-B", "-C", os.path.join(ROOT, "native"), "libsgcnpart.so"],
        check=True, capture_output=True, text=True))
    return dt


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="4 also runs the four-chip leg and fails with "
                         "fewer than four devices")
    ap.add_argument("--rehearse", action="store_true",
                    help="sandbox dry run on virtual CPU devices at tiny "
                         "shapes; never a pass (exits non-zero)")
    args = ap.parse_args()

    if args.rehearse:
        use_cpu_devices(4)
    cache_dir = place_compile_cache()

    import jax
    import jaxlib

    from sgcn_tpu.parallel.launch import init_distributed

    # as the trainer CLI does right after flag parsing; on one host — one
    # chip or four — the pod autodetect must stay a no-op
    ctx = init_distributed()
    check(ctx.num_processes == 1, ctx)

    try:
        from importlib.metadata import version
        libtpu = version("libtpu")
    except Exception:                   # noqa: BLE001 — version is a label
        libtpu = "unknown"
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    versions = {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                "libtpu": libtpu}
    print(f"chip_smoke: {versions} device={device} cache_dir={cache_dir}",
          flush=True)
    chip = device["platform"] == "tpu"
    if not chip and not args.rehearse:
        print(f"chip_smoke: platform is {device['platform']!r}, not 'tpu' "
              "— this is a chip check", file=sys.stderr)
        return 1
    if args.chips == 4 and len(devs) < 4:
        print(f"chip_smoke: --chips 4 but JAX shows {len(devs)} device(s)",
              file=sys.stderr)
        return 1

    n = 4_000 if args.rehearse else 169_343
    counters = Counters()
    ahat, feats, labels = synth_inputs(n, 14)
    result = {"versions": versions, "cache_dir": cache_dir,
              "tpu_worker_hostnames": os.environ.get("TPU_WORKER_HOSTNAMES")}

    # four chips first: device 0 then holds nothing of the one-chip leg
    # when the per-chip bytes are compared
    if args.chips == 4:
        from sgcn_tpu.partition import partition_hypergraph_colnet

        build_s = rebuild_native()
        (pv, km1), part_s = timed(
            lambda: partition_hypergraph_colnet(ahat, 4, seed=SEED))
        four = train_leg(ahat, feats, labels, pv, devs[:4], counters, chip)
        four.update(native_rebuilt_s=round(build_s, 2), km1=int(km1),
                    partition_s=round(part_s, 2))
        gc.collect()
    else:
        four = f"not_run: {len(devs)} device(s)" + (
            ", --chips 4 not given" if len(devs) >= 4 else "")

    one = train_leg(ahat, feats, labels, np.zeros(n, np.int64), devs[:1],
                    counters, chip)
    gc.collect()
    if args.chips == 4:
        a, b = np.array(four["losses"]), np.array(one["losses"])
        gap = float(np.max(np.abs(a / b - 1)))
        four["max_rel_loss_gap_vs_one_chip"] = gap
        check(gap <= 1e-2,
              f"four-chip losses leave the one-chip leg's by {gap:.3e} "
              f"relative (limit 1e-2):\n 4: {four['losses']}\n 1: "
              f"{one['losses']}")
    result.update(one_chip=one, four_chip=four,
                  kernel=kernel_leg(devs[0], counters, chip,
                                    2_000 if args.rehearse else 8_000))

    if not chip:
        print("chip_smoke: REHEARSAL on platform "
              f"{device['platform']!r} — not a pass\n"
              + json.dumps(result, indent=1), file=sys.stderr)
        return 1
    print("chip_smoke legs: " + json.dumps(result), flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
