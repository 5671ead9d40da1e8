"""One run of one benchmark cell.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, the first to touch JAX, on the machine it is started on.  The
cell is looked up in ``BENCHMARK.json`` and resolved to data files and small
modules by ``manifest.py``; nothing here names a cell, a configuration or a
metric.  In order: inputs from seeds (the graph from ``.cache`` after the
first run) → the runner's ``build`` (partition, plan, placement) and
``warm`` (every program the window uses is compiled and run once) — all of it
set-up — then either the measured window of ``--seconds`` (``--trace 0``, the
end-to-end metrics) or a short profiled window (``--trace 1``, the per-layer
metrics) → peak memory → the plain reference on the emptied device → result.

The last stdout line is the result object and nothing else; everything a
reader may want besides is on earlier ``bench:`` lines.  Off a TPU, or with
fewer chips than the cell asks for, the run exits non-zero and prints no
result.  ``--rehearse`` is for the tests: the cell's ``rehearse`` sizes on
virtual CPU devices, every step of the above, no result line, exit code 3.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()            # process start, as near as Python gets

import argparse                                             # noqa: E402
import contextlib                                           # noqa: E402
import gc                                                   # noqa: E402
import glob                                                 # noqa: E402
import json                                                 # noqa: E402
import math                                                 # noqa: E402
import os                                                   # noqa: E402
import shutil                                               # noqa: E402
import sys                                                  # noqa: E402
import traceback                                            # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))   # the program, from this checkout

import inputs                                               # noqa: E402
import manifest                                             # noqa: E402
import tracered                                             # noqa: E402

EXIT_NO_CHIP, EXIT_REHEARSAL = 2, 3
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def say(**fields) -> None:
    print("bench: " + json.dumps(fields), flush=True)


class Counters:
    """Compile requests, their seconds, and persistent-cache hits, from
    ``jax.monitoring`` (the pattern of ``chip_smoke.Counters``)."""

    def __init__(self):
        import jax.monitoring as mon

        self.compiles, self.compile_s, self.cache_hits = 0, 0.0, 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_kw):
        if event == COMPILE_EVENT:
            self.compiles += 1
            self.compile_s += secs

    def _on_event(self, event, **_kw):
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1

    def snapshot(self) -> dict:
        return {"compiles": self.compiles, "compile_s": self.compile_s,
                "cache_hits": self.cache_hits}


class Context:
    """What a runner gets: the cell's inputs and devices, and the
    benchmark's own spans around its calls into the program."""

    def __init__(self, cell, seed: int, devices, rehearse: bool):
        self.cell, self.seed, self.devices = cell, seed, devices
        self.rehearse = rehearse
        self.spans: dict = {}       # name -> [seconds]
        self.notes: dict = {}       # anything a runner wants on a bench: line
        self.ahat = self.feats = self.labels = None

    @contextlib.contextmanager
    def span(self, name: str):
        """Host clock around a call, and the same interval as a
        ``TraceAnnotation`` on the profiler's clock."""
        import jax

        t = time.perf_counter()
        with jax.profiler.TraceAnnotation(tracered.SPAN_PREFIX + name):
            yield
        self.spans.setdefault(name, []).append(time.perf_counter() - t)


def traced_window(cell, state, ctx) -> dict:
    """Profile the epochs the runner offers, leave the trace under
    ``.cache/trace/<cell>/``, and reduce it; ``{}`` where no device plane was
    traced."""
    import jax

    fn, epochs = cell.runner.traced(state, ctx)
    pdir = os.path.join(inputs.CACHE_DIR, "trace", cell.name)
    shutil.rmtree(pdir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0        # host spans only, no Python frames
    jax.profiler.start_trace(pdir, profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    files = glob.glob(os.path.join(pdir, "**", "*.xplane.pb"), recursive=True)
    planes = tracered.load_xplane(files[0]) if files else []
    red = tracered.reduce_trace(planes, epochs)
    if red is None:
        say(device_planes=0, planes=[p["name"] for p in planes])
        return {}
    red["epochs"] = epochs
    say(epochs=epochs, per_chip=red["per_chip"],
        primitives=tracered.top(red["primitives"]))
    return red


def check_reference(cell, state, ctx, device_kind: str) -> dict:
    """First K losses of the trainer against the plain reference run from the
    same initial weights (cached per seed: it is the benchmark's own), and
    the logits at the trained weights."""
    ref = manifest.load_module(os.path.join(HERE, "reference",
                                            cell.config["reference"]["file"]))
    k = int(cell.config["reference"]["losses"])
    got = [float(x) for x in cell.runner.first_updates(state, k)]
    spec = {"ref": inputs.file_hash(ref.__file__), "seed": ctx.seed,
            "device": device_kind,
            "config": {a: b for a, b in cell.config.items() if a != "rehearse"},
            "traffic": {a: b for a, b in cell.traffic.items() if a != "rehearse"},
            "rehearse": ctx.rehearse}
    (want,), hit = inputs.cached_arrays(
        "ref", spec, lambda: [cell.runner.reference_losses(state, ctx, ref, k)],
        ("losses",))
    want = [float(x) for x in want]
    gaps = [abs(g / w - 1.0) for g, w in zip(got, want)]
    out = {"trainer": got, "reference": want, "rtol": ref.RTOL,
           "max_rel_gap": max(gaps) if gaps else None, "cache_hit": hit}
    out["ok"] = len(got) == k and all(math.isfinite(g) for g in gaps) \
        and max(gaps) <= ref.RTOL
    # row by row, at the trained weights: a loss is a mean over every row
    # and hides what a narrower table or product would do to each
    mine, theirs = cell.runner.logits_pair(
        state, ctx, ref, [c[0] for c in ref.LOGITS_CHECKS])
    out["logits"] = []
    for precision, norm, limit in ref.LOGITS_CHECKS:
        diff = (mine - theirs[precision]).astype("float64")
        rms = float((theirs[precision].astype("float64") ** 2).mean()) ** 0.5
        gaps = {"max": float(abs(diff).max()) / rms,
                "rms": float((diff ** 2).mean()) ** 0.5 / rms}
        out["logits"].append({"reference": precision, **gaps,
                              "limit": [norm, limit]})
        out["ok"] = out["ok"] and gaps[norm] <= limit
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tests only: tiny sizes on virtual CPU devices, "
                         "no result line, exit code 3")
    args = ap.parse_args()

    cell = manifest.resolve(args.workload, rehearse=args.rehearse)
    if args.seconds is None:
        args.seconds = float(manifest.read_json(
            os.path.join(manifest.ROOT, "BENCHMARK.json"))["run_seconds"])

    from sgcn_tpu.utils.backend import place_compile_cache, use_cpu_devices

    if args.rehearse:
        use_cpu_devices(cell.chips)
    cache_dir = place_compile_cache()

    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if not args.rehearse and device["platform"] != "tpu":
        print(f"benchmark: platform is {device['platform']!r}, not 'tpu'; "
              "no chip, no result", file=sys.stderr)
        return EXIT_NO_CHIP
    if len(devs) < cell.chips:
        print(f"benchmark: cell {cell.name} needs {cell.chips} chip(s), JAX "
              f"shows {len(devs)}", file=sys.stderr)
        return EXIT_NO_CHIP
    chips = devs[:cell.chips]
    counters = Counters()
    ctx = Context(cell, args.seed, chips, args.rehearse)
    say(cell=cell.name, seed=args.seed, seconds=args.seconds,
        trace=args.trace, device=device, compile_cache=cache_dir,
        rehearse=args.rehearse)

    # ------------------------------------------------------------- set-up
    cfg = cell.config
    with ctx.span("inputs.graph"):
        ctx.ahat, graph_hit = inputs.load_graph(cfg["n"], cfg["graph"])
    with ctx.span("inputs.features"):
        ctx.feats, ctx.labels = inputs.features_and_labels(
            cfg["n"], cfg["f_in"], cfg["classes"], args.seed)
    state = cell.runner.build(cell, ctx)
    cell.runner.warm(state, ctx)
    after_setup = counters.snapshot()
    on_chip = device["platform"] == "tpu"
    in_use = [d.memory_stats()["bytes_in_use"] for d in chips] \
        if on_chip else []
    setup_s = time.perf_counter() - T0
    say(setup_s=setup_s, graph_cache_hit=graph_hit, nnz=int(ctx.ahat.nnz),
        spans={k: [round(x, 4) for x in v] for k, v in ctx.spans.items()},
        counters=after_setup, notes=ctx.notes)

    # ------------------------------------------------------------- window
    trace = {}
    if args.trace:
        trace = traced_window(cell, state, ctx)
    else:
        try:
            cell.runner.sample(state, ctx, args.seconds)
        except Exception:       # noqa: BLE001 — a failed epoch is a result
            traceback.print_exc()
            state.failed += 1
    in_window = counters.snapshot()
    # live arrays peak in ``peak_bytes_in_use``; what the runtime sets aside
    # for the loaded programs' temporaries is booked apart, as reserved
    stats = [d.memory_stats() for d in chips] if on_chip else []
    reserved = [s["peak_bytes_reserved"] for s in stats]
    peak = [s["peak_bytes_in_use"] + s["peak_bytes_reserved"] for s in stats]
    say(samples=state.samples, attempted=state.attempted, failed=state.failed,
        compiles_in_window=in_window["compiles"] - after_setup["compiles"],
        losses_first=state.losses[:4], loss_last=state.losses[-1:],
        bytes_in_use=in_use, peak_bytes_reserved=reserved, peak_bytes=peak)

    # ---------------------------------------------------------- correctness
    cell.runner.release(state)
    gc.collect()
    ref = check_reference(cell, state, ctx, device["kind"])
    say(reference=ref)
    finite = all(math.isfinite(x) for x in state.losses)
    checks = {          # (the platform and chip count were held to above)
        "no_compile_in_window":
            in_window["compiles"] == after_setup["compiles"],
        "losses_finite": finite and state.failed == 0,
        "loss_fell": len(state.losses) > 1
        and state.losses[-1] < state.losses[0],
        "reference": ref["ok"],
    }
    say(checks=checks)

    run = {
        "setup_s": setup_s, "spans": ctx.spans, "samples": state.samples,
        "counters": {"setup": after_setup, "window": in_window},
        "memory": {"in_use": in_use, "reserved": reserved, "peak": peak},
        "halo_counts": state.halo_counts, "nnz": int(ctx.ahat.nnz),
        "chips": cell.chips, "config": cfg, "traffic": cell.traffic,
        "trace": trace, "device_kind": device["kind"], "notes": ctx.notes,
    }
    metrics = {}
    for name, unit, reader in (cell.per_layer if args.trace
                               else cell.end_to_end):
        value = reader.read(run)
        if value is not None:
            metrics[name] = {"value": value, "unit": unit}

    if args.rehearse:
        print("benchmark rehearsal (cpu, not a result): "
              + json.dumps({"checks": checks, "metrics": sorted(metrics)}),
              flush=True)
        return EXIT_REHEARSAL

    device["memory_peak_bytes"] = max(peak)
    result = {"correct": all(checks.values()),
              "attempted": state.attempted, "failed": state.failed,
              "metrics": metrics, "device": device}
    if args.trace:
        if not trace:
            print("benchmark: the trace shows no device plane",
                  file=sys.stderr)
            return 1
        device["busy_s"], device["window_s"] = trace["busy_s"], trace["window_s"]
        result["breakdown"] = {"device_ops": tracered.top(trace["ops"]),
                               "idle_gaps": tracered.top(trace["gaps"])}
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
