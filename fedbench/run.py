"""The benchmark's one command.

    python3 -m fedbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, no children.  Set-up (data from the seed, reference check,
engine, placement, warm-up), then the measured window, then one JSON line.
Everything about a cell comes from files found by the names in
BENCHMARK.json (fedbench/harness/manifest.py).
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

T_START = time.perf_counter()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="fedbench.run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from fedbench.harness import manifest
    cell = manifest.Cell(args.workload)
    knobs = manifest.load_json(os.path.join(manifest.BENCH_DIR, "harness",
                                            "harness.json"))
    try:
        from fedml_tpu.utils import compile_cache
    except ImportError as e:
        raise SystemExit(f"fedbench: the system under test is not in this "
                         f"checkout ({e})")
    compile_cache.configure()
    import jax
    from fedbench.harness import build, correctness, device, loop
    doc = device.require_device(cell.chips)
    on_chip = doc["platform"] == "tpu"
    compiles = device.CompileCounter()
    split = {}

    def lap(name, t):
        split[name] = time.perf_counter() - t
        return time.perf_counter()

    # ---- set-up ----------------------------------------------------------
    t = time.perf_counter()
    jax.block_until_ready(jax.jit(lambda: jax.numpy.zeros(()))())
    t = lap("device_s", t)                  # the runtime's first program
    data = build.make_data(cell.traffic, args.seed)
    t = lap("data_s", t)
    # before the cell's own state is placed: the check engine and the
    # reference then have the chip to themselves, one after the other
    check = correctness.check_round(cell.config, cell.traffic, data, args.seed,
                                    knobs["check"])
    t = lap("check_s", t)
    engine = build.make_engine(cell.config, cell.traffic, data, args.seed)
    t = lap("build_s", t)
    state = loop.State(engine, build.init_variables(engine), args.seed)
    jax.block_until_ready(state.variables)
    t = lap("init_s", t)
    if not engine.streaming:
        # the resident population's first placement, which _round_args(0)
        # would otherwise hide inside the first warm-up round
        jax.block_until_ready(engine._device_stack())
    t = lap("place_s", t)
    c0 = compiles.seconds
    warm = loop.run_rounds(state, knobs["in_flight_rounds"],
                           rounds=knobs["warmup_rounds"])
    split["compile_s"] = compiles.seconds - c0
    split["warmup_s"] = time.perf_counter() - t - split["compile_s"]
    norm = jax.jit(lambda v: sum(jax.numpy.sum(jax.numpy.abs(a))
                                 for a in jax.tree.leaves(v)))
    norm_before = float(norm(state.variables))
    engine.transfer_stats.reset()     # a reader of the program's counters sees the window only
    n_compiles = compiles.count
    setup_s = time.perf_counter() - T_START

    # ---- the window ------------------------------------------------------
    depth = knobs["in_flight_rounds"]
    trace_dir = None
    if args.trace:
        trace_dir = os.path.join(manifest.ROOT, ".fedbench_out", "trace",
                                 cell.name)
        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0      # the harness's spans are TraceMes
        options.enable_hlo_proto = False
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            # the window's clock bounds the trace; a mix whose rounds are so
            # long that a few of them fill the profiler's buffer caps the
            # count in its own file (``trace_rounds``)
            win = loop.run_rounds(
                state, depth, rounds=cell.traffic.get("trace_rounds"),
                seconds=min(args.seconds, knobs["trace_seconds"]))
        finally:
            jax.profiler.stop_trace()
    else:
        win = loop.run_rounds(state, depth, seconds=args.seconds)
    loop.join_prefetch(engine)
    window_compiles = compiles.count - n_compiles
    norm_after = float(norm(state.variables))

    # ---- the line --------------------------------------------------------
    completed = len(win["done_t"]) - win["failed"]
    samples = _real_samples(engine, data, state.next_round - win["attempted"],
                            win["attempted"])
    first_loss = warm["losses"][0] if warm["losses"] else float("nan")
    correct = bool(
        check["ok"] and window_compiles == 0 and win["failed"] == 0
        and win["attempted"] > 0 and norm_after != norm_before
        and correctness.loss_in_band(first_loss, check["reference_loss"]))
    ctx = {"cell": cell, "engine": engine, "data": data, "window": win,
           "setup_s": setup_s, "completed": completed, "samples": samples,
           "device": doc, "on_chip": on_chip, "trace": None,
           "params": state.variables["params"],
           "memory_peak_bytes": device.memory_peak_bytes(cell.chips)}
    dev = {"platform": doc["platform"], "kind": doc["kind"],
           "count": cell.chips if on_chip else doc["count"],
           "memory_peak_bytes": ctx["memory_peak_bytes"]}
    out = {"correct": correct, "attempted": win["attempted"],
           "failed": win["failed"]}
    if args.trace:
        from fedbench.harness import trace_reduce
        ctx["trace"] = trace_reduce.reduce_dir(trace_dir, cell.chips)
        if ctx["trace"] is not None and on_chip:
            dev["busy_s"] = ctx["trace"]["busy_s"]
            dev["window_s"] = ctx["trace"]["window_s"]
            out["breakdown"] = ctx["trace"]["breakdown"]
    group = "per_layer" if args.trace else "end_to_end"
    from fedbench import layer_metrics
    metrics = {}
    for m in cell.metrics(group):
        value = layer_metrics.read(m, ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    out["metrics"], out["device"] = metrics, dev
    detail = {"setup_split": split,
              "memory_stats": jax.devices()[0].memory_stats(), "check": check, "first_loss": first_loss,
              "window_compiles": window_compiles, "rounds_completed": completed,
              "elapsed_s": win["elapsed_s"], "real_samples": samples}
    if ctx["trace"] is not None:
        detail["trace"] = {k: ctx["trace"][k] for k in (
            "rounds", "categories_s", "idle_by_span_s", "top_ops_s")}
        detail["roofline_bound"] = ctx.get("roofline_bound")
    print("fedbench detail " + json.dumps(detail), flush=True)
    print(json.dumps(out), flush=True)
    return 0


def _real_samples(engine, data, first_round: int, n_rounds: int) -> float:
    """Real (mask = 1) training samples of the window's rounds: the sampler
    is a pure function of the round index, so the cohorts are replayed."""
    sizes = data.client_num_samples
    return float(sum(sizes[engine.sampler.sample(r)].sum()
                     for r in range(first_round, first_round + n_rounds)))


if __name__ == "__main__":
    sys.exit(main())
