#!/usr/bin/env python3
"""The process that holds the chip during a benchmark run.

It runs the serving server unchanged, ``infinistore_tpu.serve.main(argv)`` on
the main thread, and adds the three things only the chip's own process can do:

* before: look at the device (a run that finds no TPU stops here, it never
  falls back), and check that weights + cache fill the chip as a deployment;
* during: a side thread starts and stops ``jax.profiler`` when run.py drops a
  control file (not a signal, ``serve`` owns those); with ``--trace 0`` it
  does nothing;
* after ``serve.main`` returns (run.py's SIGTERM): record the peak device
  memory, free the server's arrays, run the plain reference on the probes and
  reduce the trace.  No second runtime start, and nothing timed.

The reference and the counts are the configuration's own
(``harness/family.py``): this file knows of no model family.

Everything goes to files in ``--run-dir``; run.py reads them.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), os.path.join(HERE, "harness"),
                os.path.join(HERE, "trace")]


def write_json(path: str, obj) -> None:
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(obj, f)
    os.replace(tmp, path)      # readers poll for the file: never half-written


def tracer(run_dir: str, stop: threading.Event) -> None:
    """Start/stop the profiler on run.py's word: ``ctl_trace_start`` and
    ``ctl_trace_stop`` appear in the run directory."""
    import jax

    start_f = os.path.join(run_dir, "ctl_trace_start")
    stop_f = os.path.join(run_dir, "ctl_trace_stop")
    while not stop.is_set() and not os.path.exists(start_f):
        time.sleep(0.02)
    if stop.is_set():
        return
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0      # the engine's Python frames would
    opts.host_tracer_level = 2        # swamp the trace and slow the host
    jax.profiler.start_trace(os.path.join(run_dir, "trace"), profiler_options=opts)
    t_on = time.time()
    while not stop.is_set() and not os.path.exists(stop_f):
        time.sleep(0.02)
    t_off = time.time()
    jax.profiler.stop_trace()
    write_json(os.path.join(run_dir, "trace_span.json"),
               {"wall_start": t_on, "wall_stop": t_off})


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--run-dir", required=True)
    ap.add_argument("--config", required=True, help="benchmarks/configs/*.json")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--chips", type=int, default=1)
    ap.add_argument("--rehearse", type=int, default=0)
    ap.add_argument("--control", default="none",
                    help="none, or ref-<precision>: the reference recomputed in "
                         "that lower precision, in the program's place")
    ap.add_argument("serve_argv", nargs=argparse.REMAINDER)
    args = ap.parse_args()
    serve_argv = [a for a in args.serve_argv if a != "--"]
    with open(args.config) as f:
        config = json.load(f)

    import jax

    import costs
    import family

    counts = family.counts(config)
    devs = jax.devices()
    stats = devs[0].memory_stats() or {}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "bytes_limit": int(stats.get("bytes_limit", 0))}
    if not args.rehearse:
        if device["platform"] != "tpu" or len(devs) < args.chips:
            print(f"serve_proc: need {args.chips} TPU chip(s), JAX found "
                  f"{device}: not serving from anything else", file=sys.stderr)
            return 2
        costs.peaks(device["kind"])        # an unknown device is an error
        sv = config["serve"]
        need = (counts.weight_bytes(config) + sv["n_blocks"] * sv["block_tokens"]
                * counts.cache_bytes_per_token(config))
        device["fill"] = need / device["bytes_limit"]
        if device["fill"] < sv["min_fill"]:
            print(f"serve_proc: weights + cache fill {device['fill']:.1%} of "
                  f"the device, under {sv['min_fill']:.0%}", file=sys.stderr)
            return 4
    write_json(os.path.join(args.run_dir, "device.json"), device)

    stop = threading.Event()
    side = threading.Thread(target=tracer, args=(args.run_dir, stop), daemon=True)
    side.start()

    from infinistore_tpu import serve

    serve.main(serve_argv)             # returns after SIGTERM / SIGINT
    stop.set()
    side.join(timeout=30)

    stats = devs[0].memory_stats() or {}
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devs)
    post = {"memory_peak_bytes": peak,
            "bytes_in_use_at_stop": int(stats.get("bytes_in_use", 0))}
    write_json(os.path.join(args.run_dir, "post.json"), post)

    # -- the output check, outside any timed window ------------------------
    probes_f = os.path.join(args.run_dir, "probes.json")
    if os.path.exists(probes_f):
        ref_mod = family.reference(config)
        for a in jax.live_arrays():    # the server is gone; its weights and
            a.delete()                 # cache must not sit beside the reference's
        with open(probes_f) as f:
            probes = json.load(f)
        s = counts.sizes(config)
        t0 = time.time()
        params = ref_mod.draw_weights(s, args.seed)
        ref = ref_mod.reference_logprobs(ref_mod.make_forward(s, "f32"), params, probes)
        check = {"reference": family.reference_name(config),
                 "f32": ref_mod.compare(probes, ref)}
        if args.control.startswith("ref-"):
            # the control: the reference itself one precision down, put in the
            # program's place and held to the same comparison
            low_p = args.control[len("ref-"):]
            low = ref_mod.reference_logprobs(ref_mod.make_forward(s, low_p), params, probes)
            check[f"control_ref_{low_p}"] = ref_mod.compare(
                ref_mod.control_answers(low, probes), ref)
        check["seconds"] = time.time() - t0
        write_json(os.path.join(args.run_dir, "check.json"), check)

    # -- the trace ------------------------------------------------------------
    trace_dir = os.path.join(args.run_dir, "trace")
    if os.path.isdir(trace_dir):
        import shutil

        import reduce as trace_reduce

        tr = trace_reduce.load_xplane(trace_reduce.find_xplane(trace_dir))
        with open(os.path.join(args.run_dir, "trace_describe.txt"), "w") as f:
            f.write(trace_reduce.describe(tr))
        try:
            write_json(os.path.join(args.run_dir, "trace.json"),
                       trace_reduce.reduce(tr))
        except ValueError as e:
            write_json(os.path.join(args.run_dir, "trace.json"), {"error": str(e)})
        if os.environ.get("BENCH_KEEP_TRACE_SAMPLE"):
            small = trace_reduce.load_xplane(
                trace_reduce.find_xplane(trace_dir), max_events_per_line=400)
            write_json(os.path.join(args.run_dir, "trace_sample.json"), small)
        else:
            shutil.rmtree(trace_dir)       # ten seconds are some 100 MB
    return 0


if __name__ == "__main__":
    sys.exit(main())
