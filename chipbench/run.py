"""Run one benchmark cell on the TPU this process finds.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  Makes the cell's studies from the seed, warms
every program the cell's traffic uses (set-up), runs the traffic for
``--seconds``, then holds every answer of the window against the plain
reference (``chipbench/reference``).  The last line of standard output is
one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics, or with ``--trace 1`` its per-layer metrics read
from a profiler trace of the window), ``device``, with ``--trace 1`` a
``breakdown``, and last ``check``: each number compared beside its limit,
which also end standard error.  Exits non-zero with no result line when JAX
finds no TPU or fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import compare, harness, reference  # noqa: E402
from chipbench.traffic import generate  # noqa: E402

STATE = os.path.join(ROOT, ".chipbench")  # traces; listed in .gitignore


def _process_age() -> float:
    """Seconds this process had run when ``T0`` was taken (Linux)."""
    try:
        with open("/proc/self/stat") as f:
            start = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return max(0.0, up - start / os.sysconf("SC_CLK_TCK")
                   - (time.perf_counter() - T0))
    except (OSError, ValueError, IndexError):
        return 0.0


def _environment(cfg: dict) -> None:
    """JAX's persistent compilation cache in the checkout, at a fixed path
    and with no size cap (an evicting cache drops entries between runs);
    TPU runtime logs off; then the configuration's own settings."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.update(cfg["env"])


def _log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


def _parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, *, need_tpu: bool = True) -> int:
    age = _process_age()
    args = _parse(argv)
    bench = harness.benchmark()
    cell = harness.cell(bench, args.workload)
    cfg = harness.config(cell["config"])
    traffic = generate.load(cell["traffic"])
    seed = args.seed % 2**64
    _environment(cfg)
    src = os.path.join(ROOT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)

    import jax

    devices = jax.devices()
    if need_tpu and (devices[0].platform != "tpu"
                     or len(devices) < cell["chips"]):
        _log(f"needs {cell['chips']} TPU chip(s); JAX sees "
             f"{len(devices)} {devices[0].platform} device(s)")
        return 3
    dev = devices[0]
    pk = harness.peaks(dev.device_kind) if need_tpu else None

    from repro.runtime.compile_cache import use_compile_cache

    from chipbench.census import Compiles

    use_compile_cache()
    compiles = Compiles()
    cases = generate.build_cases(cfg, traffic, seed)
    order = generate.order(traffic, len(cases), seed)
    driver = harness.load_module("drivers", cfg["driver"]).Driver(
        cfg, cases, order, bool(args.trace))
    sync = jax.jit(lambda x: x + 1)
    sync(0.0).block_until_ready()
    driver.warm()
    sync(0.0).block_until_ready()
    trace_dir = os.path.join(STATE, "trace", args.workload)
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
    setup_s = age + time.perf_counter() - T0
    before = compiles.total
    with jax.profiler.TraceAnnotation("chipbench.window"):
        record = driver.window(args.seconds)
        in_window = compiles.total - before
        sync(0.0).block_until_ready()
    if args.trace:
        jax.profiler.stop_trace()
    compiles.close()
    _log(f"set-up {setup_s:.3f} s, {before} compiles; window "
         f"{record['elapsed_s']:.3f} s, {record['cases']} cases, "
         f"{in_window} compiles in the window")
    top = sorted(compiles.by_program.items(), key=lambda kv: -kv[1][1])[:12]
    _log("compiles by program: " + ", ".join(
        f"{name}={n} ({sec:.1f} s)" for name, (n, sec) in top))
    peak = max(d.memory_stats().get("peak_bytes_in_use", 0)
               for d in devices[:cell["chips"]]) if need_tpu else 0
    driver.close()
    del driver
    gc.collect()

    families = tuple(cfg["extractor"].get("families", ("shape",)))
    t = time.perf_counter()
    refs = [reference.features(c, families) for c in cases]
    found = compare.numbers(cfg["check"], record["answers"], refs)
    correct, table = compare.verdict(cfg["check"], found)
    correct = correct and not record["errors"]
    _log(f"reference {time.perf_counter() - t:.1f} s over {len(cases)} "
         f"studies, {len(record['answers'])} answers compared")
    for err in record["errors"][:5]:
        _log(f"failed: {err}")

    run = harness.Run(cell=cell, config=cfg, seed=seed, seconds=args.seconds,
                      trace=bool(args.trace), cases=cases, record=record,
                      setup_s=setup_s, refs=refs, peaks=pk)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    out = {"correct": bool(correct), "attempted": record["attempted"],
           "failed": len(record["errors"])}
    if args.trace:
        from chipbench import trace

        run.summary = trace.read(trace_dir)
        _log(f"trace: kernel seconds {run.summary.kernel_s}, calls "
             f"{run.summary.kernel_calls}")
        device.update(busy_s=run.summary.busy_s,
                      window_s=run.summary.window_s)
        out["metrics"] = harness.read_metrics(
            run, harness.metrics_for(bench, cell["name"], "per_layer"))
        out["device"] = device
        out["breakdown"] = {"device_ops": run.summary.device_ops,
                            "idle_gaps": run.summary.idle_gaps}
    else:
        out["metrics"] = harness.read_metrics(
            run, harness.metrics_for(bench, cell["name"], "end_to_end"))
        out["device"] = device
    out["check"] = table
    for name, row in table.items():
        _log(f"check {name} = {row['value']!r} (limit {row['limit']!r})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
