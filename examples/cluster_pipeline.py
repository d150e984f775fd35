"""HPC radiomics pipeline: resilient streaming extraction, the xLUNGS story.

The paper's motivation is feature extraction over ~40 000 CT scans on a
cluster.  This driver shows the production pattern for that job, built on
the resilience layer (``runtime/resilience``) over the streaming
plan/executor pipeline:

  * cases flow through as an ITERATOR -- nothing materialises the whole
    batch; the runner mirrors ``extract_stream``'s overlap (host prep of
    window k+1 while the device executes window k);
  * completed features land in a :class:`RunManifest` -- atomic
    append-only JSONL keyed by a CONTENT hash of each mask+spacing, so a
    killed job resumes where it left off even if cases were renamed or
    reordered, redoing at most one window of work;
  * a poisoned case (NaN mask, dead loader) quarantines as a row-level
    ``error`` record instead of killing the run, and ``--retries`` turns
    on backed-off re-submission of a window whose collect hits a
    transient fault;
  * SIGTERM (the cluster preemption notice) is caught by the runner's
    :class:`PreemptionHandler`: the in-flight window drains and commits,
    the open buffer is abandoned, and the next invocation resumes;
  * every window's plan census (shape/cap buckets, pad waste, resolved
    schedule, straggler flag) prints as it drains -- the telemetry a
    cluster operator watches for bucket explosion on heterogeneous
    cohorts;
  * the executor still configures itself (the PR 5 cost-model layer):
    ``--schedule auto`` picks counted vs static per window and
    ``--prep hint`` keeps the submit path free of per-case host syncs --
    all bit-identical to the fixed knobs (tier-1-locked).

    PYTHONPATH=src python examples/cluster_pipeline.py --cases 24
    PYTHONPATH=src python examples/cluster_pipeline.py --cases 24 \\
        --window 8 --schedule static --prep count --retries 2  # pin knobs
"""
import argparse

from repro.core.pipeline import BatchedExtractor
from repro.runtime.compile_cache import use_compile_cache
from repro.data.synthetic import stream_cases
from repro.runtime.resilience import (
    FEATURE_NAMES,  # noqa: F401  (re-export kept for downstream scripts)
    ResilientRunner,
    RetryPolicy,
    RunManifest,
)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cases", type=int, default=16)
    ap.add_argument("--window", type=int, default=8,
                    help="cases per stream window (a kill redoes at most "
                         "one of these)")
    ap.add_argument("--out", default="/tmp/repro_pipeline/features.jsonl")
    ap.add_argument("--variant", default="seqacc")
    ap.add_argument("--schedule", default="auto",
                    choices=("auto", "static", "counted"),
                    help="pass-2b bucket schedule (auto: cost-model-picked "
                         "per window; static: sync-free pass 1)")
    ap.add_argument("--prep", default="hint", choices=("hint", "count"),
                    help="pass-0 cap sizing (hint: metadata-only, "
                         "sync-free; count: per-case measured)")
    ap.add_argument("--retries", type=int, default=2,
                    help="per-window collect retries (0 disables)")
    args = ap.parse_args()
    use_compile_cache()

    def census(widx, s):
        print(f"window {widx}: {s['cases']} cases, "
              f"{s['shape_buckets']} shape buckets, "
              f"{s['cap_buckets']} vertex buckets, "
              f"pad waste mask {s['mask_pad_waste']:.0%} / "
              f"verts {s['vertex_pad_waste']:.0%}, "
              f"schedule={s['schedule']}, {s['seconds']:.2f}s"
              + (", QUARANTINED={}".format(s["quarantined"])
                 if s.get("quarantined") else "")
              + (", STRAGGLER" if s.get("straggler") else ""))

    ext = BatchedExtractor(  # mesh=None: single device
        variant=args.variant, schedule=args.schedule, prep=args.prep,
        retry=RetryPolicy(max_retries=args.retries) if args.retries else None,
    )
    manifest = RunManifest(args.out)
    already = len(manifest.resume())
    if already:
        print(f"resuming: {already} cases already in the manifest")

    runner = ResilientRunner(ext, manifest, window=args.window,
                             stats_callback=census)
    # stream (name, image, mask, spacing); the runner skips done cases
    # by CONTENT id, so renames/reorders of the input cannot double-run
    rep = runner.run(stream_cases(args.cases))
    manifest.close()

    if rep.processed == 0 and rep.status == "complete":
        print(f"nothing to do ({rep.skipped} cases already extracted)")
        return
    log = ext.executor.transfer_log
    print(f"{rep.status}: {rep.processed} rows in {rep.seconds:.1f}s "
          f"({rep.cases_per_second:.2f} cases/s, {rep.windows} windows, "
          f"skipped {rep.skipped} done, quarantined {rep.quarantined}, "
          f"window retries {rep.window_retries}, "
          f"stragglers {len(rep.stragglers)}; "
          f"per-case host syncs: pass0={log.get('prep', 0)} "
          f"pass1={log.get('pass1', 0)})")
    print(f"manifest: {manifest.path}")
    if rep.status == "preempted":
        print("preempted -- re-run the same command to resume")


if __name__ == "__main__":
    main()
