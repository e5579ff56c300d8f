import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))

"""Perf hillclimb driver: re-run a dry-run cell under optimization levers
and record hypothesis -> before -> after (EXPERIMENTS.md §Perf).

Levers (env-driven, so the baseline stays reproducible):
  attn_bf16   REPRO_ATTN_BF16=1   bf16 QK/PV matmuls, f32 softmax state
  fused_attn  REPRO_FUSED_ATTN=1  Pallas-flash accounting: kernel-internal
                                  tensors VMEM-resident
  chunk<k>    REPRO_FLASH_CHUNK=k larger KV chunks (fewer accumulator
                                  read/write rounds)

Usage:
  PYTHONPATH=src python -m repro.launch.hillclimb --arch X --shape Y \
      --levers attn_bf16,fused_attn [--tag iter1]
"""
import argparse  # noqa: E402
import json  # noqa: E402

from repro.launch.dryrun import run_cell  # noqa: E402

LEVER_ENV = {
    "attn_bf16": ("REPRO_ATTN_BF16", "1"),
    "fused_attn": ("REPRO_FUSED_ATTN", "1"),
    "ar_bf16": ("REPRO_AR_BF16", "1"),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--levers", default="")
    ap.add_argument("--tag", default="opt")
    ap.add_argument("--out", default="experiments/perf")
    args = ap.parse_args()

    levers = [l for l in args.levers.split(",") if l]
    for l in levers:
        if l.startswith("chunk"):
            os.environ["REPRO_FLASH_CHUNK_OPT"] = l[5:]
        else:
            k, v = LEVER_ENV[l]
            os.environ[k] = v

    rec = run_cell(args.arch, args.shape, multi_pod=False,
                   outdir=os.path.join(args.out, args.tag))
    rl = rec.get("roofline", {})
    print(json.dumps({
        "tag": args.tag, "levers": levers,
        "compute_ms": rl.get("compute_s", 0) * 1e3,
        "memory_ms": rl.get("memory_s", 0) * 1e3,
        "collective_ms": rl.get("collective_s", 0) * 1e3,
        "bound": rl.get("bound"), "mfu": rl.get("mfu"),
        "step_ms": rl.get("step_s", 0) * 1e3,
    }, indent=1))


if __name__ == "__main__":
    main()
