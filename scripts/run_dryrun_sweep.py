"""Run the full dry-run sweep: every (arch × shape × mesh) cell as a subprocess.

Cells are ordered cheapest-first (decode < prefill < train; small archs first) so
failures surface early.  Results are cached as JSON files; re-running skips done
cells.  Usage: python scripts/run_dryrun_sweep.py [outdir]
"""

import os
import subprocess
import sys
import time
from pathlib import Path

ARCH_ORDER = [
    "smollm-135m", "mamba2-130m", "musicgen-large", "internvl2-2b",
    "starcoder2-7b", "llama3-8b", "qwen3-14b", "deepseek-moe-16b",
    "jamba-v0.1-52b", "qwen3-moe-235b-a22b",
]
SHAPE_ORDER = ["decode_32k", "long_500k", "prefill_32k", "train_4k"]

def main():
    outdir = Path(sys.argv[1] if len(sys.argv) > 1 else "artifacts/dryrun")
    outdir.mkdir(parents=True, exist_ok=True)
    jobs = []
    for mp in (False, True):
        for shape in SHAPE_ORDER:
            for arch in ARCH_ORDER:
                jobs.append((arch, shape, mp))
    # each child compiles for 512 forced host devices: pin it to the CPU so
    # that on a machine with an accelerator no child tries to open the chip
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    t0 = time.time()
    for i, (arch, shape, mp) in enumerate(jobs):
        tag = f"{arch}__{shape}__{'2x16x16' if mp else '16x16'}"
        if (outdir / f"{tag}.json").exists():
            continue
        cmd = [
            sys.executable, "-m", "repro.launch.dryrun",
            "--arch", arch, "--shape", shape, "--out", str(outdir),
        ]
        if mp:
            cmd.append("--multi-pod")
        print(f"[{i+1}/{len(jobs)}] {tag}  (t={time.time()-t0:.0f}s)", flush=True)
        try:
            subprocess.run(cmd, timeout=3000, check=False, env=env)
        except subprocess.TimeoutExpired:
            (outdir / f"{tag}.json").write_text(
                '{"arch": "%s", "shape": "%s", "mesh": "%s", '
                '"status": "error", "error": "compile timeout 3000s"}'
                % (arch, shape, "2x16x16" if mp else "16x16")
            )
    print(f"sweep done in {time.time()-t0:.0f}s")


if __name__ == "__main__":
    main()
