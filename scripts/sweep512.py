#!/usr/bin/env python
"""seq512 tuning sweep: runs bench.py --child over a grid of flash block
sizes x batch x remat policy (the policy rides the --remat child flag),
each in a fresh subprocess with per-candidate env (FLASH_BLK_Q/K,
BENCH_DROPOUT).

Appends every measurement to results/sweep512.jsonl so an interrupted sweep
keeps its partial results. Run: python scripts/sweep512.py [--steps 20]
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
BENCH = os.path.join(REPO, "bench.py")
OUT = os.path.join(REPO, "results", "sweep512.jsonl")

# (label, batch, attn, remat, env-overrides)
GRID = [
    ("blk512_b16", 16, "auto", False, {}),
    ("blk256_b16", 16, "auto", False, {"FLASH_BLK_Q": "256", "FLASH_BLK_K": "256"}),
    ("blk256q_512k_b16", 16, "auto", False, {"FLASH_BLK_Q": "256", "FLASH_BLK_K": "512"}),
    ("blk512q_256k_b16", 16, "auto", False, {"FLASH_BLK_Q": "512", "FLASH_BLK_K": "256"}),
    ("blk512_b20", 20, "auto", False, {}),
    ("blk512_b24", 24, "auto", False, {}),
    ("blk512_b24_mlponly", 24, "auto", "mlp_only", {}),
    ("blk512_b32_mlponly", 32, "auto", "mlp_only", {}),
    ("blk512_b32_dots", 32, "auto", "dots", {}),
    ("blk512_b48_mlponly", 48, "auto", "mlp_only", {}),
    # diagnostic: dropout-mask cost
    ("blk512_b16_nodrop", 16, "auto", False, {"BENCH_DROPOUT": "0"}),
    # ablation budget map: each knob isolates one subsystem's cost
    ("abl_b16_sgd", 16, "auto", False, {"BENCH_OPT": "sgd"}),
    ("abl_b16_xla_ln", 16, "auto", False, {"BENCH_FUSED": "0"}),
    ("abl_b16_no_attn_drop", 16, "auto", False, {"BENCH_ATTN_DROPOUT": "0"}),
    ("abl_b16_no_hidden_drop", 16, "auto", False,
     {"BENCH_HIDDEN_DROPOUT": "0"}),
]

OOM_MARKERS = ("RESOURCE_EXHAUSTED", "Ran out of memory", "Exceeded hbm",
               "out of memory")


def main():
    steps = "20"
    if "--steps" in sys.argv:
        steps = sys.argv[sys.argv.index("--steps") + 1]
    only = None
    if "--only" in sys.argv:
        only = sys.argv[sys.argv.index("--only") + 1].split(",")
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    done = set()
    if os.path.exists(OUT) and "--fresh" not in sys.argv:
        with open(OUT) as f:
            for line in f:
                try:
                    done.add(json.loads(line)["label"])
                except (ValueError, KeyError):
                    pass

    for label, batch, attn, remat, env_over in GRID:
        if label in done:
            print(f"# {label}: already measured, skipping", file=sys.stderr)
            continue
        if only and label not in only:
            continue
        cmd = [sys.executable, BENCH, "--child", "--batch", str(batch),
               "--steps", steps, "--seq", "512", "--attn", attn,
               "--unroll", "24"]
        cmd += ["--remat", remat if isinstance(remat, str) else "none"]
        env = dict(os.environ, **env_over)
        print(f"# running {label} ...", file=sys.stderr, flush=True)
        try:
            proc = subprocess.run(cmd, capture_output=True, text=True,
                                  timeout=1500, env=env)
        except subprocess.TimeoutExpired:
            rec = {"label": label, "status": "timeout"}
        else:
            rec = {"label": label, "status": "fail",
                   "env": env_over, "batch": batch, "remat": remat}
            for line in proc.stdout.splitlines():
                if line.startswith("BENCH_RESULT "):
                    rec.update(json.loads(line[len("BENCH_RESULT "):]))
                    rec["status"] = "ok"
            if rec["status"] == "fail":
                if any(m in proc.stderr for m in OOM_MARKERS):
                    rec["status"] = "oom"
                else:
                    rec["stderr_tail"] = proc.stderr[-1500:]
        print(json.dumps(rec), flush=True)
        with open(OUT, "a") as f:
            f.write(json.dumps(rec) + "\n")


if __name__ == "__main__":
    main()
