#!/usr/bin/env python
"""Quickest proof that the system still starts on the chip.

    python chip_smoke.py              # one TPU chip: kernels, train-128,
                                      # train-512, serve — BERT-Large
    python chip_smoke.py --chips 4    # four chips: data-parallel ZeRO-1
                                      # training vs the same run on one
    python chip_smoke.py --rehearse   # same control flow on the CPU at a
                                      # tiny size (finds wrong paths, not
                                      # wrong kernels)

Drives the main path once through the entry points a user would call
(`run_pretraining.py`, `run_server.py`, `scripts/make_serving_fixture.py`,
`tools/kernel_parity.py`) at the full width and depth of
configs/bert_large_uncased_config.json, on data and weights made from
`--seed` inside the run — no network, no checkpoint, no git checkout needed.

This process never touches the JAX backend: a chip belongs to one process at
a time, so every phase runs as one child that owns the chip until it exits,
and phases run one after another. Each phase checks what came out (finite
flat-or-falling losses, compile counts flat after warm-up, the kernels named
in the compiled HLO, 2xx answers, a clean drain); the first failure ends the
run. Everything worth reading goes on earlier lines; the LAST stdout line is
one JSON object,

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

with the device as JAX reports it to the children. Anything but a TPU is a
failure (`"ok": false`, exit 1) unless `--rehearse` asked for the CPU; under
it the line still names the platform the phases really ran on.

Where the compile cache goes is decided outside (bert_pytorch_tpu/
compile_cache.py): `JAX_COMPILATION_CACHE_DIR` if set, else
`<checkout>/.jax_cache`. train-128 runs twice — six steps, then a resumed
session from its checkpoint — and prints both sessions' compile seconds and
the second one's persistent-cache hits, so a cache that never hits is
visible.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import re
import shutil
import signal
import subprocess
import sys
import tempfile
import time
import traceback
import urllib.error
import urllib.request

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
LARGE_CONFIG = os.path.join(REPO, "configs", "bert_large_uncased_config.json")
TIME_LIMIT_S = 1150.0       # the contract allows 1200 s, compilation included
CLS, SEP, MASK = 101, 102, 103     # bert-base/large-uncased vocab ids

# The phase-1 / phase-2 recipe shapes (configs/bert_pretraining_phase{1,2}_
# config.json) at a local batch one 16 GB chip holds, with a short
# accumulation; lr / warmup / max_steps are the recipes' own, so the few steps
# taken here sit at the very start of warm-up. Compiled for a described v5e,
# the default scan-stacked step at seq 128 wants 17.0 GB un-rematted at local
# batch 64 (and the recipe's 96 more): 64 runs with --checkpoint_activations
# (7.8 GB). Phase 2's local batch 16 fits as it is.
FULL = {
    "model_config": LARGE_CONFIG,
    "parity": [],                                   # tool defaults: S512 H16
    "train-128": dict(seq=128, max_pred=20, local_batch=64, accum=2,
                      steps=6, resume_steps=2, samples=1024,
                      lr=6e-3, warmup=0.2843, max_steps=7038),
    "train-512": dict(seq=512, max_pred=80, local_batch=16, accum=2,
                      steps=6, samples=512,
                      lr=4e-3, warmup=0.128, max_steps=1563),
    "serve": dict(tasks=["squad", "classify"], buckets="64,128,256,512",
                  long_words=440),
    "dp": dict(seq=128, max_pred=20, global_batch=64, steps=4, samples=512,
               lr=6e-3, warmup=0.2843, max_steps=7038),
}
# --rehearse: the same phases and checks on a 2-layer toy (D=64 heads, so the
# flash layout logic is the real one), kernels in interpret mode
REHEARSAL = {
    "model_config": None,       # written into the work dir, see _toy_config
    "parity": ["--batch", "1", "--seq", "256", "--heads", "2"],
    "train-128": dict(FULL["train-128"], local_batch=4, samples=64),
    "train-512": dict(FULL["train-512"], local_batch=2, samples=64),
    "serve": dict(FULL["serve"], long_words=300),
    "dp": dict(FULL["dp"], global_batch=8, samples=64),
}
TOY_CONFIG = {
    "vocab_size": 2048, "hidden_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 2, "intermediate_size": 256,
    "max_position_embeddings": 512, "type_vocab_size": 2,
    "hidden_dropout_prob": 0.1, "attention_probs_dropout_prob": 0.1,
    "next_sentence": True, "tokenizer": "wordpiece", "dtype": "bfloat16",
    "fused_ops": True,
}


class PhaseFailed(Exception):
    """A phase's child failed or a check on its output did not hold."""


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class Run:
    """One chip_smoke run: sizes, work dir, child env, deadline, and the
    children started (so that none outlives the run)."""

    def __init__(self, args):
        self.rehearse = args.rehearse
        self.seed = args.seed
        self.sizes = REHEARSAL if args.rehearse else FULL
        self.deadline = time.monotonic() + TIME_LIMIT_S
        self.work = tempfile.mkdtemp(prefix="chip_smoke_")
        self.children: list = []
        self.device: dict | None = None
        self.env = dict(os.environ, PYTHONUNBUFFERED="1")
        if args.rehearse:
            self.env.update(JAX_PLATFORMS="cpu", BPT_PALLAS_INTERPRET="1")
        self.model_config = self.sizes["model_config"]
        if self.model_config is None:
            self.model_config = self.path("toy_model_config.json")
            with open(self.model_config, "w", encoding="utf-8") as f:
                json.dump(TOY_CONFIG, f)
        with open(self.model_config, encoding="utf-8") as f:
            self.vocab_size = json.load(f)["vocab_size"]

    def path(self, *parts: str) -> str:
        return os.path.join(self.work, *parts)

    def remaining(self) -> float:
        left = self.deadline - time.monotonic()
        if left <= 0:
            raise PhaseFailed(f"out of time ({TIME_LIMIT_S:.0f}s limit)")
        return left

    # -- children -----------------------------------------------------------

    def child_env(self, n_devices: int) -> dict:
        """Env of a child that must see exactly n_devices devices."""
        env = dict(self.env)
        if self.rehearse:
            flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                           env.get("XLA_FLAGS", "")).strip()
            env["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{n_devices}").strip()
        elif n_devices == 1 and self.device and self.device["count"] > 1:
            # one process, one chip of a multi-chip host (libtpu's own
            # process-topology variables)
            env.update(TPU_VISIBLE_CHIPS="0", TPU_VISIBLE_DEVICES="0",
                       TPU_CHIPS_PER_PROCESS_BOUNDS="1,1,1",
                       TPU_PROCESS_BOUNDS="1,1,1")
        return env

    def start(self, name: str, cmd: list, env: dict):
        log = open(self.path(f"{name}.log"), "w", encoding="utf-8")
        proc = subprocess.Popen(cmd, cwd=REPO, env=env, stdout=log,
                                stderr=subprocess.STDOUT)
        proc.smoke_name, proc.smoke_log = name, log
        self.children.append(proc)
        return proc

    def wait(self, proc, timeout: float | None = None) -> int:
        try:
            rc = proc.wait(timeout=min(timeout or math.inf, self.remaining()))
        except subprocess.TimeoutExpired:
            self.stop(proc)
            raise PhaseFailed(
                f"{proc.smoke_name}: still running at its time limit\n"
                + self.tail(proc.smoke_name)) from None
        proc.smoke_log.close()
        return rc

    def run(self, name: str, cmd: list, env: dict) -> str:
        """Run one child to its end; its log on success, PhaseFailed (with
        the log's tail) on a non-zero exit."""
        t0 = time.monotonic()
        rc = self.wait(self.start(name, cmd, env))
        if rc != 0:
            raise PhaseFailed(f"{name}: exit code {rc}\n" + self.tail(name))
        say(f"{name}: child done in {time.monotonic() - t0:.1f}s")
        with open(self.path(f"{name}.log"), encoding="utf-8",
                  errors="replace") as f:
            return f.read()

    def stop(self, proc) -> None:
        if proc.poll() is None:
            proc.terminate()
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()
        proc.smoke_log.close()

    def tail(self, name: str, lines: int = 40) -> str:
        try:
            with open(self.path(f"{name}.log"), encoding="utf-8",
                      errors="replace") as f:
                text = f.read().splitlines()[-lines:]
        except OSError:
            return "(no log)"
        return "\n".join(f"    | {ln}" for ln in text)

    def close(self) -> None:
        for proc in self.children:
            self.stop(proc)
        shutil.rmtree(self.work, ignore_errors=True)

    # -- device -------------------------------------------------------------

    def note_device(self, platform: str, kind: str, count: int,
                    who: str) -> None:
        """Every child reports the device it ran on; they must all agree
        with the probe (a child that quietly fell to another platform is a
        failure, not a footnote)."""
        seen = {"platform": platform, "kind": kind, "count": int(count)}
        if self.device is None:
            self.device = seen
        elif (seen["platform"], seen["kind"]) != (
                self.device["platform"], self.device["kind"]):
            raise PhaseFailed(f"{who} ran on {seen}, the probe saw "
                              f"{self.device}")


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise PhaseFailed(msg)


# -- synthesized data -------------------------------------------------------------


def write_shards(out_dir: str, n_samples: int, seq: int, vocab: int,
                 seed: int, n_shards: int = 2) -> str:
    """HDF5 shards in the reference schema (input_ids,
    special_token_positions, next_sentence_labels; src/dataset.py) holding
    [CLS] a [SEP] b [SEP] pairs of varied real length, zero-padded to seq."""
    import h5py

    os.makedirs(out_dir, exist_ok=True)
    rng = np.random.RandomState(seed)
    per = n_samples // n_shards
    for s in range(n_shards):
        ids = rng.randint(1000, vocab, (per, seq)).astype(np.int32)
        ids[:, 0] = CLS
        specials = np.zeros((per, 3), np.int32)
        for i in range(per):
            last = rng.randint(seq // 4, seq - 1)      # second [SEP]
            sep1 = rng.randint(2, last - 2)
            ids[i, sep1] = ids[i, last] = SEP
            ids[i, last + 1:] = 0
            specials[i] = (0, sep1, last)
        with h5py.File(os.path.join(out_dir, f"shard_{s}.hdf5"), "w") as f:
            f.create_dataset("input_ids", data=ids, compression="gzip")
            f.create_dataset("special_token_positions", data=specials,
                             compression="gzip")
            f.create_dataset("next_sentence_labels", compression="gzip",
                             data=rng.randint(0, 2, (per,)).astype(np.int8))
    return out_dir


# -- reading what a training child wrote ----------------------------------------


def read_jsonl(path: str) -> list:
    with open(path, encoding="utf-8") as f:
        return [json.loads(ln) for ln in f if ln.strip()]


def pretrain_cmd(run: Run, data: str, out: str, *, seq: int, max_pred: int,
                 local_batch: int, global_batch: int, steps: int, lr: float,
                 warmup: float, max_steps: int, extra: list,
                 model_config: str | None = None) -> list:
    return [sys.executable, os.path.join(REPO, "run_pretraining.py"),
            "--model_config_file", model_config or run.model_config,
            "--input_dir", data, "--output_dir", out,
            "--max_predictions_per_seq", str(max_pred),
            "--masked_token_fraction", "0.15",
            "--mask_token_index", str(MASK),
            "--learning_rate", str(lr), "--warmup_proportion", str(warmup),
            "--max_steps", str(max_steps), "--steps", str(steps),
            "--global_batch_size", str(global_batch),
            "--local_batch_size", str(local_batch),
            "--num_steps_per_checkpoint", "100000", "--log_freq", "2",
            "--tensorboard", "off", "--seed", str(run.seed),
            "--log_prefix", "smoke"] + extra


def read_session(run: Run, out: str, who: str, first_new_record: int = 0
                 ) -> dict:
    """Facts of one run_pretraining session from its jsonl log (a resumed
    session appends to the first one's file, and repeats no header that
    has not changed)."""
    every = read_jsonl(os.path.join(out, "smoke.jsonl"))
    records = every[first_new_record:]
    headers = [r for r in every if r["tag"] == "header"]
    check(bool(headers), f"{who}: no header record in its log")
    h = headers[0]
    run.note_device(h.get("platform"), h.get("device_kind"),
                    h.get("device_count", 0), who)
    train = [r for r in records if r["tag"] == "train"]
    perf = [r for r in records if r["tag"] == "perf"]
    check(bool(train) and bool(perf), f"{who}: no train/perf records")
    losses = [r["step_loss"] for r in train]
    check(all(math.isfinite(x) for x in losses),
          f"{who}: non-finite loss in {losses}")
    check(all(r.get("loss_nonfinite", 0) == 0
              and r.get("grad_nonfinite", 0) == 0 for r in train),
          f"{who}: non-finite loss/gradient flags raised")
    kernels = next((r["program_kernels"] for r in reversed(headers)
                    if "program_kernels" in r), None)
    collectives = next((r["program_collectives"] for r in reversed(headers)
                        if "program_collectives" in r), None)
    return {"n_records": len(every),
            "device_count": int(h.get("device_count", 0)),
            "steps": [r["step"] for r in train], "losses": losses,
            "perf": perf, "kernels": kernels, "collectives": collectives}


def check_flat_or_falling(losses: list, who: str) -> None:
    """Random tokens sit at the entropy floor (~ln V + ln 2) and the steps
    are the first of a long warm-up: the loss must not climb. 2% of the
    first loss allows batch-to-batch noise."""
    check(losses[-1] <= losses[0] * 1.02,
          f"{who}: loss rose from {losses[0]:.4f} to {losses[-1]:.4f}")


def check_compiles_flat(perf: list, who: str) -> None:
    """run_pretraining arms the compile watch at the first perf interval
    with three dispatches behind it; from then on the count must not move."""
    check(len(perf) >= 2, f"{who}: fewer than two perf intervals")
    check(perf[-1]["recompiles_after_warmup"] == 0
          and perf[-1]["compiles"] == perf[-2]["compiles"],
          f"{who}: compiled after warm-up: "
          f"{[(p['step'], p['compiles']) for p in perf]}")


def perf_line(perf: list) -> str:
    """Host-clock step time per log interval (no device sync inside an
    interval: a liveness figure, not a measurement)."""
    return ("host ms/step per log interval (the 1st holds the compile) "
            + "/".join(f"{p['step_time_ms']:.0f}" for p in perf))


# -- phases (one chip) ----------------------------------------------------------

_PROBE = ("import json, jax; d = jax.devices(); print('CHIP_SMOKE_PROBE ' + "
          "json.dumps({'platform': d[0].platform, 'kind': d[0].device_kind, "
          "'count': len(d)}))")


def probe_device(run: Run, n_devices: int) -> dict:
    """The device as JAX reports it, asked in a child (this process stays
    off the backend). A probe that fails is a failed run."""
    log = run.run("probe", [sys.executable, "-c", _PROBE],
                  run.child_env(n_devices))
    found = re.findall(r"CHIP_SMOKE_PROBE (\{.*\})", log)
    check(bool(found), "probe: no device line\n" + run.tail("probe"))
    return json.loads(found[-1])


def phase_kernels(run: Run) -> None:
    """Each Pallas kernel of the main path, executed on the device, against
    its XLA reference (tools/kernel_parity.py)."""
    log = run.run("kernels", [sys.executable,
                              os.path.join(REPO, "tools", "kernel_parity.py")]
                  + run.sizes["parity"], run.child_env(1))
    head = re.search(r"kernel_parity: platform=(\S+) kind=(.+?) interpret=",
                     log)
    check(head is not None, "kernels: no device line\n" + run.tail("kernels"))
    run.note_device(head.group(1), head.group(2), 1, "kernels")
    errs = [float(x) for x in re.findall(r"max_err (\S+)", log)]
    say(f"kernels: {len(errs)} kernel-vs-XLA checks within tolerance, "
        f"worst max_err {max(errs):.2e}")


def phase_train_128(run: Run) -> None:
    c = run.sizes["train-128"]
    data = write_shards(run.path("data128"), c["samples"], c["seq"],
                        run.vocab_size, run.seed)
    out = run.path("train128")
    common = dict(seq=c["seq"], max_pred=c["max_pred"],
                  local_batch=c["local_batch"],
                  global_batch=c["local_batch"] * c["accum"], lr=c["lr"],
                  warmup=c["warmup"], max_steps=c["max_steps"],
                  extra=["--checkpoint_activations"])
    env = run.child_env(1)

    run.run("train-128", pretrain_cmd(run, data, out, steps=c["steps"],
                                      **common), env)
    cold = read_session(run, out, "train-128")
    check(cold["device_count"] == 1, "train-128: not on one device")
    check(cold["steps"] == list(range(1, c["steps"] + 1)),
          f"train-128: steps {cold['steps']}")
    check_flat_or_falling(cold["losses"], "train-128")
    check_compiles_flat(cold["perf"], "train-128")
    check(os.path.isdir(os.path.join(out, "pretrain_ckpts",
                                     str(c["steps"]))),
          "train-128: no checkpoint written at the end of the session")
    say(f"train-128: losses {_fmt(cold['losses'])}; "
        f"{perf_line(cold['perf'])}; checkpoint at step {c['steps']}")

    # the same command again: auto-resume from that checkpoint, against the
    # compile cache the first session filled
    log = run.run("train-128-resume",
                  pretrain_cmd(run, data, out, steps=c["resume_steps"],
                               **common), env)
    warm = read_session(run, out, "train-128-resume", cold["n_records"])
    check(f"auto-resumed from step {c['steps']}" in log,
          "train-128-resume: did not resume from the checkpoint")
    last = c["steps"] + c["resume_steps"]
    check(warm["steps"] == list(range(c["steps"] + 1, last + 1)),
          f"train-128-resume: steps {warm['steps']}")
    check_flat_or_falling([cold["losses"][0]] + warm["losses"],
                          "train-128-resume")
    cold_c, warm_c = cold["perf"][-1], warm["perf"][-1]
    say(f"train-128-resume: losses {_fmt(warm['losses'])}")
    say(f"compile cache: cold session {cold_c['compile_secs']:.1f}s in "
        f"{cold_c['compiles']} compiles ({cold_c['compile_cache_hits']} "
        f"cache hits); warm session {warm_c['compile_secs']:.1f}s in "
        f"{warm_c['compiles']} compiles ({warm_c['compile_cache_hits']} "
        f"cache hits)")
    check(warm_c["compile_cache_hits"] >= 1,
          "train-128-resume: no persistent compile-cache hit in a second "
          "session of the same program")


def phase_train_512(run: Run) -> None:
    """Phase-2 shape with packing: the path that takes the flash kernel, the
    fused residual+dropout+LayerNorm kernel and segment ids together."""
    c = run.sizes["train-512"]
    data = write_shards(run.path("data512"), c["samples"], c["seq"],
                        run.vocab_size, run.seed + 1)
    out = run.path("train512")
    run.run("train-512", pretrain_cmd(
        run, data, out, seq=c["seq"], max_pred=c["max_pred"],
        local_batch=c["local_batch"],
        global_batch=c["local_batch"] * c["accum"], steps=c["steps"],
        lr=c["lr"], warmup=c["warmup"], max_steps=c["max_steps"],
        extra=["--packing", "--skip_checkpoint"]), run.child_env(1))
    s = read_session(run, out, "train-512")
    check(s["device_count"] == 1, "train-512: not on one device")
    check(s["steps"] == list(range(1, c["steps"] + 1)),
          f"train-512: steps {s['steps']}")
    check_flat_or_falling(s["losses"], "train-512")
    check_compiles_flat(s["perf"], "train-512")
    check(s["kernels"] is not None,
          "train-512: the step program's kernel inventory never reached the "
          "log header")
    say(f"train-512: losses {_fmt(s['losses'])}; "
        f"{perf_line(s['perf'])}; kernels in the compiled step: "
        f"{s['kernels'] or '(none)'}")
    if run.device["platform"] == "tpu":
        # a run that quietly took the XLA path fails here (a CPU rehearsal
        # runs the kernels in interpret mode: no Mosaic call to find)
        names = set(k.split("=")[0] for k in s["kernels"].split())
        want = {"flash_fwd", "flash_bwd_dqkv", "add_dropout_layernorm_fwd",
                "add_dropout_layernorm_bwd", "layernorm_fwd", "layernorm_bwd"}
        check(want <= names, f"train-512: compiled step lacks "
                             f"{sorted(want - names)} (has {sorted(names)})")


def _http(method: str, url: str, body: dict | None = None,
          timeout: float = 120.0):
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(url, data=data, method=method, headers={
        "Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, r.read().decode("utf-8", "replace")
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode("utf-8", "replace")


def _metric(text: str, name: str, **labels: str) -> float:
    """Sum of a Prometheus family's samples whose labels include `labels`."""
    total = 0.0
    for ln in text.splitlines():
        m = re.match(rf"{name}(\{{[^}}]*\}})?\s+(\S+)$", ln)
        if m and all(f'{k}="{v}"' in (m.group(1) or "")
                     for k, v in labels.items()):
            total += float(m.group(2))
    return total


def phase_serve(run: Run) -> None:
    c = run.sizes["serve"]
    fx = run.path("fixture")
    # the checkpoints are built on the host CPU in a process of its own (the
    # script pins JAX_PLATFORMS=cpu itself), through the serving contract
    run.run("serve-fixture", [
        sys.executable, os.path.join(REPO, "scripts",
                                     "make_serving_fixture.py"),
        "--out", fx, "--model_config_file", run.model_config,
        "--tasks", *c["tasks"], "--seed", str(run.seed)], run.env)
    with open(os.path.join(fx, "serve_args.txt"), encoding="utf-8") as f:
        serve_args = f.read().split("\n")[:-1]
    port_file = run.path("port")
    server = run.start("serve", [
        sys.executable, os.path.join(REPO, "run_server.py"), *serve_args,
        "--buckets", c["buckets"], "--host", "127.0.0.1", "--port", "0",
        "--port_file", port_file, "--output_dir", run.path("serve_out")],
        run.child_env(1))
    t0 = time.monotonic()
    while not os.path.exists(port_file):     # written once every bucket of
        run.remaining()                      # every task is compiled
        if server.poll() is not None:
            raise PhaseFailed(f"serve: server exited {server.returncode} "
                              "during warm-up\n" + run.tail("serve"))
        time.sleep(0.5)
    with open(port_file, encoding="utf-8") as f:
        url = f"http://127.0.0.1:{int(f.read())}"
    say(f"serve: warm after {time.monotonic() - t0:.1f}s "
        f"(tasks {c['tasks']}, buckets {c['buckets']})")
    header = read_jsonl(os.path.join(run.path("serve_out"),
                                     "serve_log.jsonl"))[0]
    run.note_device(header.get("platform"), header.get("device_kind"),
                    header.get("device_count", 0), "serve")

    _, before = _http("GET", url + "/metrics")
    words = ("the cat sat on mat a dog did run in park red blue green "
             "fast slow").split()
    rng = np.random.RandomState(run.seed)
    text = lambda n: " ".join(rng.choice(words, n))  # noqa: E731
    payloads = {
        "squad": lambda n: {"question": "who sat on the mat ?",
                            "context": text(n) + " ."},
        "classify": lambda n: {"text": text(n) + " ."},
    }
    latencies = {}
    for task in c["tasks"]:
        # three short requests, one that must ride the largest bucket, and
        # the first one again (the engine is deterministic)
        bodies = [payloads[task](n) for n in (9, 24, 40, c["long_words"])]
        answers = []
        for body in bodies + bodies[:1]:
            t1 = time.monotonic()
            status, resp = _http("POST", f"{url}/v1/{task}", body)
            latencies.setdefault(task, []).append(
                (time.monotonic() - t1) * 1e3)
            check(200 <= status < 300, f"serve: /v1/{task} -> {status}: "
                                       f"{resp[:300]}")
            answers.append(json.loads(resp))
        key = "answer" if task == "squad" else "scores"
        check(all(key in a for a in answers),
              f"serve: /v1/{task} response lacks {key!r}: {answers[0]}")
        check(answers[0][key] == answers[-1][key],
              f"serve: /v1/{task} answered the same request differently")
        if task == "classify":
            check(all(math.isfinite(p) for a in answers
                      for p in a["scores"].values()),
                  "serve: non-finite classify scores")
    _, health = _http("GET", url + "/healthz")
    health = json.loads(health)
    check(health.get("status") == "ok", f"serve: /healthz status "
                                        f"{health.get('status')!r}")
    check(sorted(health.get("tasks", [])) == sorted(c["tasks"]),
          f"serve: /healthz tasks {health.get('tasks')}")
    _, after = _http("GET", url + "/metrics")
    top = c["buckets"].split(",")[-1]
    for task in c["tasks"]:
        check(_metric(after, "bert_serve_batches_total", task=task,
                      bucket=top) >= 1,
              f"serve: no {task} batch rode bucket {top}")
    n_compiles = _metric(after, "bert_xla_compiles_total")
    check(n_compiles == _metric(before, "bert_xla_compiles_total"),
          "serve: compiled while answering requests")
    say("serve: request ms " + "; ".join(
        f"{t} {'/'.join(f'{x:.0f}' for x in ms)}"
        for t, ms in latencies.items())
        + f"; {n_compiles:.0f} compiles, all before the first request")

    server.send_signal(signal.SIGTERM)
    rc = run.wait(server, timeout=60)
    check(rc == 0, f"serve: exit code {rc} after SIGTERM\n"
                   + run.tail("serve"))
    with open(run.path("serve.log"), encoding="utf-8",
              errors="replace") as f:
        check("drain: admission stopped" in f.read(),
              "serve: no drain on SIGTERM")
    say("serve: SIGTERM -> drained, exit 0")


# -- four chips -----------------------------------------------------------------


def phase_dp4(run: Run) -> None:
    """Data-parallel ZeRO-1 training over four chips (`--mesh data=4`; on
    real chips `--mesh_config auto` resolves to the production pack:
    packing + ZeRO-1 + gather-on-use) against the same run on one chip.

    Same seed, shards, global batch and data layout (packing on in both) in
    both arms, run one after the other. Dropout is OFF in both: its masks
    are decorrelated per shard (ops/attention._flash_sharded,
    ops/layernorm._adln_sharded), so with it on the two arms would train on
    different noise and their losses could only be compared statistically.
    Without it the arms compute the same function and differ by reduction
    order in bf16: per-step losses must agree within 2^-8 (bf16's epsilon)
    relative."""
    c = run.sizes["dp"]
    with open(run.model_config, encoding="utf-8") as f:
        cfg = dict(json.load(f), hidden_dropout_prob=0.0,
                   attention_probs_dropout_prob=0.0)
    model_config = run.path("model_config_nodropout.json")
    with open(model_config, "w", encoding="utf-8") as f:
        json.dump(cfg, f)
    data = write_shards(run.path("data_dp"), c["samples"], c["seq"],
                        run.vocab_size, run.seed + 2)
    arms = {}
    for name, n_dev in (("dp4", 4), ("dp1", 1)):
        out = run.path(name)
        extra = ["--mesh", f"data={n_dev}", "--skip_checkpoint", "--packing",
                 "--checkpoint_activations"]  # 64 rows on the one-chip arm
        if run.rehearse and n_dev > 1:
            # `auto` keeps the base config on a forced-CPU mesh; the chips
            # get the production pack by themselves
            extra += ["--mesh_config", "production"]
        log = run.run(name, pretrain_cmd(
            run, data, out, seq=c["seq"], max_pred=c["max_pred"],
            local_batch=c["global_batch"] // n_dev,
            global_batch=c["global_batch"], steps=c["steps"], lr=c["lr"],
            warmup=c["warmup"], max_steps=c["max_steps"], extra=extra,
            model_config=model_config), run.child_env(n_dev))
        s = read_session(run, out, name)
        check(s["device_count"] == n_dev,
              f"{name}: ran on {s['device_count']} device(s)")
        check_flat_or_falling(s["losses"], name)
        s["log"] = log
        arms[name] = s
        say(f"{name}: losses {_fmt(s['losses'])}; "
            f"{perf_line(s['perf'])}; collectives {s['collectives']}")

    dp4, dp1 = arms["dp4"], arms["dp1"]
    check("mesh_config=production" in dp4["log"]
          and "zero1=on" in dp4["log"] and "zero1_overlap=on" in dp4["log"],
          "dp4: the production pack (zero1 + overlap) did not resolve")
    tol = 2.0 ** -8
    worst = max(abs(a - b) / abs(b)
                for a, b in zip(dp4["losses"], dp1["losses"]))
    check(worst <= tol, f"dp4 vs dp1: per-step losses differ by {worst:.2e} "
                        f"relative (> {tol:.2e}): {dp4['losses']} vs "
                        f"{dp1['losses']}")
    say(f"dp4 vs dp1: per-step losses agree within {worst:.2e} relative "
        f"(tolerance 2^-8 = {tol:.2e})")

    placed = json.loads(re.search(r"state placement: (\{.*\})",
                                  dp4["log"]).group(1))
    one = json.loads(re.search(r"state placement: (\{.*\})",
                               dp1["log"]).group(1))
    opt_total = sum(one["opt_state"].values())
    for tree in ("params", "opt_state"):
        check(len(placed[tree]) == 4 and min(placed[tree].values()) > 0,
              f"dp4: {tree} not on four devices: {placed[tree]}")
    shares = [b / opt_total for b in placed["opt_state"].values()]
    check(all(0.2 <= s <= 0.3 for s in shares),
          f"dp4: optimizer state per device is {_fmt(shares)} of the "
          "unsharded state, want about a quarter")
    say("dp4: optimizer state per device / unsharded: "
        f"{_fmt(shares)}; params bytes per device "
        f"{list(placed['params'].values())}")

    # the collectives the graph budget expects of this combo
    # (results/graph_budgets.json, zero1_overlap_dp8): ZeRO-1 with
    # gather-on-use emits per-leaf all-gathers and gradient reductions,
    # each kind within its ceiling. The ceilings were derived on the CPU
    # mesh; two kinds are reported, not gated, because the TPU partitioner
    # lowers the same sharding differently there: a reduction whose result
    # is sliced may become the reduce-scatter it is, and re-laying the
    # quarter-slices of the concatenated embedding table costs two small
    # halo collective-permutes (seen identically in the sandbox compile
    # for a described v5e:2x2).
    with open(os.path.join(REPO, "results", "graph_budgets.json"),
              encoding="utf-8") as f:
        budget = json.load(f)["combos"]["zero1_overlap_dp8"]["expect"][
            "collective_budget"]
    check(dp4["collectives"] is not None,
          "dp4: the step program's collective inventory never reached the "
          "log header")
    counts = {k: int(v) for k, v in
              (kv.split("=") for kv in dp4["collectives"].split())}
    check(counts.get("all-gather", 0) > 0
          and counts.get("all-reduce", 0) + counts.get("reduce-scatter", 0)
          > 0, f"dp4: no gather / reduction collectives in {counts}")
    gated = ("all-gather", "all-reduce", "all-to-all")
    for kind in gated:
        check(counts.get(kind, 0) <= budget[kind],
              f"dp4: {counts.get(kind, 0)} {kind} > the budget's "
              f"{budget[kind]}")
    check(not (dp1["collectives"] or "").strip(),
          f"dp1: collectives in a one-device program: {dp1['collectives']}")
    say("dp4: collectives within the zero1_overlap_dp8 ceilings "
        f"{ {k: budget[k] for k in gated} }; reported, not gated: "
        f"{ {k: v for k, v in counts.items() if k not in gated} }")


def _fmt(xs) -> str:
    return "[" + ", ".join(f"{x:.4f}" for x in xs) + "]"


ONE_CHIP_PHASES = {"kernels": phase_kernels, "train-128": phase_train_128,
                   "train-512": phase_train_512, "serve": phase_serve}
FOUR_CHIP_PHASES = {"dp4-vs-dp1": phase_dp4}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=[1, 4], default=1,
                    help="4: run ONLY the four-chip data-parallel check and "
                         "the one-chip arm it is compared with")
    ap.add_argument("--rehearse", action="store_true",
                    help="same control flow on the CPU at a tiny size, "
                         "kernels in interpret mode")
    ap.add_argument("--phases", default=None,
                    help="comma-separated subset of the phases to run")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the synthesized data and weights")
    args = ap.parse_args(argv)
    table = FOUR_CHIP_PHASES if args.chips == 4 else ONE_CHIP_PHASES
    names = args.phases.split(",") if args.phases else list(table)
    unknown = [n for n in names if n not in table]
    if unknown:
        ap.error(f"unknown phase(s) {unknown}; have {list(table)}")

    run = Run(args)
    ok = False
    t0 = time.monotonic()
    try:
        device = probe_device(run, args.chips)
        run.device = device
        platform, count = device["platform"], device["count"]
        say(f"device: {device}; rehearse={args.rehearse}; "
            f"seed={args.seed}; compile cache: "
            + os.environ.get("JAX_COMPILATION_CACHE_DIR",
                             os.path.join(REPO, ".jax_cache")))
        check(platform == ("cpu" if args.rehearse else "tpu"),
              f"no TPU: JAX's platform here is {platform!r} "
              "(--rehearse runs the control flow on the CPU)")
        check(count >= args.chips,
              f"--chips {args.chips} needs {args.chips} device(s), JAX "
              f"sees {count}")
        for name in names:
            say(f"--- phase {name} ---")
            t1 = time.monotonic()
            table[name](run)
            say(f"phase {name}: ok in {time.monotonic() - t1:.1f}s")
        ok = True
    except PhaseFailed as e:
        say(f"FAILED: {e}")
    except Exception:       # a bug in this script is a failed run too
        traceback.print_exc(file=sys.stdout)
        say("FAILED: unexpected error above")
    finally:
        run.close()
    say(f"total {time.monotonic() - t0:.1f}s")
    print(json.dumps({"ok": ok, "device": run.device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
