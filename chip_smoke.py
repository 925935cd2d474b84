"""chip_smoke.py — the quickest proof that the system still starts on
the chip.  No benchmark: every timing below is labelled set-up or
steady and is written nowhere under a speed name.

    python chip_smoke.py            # one chip
    python chip_smoke.py --dp 4     # the train stages over a 4-chip mesh

Drives the main path once, through the entry points a user calls, each
stage its OWN child process, one after another (a chip belongs to one
process at a time, so this parent imports neither jax nor veles_tpu):

1. ``train`` (cold): ``python -m veles_tpu -b tpu veles_tpu/models/
   alexnet.py`` at the model's own width — AlexNet-1000, 227x227x3,
   minibatch 128, bf16 compute, f32 params — with only the dataset
   length and epoch count cut.
2. ``train`` again, same command: must add no entry to the compile
   cache and its first dispatch must fall well below the cold one's.
3. ``pack``: a host-only child (pinned to XLA:CPU, numpy engine) builds
   a seeded ensemble package and the host oracle's answers.
4. ``serve``: ``python -m veles_tpu --serve-fleet 1 m=<pkg> -b tpu`` —
   the router parent plus one Hive child on the chip — answers a
   handful of JSONL requests; every answer's rows_n/crc echo must
   verify and agree with the host oracle at bf16 tolerance; shutdown
   must exit 0.

What is printed per stage comes from the child's own outputs (the
--metrics-dir registry and journal, --log-events, the hello).  Any
stage failing, timing out, reporting a platform other than ``tpu`` or
a non-finite loss exits non-zero with no result line.  There is no CPU
mode.  Last stdout line on success:

    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}
"""

import argparse
import glob
import json
import math
import os
import queue
import re
import shutil
import signal
import struct
import subprocess
import sys
import threading
import time
import zlib

HERE = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(HERE, "chiprun_out", "chip_smoke")
#: the whole script, compilation included, against the 1200 s contract
DEADLINE_S = 1100.0
_T0 = time.monotonic()

MINIBATCH = 128
N_TRAIN, N_VALID, EPOCHS = 512, 128, 3
TRAIN_OVERRIDES = [
    f"root.alexnet.loader.n_train={N_TRAIN}",
    f"root.alexnet.loader.n_valid={N_VALID}",
    f"root.alexnet.decision.max_epochs={EPOCHS}",
]
#: where the children keep their compile cache, by the program's rule
#: (backends.py): the operator's directory, else the one in-checkout
#: path.  Each stage's own record must name the same directory.
CACHE_DIR = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
    or os.path.join(HERE, ".jax_cache")
#: bf16 matmuls on the chip vs the f32 host member loop
#: (tests_tpu TestEnsembleEngineOnChip's tolerance)
ORACLE_RTOL, ORACLE_ATOL = 0.05, 0.02

#: the host-only stage: the tests/test_fleet.py package recipe from a
#: seed, plus the request rows and the numpy oracle's answers to them
PACK_SRC = r'''
import json, os, sys, textwrap
import numpy as np
from veles_tpu import prng
from veles_tpu.backends import NumpyDevice
from veles_tpu.ensemble.packaging import pack_ensemble
from veles_tpu.launcher import load_workflow_module

out, seed = sys.argv[1], int(sys.argv[2])
wf_path = os.path.join(out, "wf_smoke.py")
with open(wf_path, "w") as f:
    f.write(textwrap.dedent("""
        from veles_tpu import prng
        from veles_tpu.datasets import synthetic_classification
        from veles_tpu.loader import ArrayLoader
        from veles_tpu.ops.standard_workflow import StandardWorkflow

        def create_workflow(launcher):
            prng.seed_all(4242)
            train, valid, _ = synthetic_classification(
                64, 16, (6, 6, 1), n_classes=3, seed=5)
            return StandardWorkflow(
                loader_factory=lambda w: ArrayLoader(
                    w, train=train, valid=valid, minibatch_size=16,
                    name="loader"),
                layers=[
                    {"type": "all2all_tanh",
                     "->": {"output_sample_shape": 12},
                     "<-": {"learning_rate": 0.1}},
                    {"type": "softmax",
                     "->": {"output_sample_shape": 3},
                     "<-": {"learning_rate": 0.1}},
                ],
                decision_config={"max_epochs": 2}, name="smoke_wf")
    """))


class FL:
    workflow = None


prng.seed_all(seed)
w = load_workflow_module(wf_path).create_workflow(FL())
w.initialize(device=NumpyDevice())
base = {fw.name: {k: np.asarray(v) for k, v in fw.gather_params().items()}
        for fw in w.forwards}
rng = np.random.default_rng(seed)
members = []
for _ in range(3):
    params = {fn: {pn: a + 0.05 * rng.standard_normal(a.shape)
                   .astype(np.float32) for pn, a in p.items()}
              for fn, p in base.items()}
    members.append({"params": params, "valid_error": 0.0, "seed": seed,
                    "forward_names": [fw.name for fw in w.forwards],
                    "values": None})
pkg = os.path.join(out, "smoke.vpkg")
pack_ensemble(pkg, "m", members, wf_path)

requests = []
for i, n_rows in enumerate((1, 2, 3, 4, 2, 1)):
    rows = rng.standard_normal((n_rows, 6, 6, 1)).astype(np.float32)
    acc = 0.0
    for m in members:
        x = rows
        for fw in w.forwards:
            x, _ = fw.apply_fwd(
                {k: np.asarray(v) for k, v in m["params"][fw.name].items()},
                x, rng=None, train=False)
        acc = acc + np.asarray(x, np.float32)
    requests.append({"id": 100 + i, "rows": rows.tolist(),
                     "oracle": (acc / len(members)).tolist()})
with open(os.path.join(out, "requests.json"), "w") as f:
    json.dump({"pkg": pkg, "requests": requests}, f)
'''


class StageFailed(Exception):
    pass


def require(cond, msg):
    if not cond:
        raise StageFailed(msg)


def say(msg):
    print(f"[chip_smoke +{time.monotonic() - _T0:6.1f}s] {msg}",
          flush=True)


def remaining(cap):
    left = DEADLINE_S - (time.monotonic() - _T0)
    if left <= 5:
        raise StageFailed("out of time before the stage could start")
    return min(cap, left)


def tail(path, n=3000):
    try:
        with open(path, "rb") as f:
            return f.read()[-n:].decode("utf-8", "replace")
    except OSError:
        return ""


def child_env(extra=None):
    env = dict(os.environ)
    # every compile is kept, however quick, so "the warm stage adds no
    # entry" is exact instead of depending on which side of JAX's 1 s
    # threshold a compile happened to land
    env.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    env.update(extra or {})
    return env


def spawn(cmd, out_dir, env, stdin=None, stdout=None):
    """One child in its own process group, stderr (and stdout, unless
    piped) kept in ``out_dir``.  The parent must still be off jax: a
    parent that touched it would hold the chip the child needs."""
    require("jax" not in sys.modules and "veles_tpu" not in sys.modules,
            "the chip_smoke parent imported jax or veles_tpu")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "stderr.log"), "wb") as err, \
            open(os.path.join(out_dir, "stdout.log"), "wb") as out:
        return subprocess.Popen(
            cmd, cwd=HERE, env=env, stdin=stdin,
            stdout=stdout if stdout is not None else out,
            stderr=err, start_new_session=True)


def kill_group(proc, name):
    """Nothing a stage started may outlive it: a leftover would still
    hold the chip when the next stage asks for it."""
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except ProcessLookupError:
        return
    say(f"{name}: killed what was left of the process group of pid "
        f"{proc.pid}; if the next stage finds no TPU, the chip is "
        f"still held by it")


def run_stage(name, cmd, cap, env=None):
    out_dir = os.path.join(OUT, name)
    say(f"{name}: " + " ".join(a if "\n" not in a else "<source>"
                               for a in cmd))
    t0 = time.monotonic()
    proc = spawn(cmd, out_dir, child_env(env))
    try:
        rc = proc.wait(timeout=remaining(cap))
    except subprocess.TimeoutExpired:
        rc = None
    kill_group(proc, name)
    proc.wait()
    if rc != 0:
        raise StageFailed(
            f"{name}: " + ("timed out" if rc is None
                           else f"exit code {rc}") + "\n"
            + tail(os.path.join(out_dir, "stderr.log")))
    return round(time.monotonic() - t0, 1)


def read_jsonl(pattern):
    rows = []
    for path in sorted(glob.glob(pattern)):
        with open(path) as f:
            rows += [json.loads(ln) for ln in f if ln.strip()]
    return rows


def read_metrics(pattern):
    paths = glob.glob(pattern)
    require(len(paths) == 1,
            f"expected one metrics file at {pattern}, found {paths}")
    with open(paths[0]) as f:
        return json.load(f)


def cache_entries(path):
    return len(glob.glob(os.path.join(path, "*-cache")))


def require_tpu(name, facts):
    require(facts.get("platform") == "tpu",
            f"{name}: ran on platform {facts.get('platform')!r}, "
            f"not the TPU: {facts}")


# -- train -------------------------------------------------------------

def train_stage(name, dp, cap):
    before = cache_entries(CACHE_DIR)
    mdir = os.path.join(OUT, name, "metrics")
    events_file = os.path.join(OUT, name, "log_events.jsonl")
    cmd = [sys.executable, "-m", "veles_tpu", "-b", "tpu",
           os.path.join("veles_tpu", "models", "alexnet.py"),
           "--metrics-dir", mdir, "--log-events", events_file,
           *TRAIN_OVERRIDES]
    if dp:
        cmd += ["--dp", str(dp)]
    wall = run_stage(name, cmd, cap)

    journal = read_jsonl(os.path.join(mdir, "journal-*.jsonl"))
    first = {e["kind"]: e for e in journal
             if e.get("event") == "fused.first_dispatch"}
    require({"train", "eval"} <= set(first),
            f"{name}: no first-dispatch record for train and eval "
            f"in {mdir}")
    facts = first["train"]
    require_tpu(name, facts)
    require(facts["compute_dtype"] == "bfloat16"
            and facts["batch_shape"] == [MINIBATCH, 227, 227, 3]
            and facts["output_shape"] == [MINIBATCH, 1000],
            f"{name}: not AlexNet-1000 at full width in bf16: {facts}")
    require(facts["engine_devices"] == (dp or 1),
            f"{name}: engine drives {facts['engine_devices']} "
            f"device(s), asked for {dp or 1}")
    require(facts["compile_cache_dir"] == CACHE_DIR,
            f"{name}: compile cache in {facts['compile_cache_dir']}, "
            f"expected {CACHE_DIR}")

    m = read_metrics(os.path.join(mdir, "metrics-*.json"))
    c, g, h = m["counters"], m["gauges"], m["histograms"]
    train_mb = int(c["fused.train_images"]) // MINIBATCH
    require(train_mb >= 8 and c["fused.eval_images"] >= N_VALID,
            f"{name}: ran {train_mb} train minibatches and "
            f"{c['fused.eval_images']} validation images")

    losses = []
    for rec in read_jsonl(events_file):
        mt = re.match(r"epoch (\d+) (train|validation): n_err=\S+ "
                      r"loss=(\S+) ", rec.get("message", ""))
        if rec.get("unit") == "veles.decision" and mt:
            losses.append((int(mt[1]), mt[2], float(mt[3])))
    kinds = [k for _, k, _ in losses]
    require(kinds.count("train") == EPOCHS and "validation" in kinds,
            f"{name}: epochs logged: {losses}")
    require(all(math.isfinite(v) for _, _, v in losses),
            f"{name}: non-finite loss: {losses}")

    summary = [e for e in journal if e.get("event") == "fused.summary"]
    require(summary, f"{name}: no end-of-run summary in the journal")
    memory = summary[-1]["device_memory"]
    require(len(memory) == (dp or 1) and all(
        r["peak_bytes_in_use"] > r["bytes_in_use"] > 0 for r in memory),
        f"{name}: not every device holds bytes and has executed: "
        f"{memory}")
    steady = h.get("fused.train_submit", {})
    rec = {
        "stage": name, "platform": facts["platform"],
        "device_kind": facts["device_kind"],
        "device_count": facts["device_count"],
        "engine_devices": facts["engine_devices"],
        "jax": facts["jax"], "jaxlib": facts["jaxlib"],
        "libtpu": facts["libtpu"],
        "compute_dtype": facts["compute_dtype"],
        "batch_shape": facts["batch_shape"],
        "train_minibatches": train_mb,
        "dispatches": int(c["fused.dispatches"]),
        "losses": [[e, k, round(v, 4)] for e, k, v in losses],
        "setup_first_train_dispatch_s":
            round(g["fused.first_train_submit_seconds"], 3),
        "setup_first_eval_dispatch_s":
            round(g["fused.first_eval_submit_seconds"], 3),
        "steady_train_dispatch_submit_s": {
            "count": steady.get("count", 0),
            "p50": round(steady.get("p50", 0.0), 4)},
        "stage_wall_s": wall,
        "compile_cache_dir": CACHE_DIR,
        "cache_entries_before": before,
        "cache_entries_after": cache_entries(CACHE_DIR),
        "device_memory": memory,
    }
    say(json.dumps(rec))
    return rec


# -- serve -------------------------------------------------------------

def pack_stage():
    out_dir = os.path.join(OUT, "pack")
    os.makedirs(out_dir, exist_ok=True)
    run_stage("pack", [sys.executable, "-c", PACK_SRC, out_dir, "11"],
              cap=120, env={"JAX_PLATFORMS": "cpu"})
    with open(os.path.join(out_dir, "requests.json")) as f:
        return json.load(f)


def _reader(stream, lines):
    for raw in stream:
        lines.put(raw)
    lines.put(None)


def next_json(lines, want, timeout, what):
    """The next stdout object for which ``want(obj)`` holds
    (heartbeats and other traffic pass by)."""
    end = time.monotonic() + timeout
    while True:
        try:
            raw = lines.get(timeout=max(0.1, end - time.monotonic()))
        except queue.Empty:
            raise StageFailed(f"serve: no {what} within {timeout:.0f}s")
        if raw is None:
            raise StageFailed(f"serve: the fleet closed its stdout "
                              f"before {what}")
        try:
            obj = json.loads(raw)
        except ValueError:
            continue
        if want(obj):
            return obj


def check_answer(req, resp):
    rid = req["id"]
    require("error" not in resp, f"serve: request {rid}: {resp}")
    probs, oracle = resp["probs"], req["oracle"]
    flat = [v for row in probs for v in row]
    require(resp["rows_n"] == len(req["rows"]) == len(probs),
            f"serve: request {rid}: rows_n echo {resp['rows_n']} for "
            f"{len(req['rows'])} rows")
    crc = zlib.crc32(struct.pack(f"<{len(flat)}f", *flat))
    require(crc == resp["crc"],
            f"serve: request {rid}: crc {crc} != echoed {resp['crc']}")
    worst = 0.0
    for prow, orow in zip(probs, oracle):
        require(abs(sum(prow) - 1.0) <= ORACLE_ATOL,
                f"serve: request {rid}: probabilities sum to "
                f"{sum(prow)}")
        for p, o in zip(prow, orow):
            require(math.isfinite(p), f"serve: request {rid}: {p}")
            worst = max(worst, abs(p - o))
            require(abs(p - o) <= ORACLE_ATOL + ORACLE_RTOL * abs(o),
                    f"serve: request {rid}: {p} vs host oracle {o}")
    return worst


def serve_stage(packed):
    name = "serve"
    out_dir = os.path.join(OUT, name)
    mdir = os.path.join(out_dir, "metrics")
    before = cache_entries(CACHE_DIR)
    cmd = [sys.executable, "-m", "veles_tpu", "--serve-fleet", "1",
           f"m={packed['pkg']}", "-b", "tpu", "--metrics-dir", mdir,
           "--max-batch", "8", "--max-wait-ms", "5"]
    say(f"{name}: {' '.join(cmd)}")
    t0 = time.monotonic()
    proc = spawn(cmd, out_dir, child_env(), stdin=subprocess.PIPE,
                 stdout=subprocess.PIPE)
    lines = queue.Queue()
    threading.Thread(target=_reader, args=(proc.stdout, lines),
                     daemon=True).start()

    def send(obj):
        proc.stdin.write((json.dumps(obj) + "\n").encode())
        proc.stdin.flush()

    try:
        hello = next_json(lines, lambda o: o.get("ready"),
                          remaining(300), "hello")
        require_tpu(name, hello)
        require(hello["fleet"] == 1 and len(hello["replica_pids"]) == 1
                and hello["replica_pids"][0] != hello["pid"],
                f"serve: not a router parent plus one replica: {hello}")
        worst = 0.0
        for req in packed["requests"]:
            send({"id": req["id"], "model": "m", "rows": req["rows"]})
            resp = next_json(lines,
                             lambda o, i=req["id"]: o.get("id") == i,
                             remaining(120), f"answer {req['id']}")
            worst = max(worst, check_answer(req, resp))
        send({"op": "fleet", "id": 1})
        rows = next_json(lines, lambda o: o.get("id") == 1,
                         remaining(30), "fleet status")["fleet"][
                             "replicas"]
        require(len(rows) == 1 and rows[0]["platform"] == "tpu"
                and rows[0]["healthy"] and rows[0]["deaths"] == 0,
                f"serve: replica rows {rows}")
        send({"op": "shutdown"})
        rc = proc.wait(timeout=remaining(60))
        require(rc == 0, f"serve: shutdown exit code {rc}")
    except (StageFailed, subprocess.TimeoutExpired, OSError) as e:
        raise StageFailed(
            f"{e}\n" + tail(os.path.join(out_dir, "stderr.log")))
    finally:
        kill_group(proc, name)
        proc.wait()

    rdir = os.path.join(mdir, "replica-0")
    ready = [e for e in read_jsonl(os.path.join(rdir, "journal-*.jsonl"))
             if e.get("event") == "serve.ready"]
    require(ready, f"serve: no serve.ready record in {rdir}")
    facts = ready[-1]
    require_tpu(name, facts)
    require(facts["pid"] == hello["replica_pids"][0],
            f"serve: the chip's owner {facts['pid']} is not the "
            f"replica {hello['replica_pids']}")
    m = read_metrics(os.path.join(rdir, "metrics-*.json"))
    steady = m["histograms"].get("serve.dispatch_seconds", {})
    rec = {
        "stage": name, "platform": hello["platform"],
        "device_kind": hello["device_kind"],
        "device_count": facts["device_count"],
        "jax": facts["jax"], "jaxlib": facts["jaxlib"],
        "libtpu": facts["libtpu"],
        "compute_dtype": facts["compute_dtype"],
        "router_pid": hello["pid"], "replica_pid": facts["pid"],
        "replica_device_budget_bytes": rows[0]["device_budget"],
        "requests_answered": len(packed["requests"]),
        "crc_verified": len(packed["requests"]),
        "max_abs_diff_vs_host_oracle": round(worst, 5),
        "setup_first_dispatch_s":
            m["gauges"].get("serve.first_dispatch_seconds"),
        "steady_dispatch_s": {
            "count": steady.get("count", 0),
            "p50": round(steady.get("p50", 0.0), 5)},
        "stage_wall_s": round(time.monotonic() - t0, 1),
        "shutdown_rc": rc,
        "compile_cache_dir": facts["compile_cache_dir"],
        "cache_entries_before": before,
        "cache_entries_after": cache_entries(CACHE_DIR),
    }
    say(json.dumps(rec))
    return rec


# -- main --------------------------------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--dp", type=int, default=0,
                    help="run the train stages with --dp N (fails on "
                         "fewer than N chips; never shrinks the mesh)")
    args = ap.parse_args(argv)
    shutil.rmtree(OUT, ignore_errors=True)
    os.makedirs(OUT)
    try:
        cold = train_stage("train-cold", args.dp, cap=800)
        warm = train_stage("train-warm", args.dp, cap=400)
        require(warm["cache_entries_after"]
                == warm["cache_entries_before"],
                f"train-warm added compile-cache entries "
                f"({warm['cache_entries_before']} -> "
                f"{warm['cache_entries_after']} in {CACHE_DIR}): the "
                f"cache key varies run to run")
        if cold["cache_entries_after"] > cold["cache_entries_before"]:
            require(warm["setup_first_train_dispatch_s"]
                    < 0.5 * cold["setup_first_train_dispatch_s"],
                    f"train-warm first dispatch "
                    f"{warm['setup_first_train_dispatch_s']}s is not "
                    f"well below the cold stage's "
                    f"{cold['setup_first_train_dispatch_s']}s")
        else:
            say("the compile cache was warm before the first stage "
                "(it added no entry): no cold first dispatch to "
                "compare against")
        serve = serve_stage(pack_stage())
        for rec in (warm, serve):
            require((rec["platform"], rec["device_kind"])
                    == (cold["platform"], cold["device_kind"]),
                    f"stages disagree on the device: {cold} vs {rec}")
    except StageFailed as e:
        say(f"FAILED: {e}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": cold["platform"], "kind": cold["device_kind"],
        "count": cold["device_count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
