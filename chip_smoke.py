#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system starts on the chip.

Drives the three normal entry points once, end to end, on one TPU at
transformer-big width (6+6 layers, emb 1024, ffn 4096, 16 heads, V 32000,
bf16 compute as `--task transformer-big` sets it; weights random from
--seed, corpus a seeded copy task):

  train   marian-train, trainer defaults, one length bucket, ~60 updates,
          one save — cost finite and falling, a committed bundle, and the
          fused-CE kernels in the compiled train step (attention at these
          widths is XLA's dense einsum since PR 52)
  decode  marian-decoder --beam-size 6 (and 1) on that checkpoint — one
          line out per line in, not all empty, the fused decode kernel in
          the compiled search
  serve   marian-server --batching-mode iteration, greedy then beam 6 —
          /readyz, every reply a translation, /poolz?check=1 clean, greedy
          replies equal to the beam-1 decoder's, SIGTERM drains with exit 0

Each phase is ONE CHILD PROCESS that calls the CLI's own main(); this
parent never imports JAX (a process that touched JAX holds the chip).
`--chips 4` runs only the ZeRO-1 data-parallel trainer on four chips and
its one-chip comparison. Without a TPU the script exits non-zero and
prints no result line; this is not the benchmark and prints no rate.

Last stdout line on success:
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
"""

import argparse
import json
import os
import random
import re
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))
EXIT_NO_CHIP = 4        # 2 and 3 are the chip tool's own

BIG = {"emb": 1024, "ffn": 4096, "heads": 16, "depth": 6, "vocab": 32000,
       "words": 4096, "updates": 60, "lr": 0.0002, "min_len": 20,
       "max_len": 31}
# --rehearse only: control flow on any platform, never a result
TINY = {"emb": 64, "ffn": 128, "heads": 4, "depth": 2, "vocab": 512,
        "words": 512, "updates": 100, "lr": 0.003, "min_len": 5,
        "max_len": 7}

# a named pallas_call among a program's custom calls: in compiled HLO text
# (op_name ".../<name>/pallas_call", "jvp(<name>)" under autodiff) and in
# the StableHLO JAX hands the compiler (kernel_name = "<name>")
KERNEL_RE = re.compile(
    r'custom_call_target="tpu_custom_call"[^\n]*?op_name="([^"]*)"'
    r'|@tpu_custom_call\([^\n]*?kernel_name = "([^"]*)"')
KERNELS = ("packed_attention_fwd", "packed_attention_bwd",
           "flash_attention_fwd", "flash_attention_dq", "flash_attention_dkv",
           "fused_ce_fwd", "fused_ce_dx", "fused_ce_dw",
           "decode_attention", "paged_decode_attention")


class SmokeFailure(Exception):
    pass


def kernels_in(program_text):
    """The named Pallas kernels among a program's TPU custom calls."""
    names = set()
    for op_name, kernel_name in KERNEL_RE.findall(program_text):
        names.update(re.split(r"[/()]", op_name) if op_name else [kernel_name])
    return names & set(KERNELS)


def say(msg):
    print(msg, flush=True)


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


# ---------------------------------------------------------------------------
# child: one CLI entry point in its own process
# ---------------------------------------------------------------------------

def child_main(phase, stats_path, rehearse, argv):
    """Call the CLI main() `phase` names, then write what only the process
    that held the chip can know: the device, compile seconds, peak device
    memory, and (trainer) where the optimizer state landed."""
    import jax
    import jax.monitoring

    devs = jax.devices()
    if devs[0].platform != "tpu" and not rehearse:
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{devs[0].platform!r})", file=sys.stderr)
        sys.exit(EXIT_NO_CHIP)
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: compiles.append(secs)
        if name.endswith("backend_compile_duration") else None)
    stats = {"device": {"platform": devs[0].platform,
                        "kind": devs[0].device_kind, "count": len(devs)}}
    groups = []
    if phase == "train":
        from marian_tpu.cli.marian_train import main
        from marian_tpu.training.graph_group import GraphGroup
        # keep the trainer's GraphGroup reachable after main() returns,
        # to read the optimizer state's real placement
        init = GraphGroup.initialize

        def initialize(self, *a, **kw):
            groups.append(self)
            return init(self, *a, **kw)
        GraphGroup.initialize = initialize
    elif phase == "decode":
        from marian_tpu.cli.marian_decoder import main
    else:
        from marian_tpu.cli.marian_server import main
    try:
        main(argv)
    finally:
        stats["compile_s"] = round(sum(compiles), 1)
        stats["n_compiles"] = len(compiles)
        stats["peak_bytes"] = [
            int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in devs]
        if groups:
            from marian_tpu.parallel import collectives, zero
            opt = groups[-1].opt_state
            stats["opt_bytes_per_device"] = zero.optimizer_sweep_bytes(opt)
            stats["opt_logical_bytes"] = zero.optimizer_logical_bytes(opt)
            hlo = argv[argv.index("--dump-hlo") + 1] + ".hlo_opt.txt"
            if os.path.exists(hlo):
                with open(hlo, errors="replace") as fh:
                    stats["collectives"] = {
                        op: v["count"] for op, v in
                        collectives.collective_stats(fh.read()).items()}
        with open(stats_path, "w") as fh:
            json.dump(stats, fh)


# ---------------------------------------------------------------------------
# parent: stdlib only
# ---------------------------------------------------------------------------

class Smoke:
    def __init__(self, args):
        self.args = args
        self.dims = TINY if args.rehearse else BIG
        self.work = tempfile.mkdtemp(prefix="chip_smoke_")
        self.logs = os.path.join(ROOT, "chiprun_out", "chip_smoke")
        os.makedirs(self.logs, exist_ok=True)
        self.procs = []
        self.device = None
        self.vocab = os.path.join(self.work, "vocab.json")
        self.model = os.path.join(self.work, "model.npz")

    # -- processes ----------------------------------------------------------
    def spawn(self, phase, tag, argv):
        """Start one phase child; returns (proc, stats_path, dump_dir).
        JAX dumps every program it hands the compiler into dump_dir —
        before the persistent cache is asked, so a warm cache hides none."""
        stats = os.path.join(self.work, f"{tag}.stats.json")
        dump = os.path.join(self.work, f"{tag}.ir")
        env = dict(os.environ, JAX_DUMP_IR_TO=dump)
        cmd = [sys.executable, os.path.abspath(__file__), "--child", phase,
               "--stats", stats] + (["--rehearse"] if self.args.rehearse
                                    else []) + ["--"] + argv
        log = open(os.path.join(self.logs, f"{tag}.log"), "w")
        proc = subprocess.Popen(cmd, cwd=self.work, env=env, stdout=log,
                                stderr=subprocess.STDOUT,
                                start_new_session=True)
        proc.tag, proc.log = tag, log
        self.procs.append(proc)
        return proc, stats, dump

    def finish(self, proc, stats_path, dump, t0, timeout):
        """Wait for a child; returns its stats + the kernels in the
        programs it handed the compiler."""
        try:
            rc = proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            raise SmokeFailure(f"{proc.tag}: still running after {timeout}s")
        proc.log.close()
        if rc == EXIT_NO_CHIP:
            print("chip_smoke: JAX found no TPU; no result", file=sys.stderr)
            raise SystemExit(EXIT_NO_CHIP)
        check(rc == 0,
              f"{proc.tag}: exit code {rc}\n{self.log_tail(proc.tag)}")
        with open(stats_path) as fh:
            stats = json.load(fh)
        self.device = stats["device"]
        found = set()
        if os.path.isdir(dump):
            for name in os.listdir(dump):
                found |= kernels_in(self.read(dump, name))
            shutil.rmtree(dump, ignore_errors=True)
        stats["kernels"] = sorted(found)
        gib = max(stats["peak_bytes"]) / 2 ** 30
        say(f"{proc.tag}: {time.time() - t0:.1f}s wall, "
            f"{stats['compile_s']}s in {stats['n_compiles']} compiles or "
            f"cache loads, "
            f"peak {gib:.2f} GiB on {stats['device']['kind']}, "
            f"kernels {stats['kernels'] or 'none'}")
        return stats

    def run(self, phase, tag, argv, timeout=900):
        t0 = time.time()
        return self.finish(*self.spawn(phase, tag, argv), t0, timeout)

    @staticmethod
    def read(*path):
        with open(os.path.join(*path), errors="replace") as fh:
            return fh.read()

    def log_tail(self, tag, n=40):
        return "\n".join(self.read(self.logs, f"{tag}.log").split("\n")[-n:])

    def need_kernels(self, found, tag, names):
        if self.device["platform"] != "tpu":
            return          # rehearsal: Pallas runs interpreted off the chip
        missing = [k for k in names if k not in found]
        check(not missing, f"{tag}: {missing} not in the compiled program — "
                           f"a kernel gave way to a reference")

    def close(self):
        for p in self.procs:
            if p.poll() is None:
                os.killpg(p.pid, signal.SIGKILL)
                p.wait()
        shutil.rmtree(self.work, ignore_errors=True)

    # -- data ---------------------------------------------------------------
    def make_data(self):
        """Vocabulary of exactly `vocab` entries and a copy-task corpus
        whose lines all land in one length bucket (one train-step shape)."""
        d, rng = self.dims, random.Random(self.args.seed)
        words = [f"w{i}" for i in range(d["vocab"] - 2)]
        with open(self.vocab, "w") as fh:
            json.dump({"</s>": 0, "<unk>": 1,
                       **{w: i + 2 for i, w in enumerate(words)}}, fh)
        # Zipf-skewed draws over a small head of the vocabulary: a few
        # dozen updates are enough to move mass onto real tokens
        head = words[:min(200, len(words))]
        weights = [1.0 / (r + 1) for r in range(len(head))]

        def lines(n):
            return [" ".join(rng.choices(head, weights, k=rng.randint(
                d["min_len"], d["max_len"]))) for _ in range(n)]
        self.test = lines(32)
        for name, text in (("train", lines(d["words"] // 8)),
                           ("test", self.test)):
            for side in ("src", "trg"):
                path = os.path.join(self.work, f"{name}.{side}")
                with open(path, "w") as fh:
                    fh.write("\n".join(text) + "\n")

    def train_args(self, tag, updates, disp):
        d, w = self.dims, self.work
        return [
            "--type", "transformer", "--dim-emb", str(d["emb"]),
            "--transformer-dim-ffn", str(d["ffn"]),
            "--transformer-heads", str(d["heads"]),
            "--enc-depth", str(d["depth"]), "--dec-depth", str(d["depth"]),
            "--tied-embeddings-all", "--precision", "bfloat16", "float32",
            "--train-sets", f"{w}/train.src", f"{w}/train.trg",
            "--vocabs", self.vocab, self.vocab,
            "--model", f"{w}/{tag}.npz" if tag != "train" else self.model,
            "--overwrite",
            "--mini-batch-words", str(d["words"]),
            # as `--task transformer-big` sets them; the rest are defaults
            "--learn-rate", str(d["lr"]),
            "--cost-type", "ce-mean-words",
            "--after-batches", str(updates), "--disp-freq", f"{disp}u",
            "--seed", str(self.args.seed), "--dump-hlo", f"{w}/{tag}.step"]

    def costs(self, tag):
        return [float(c) for c in re.findall(
            r"Cost ([-+.\de]+|nan|inf)", self.read(self.logs, f"{tag}.log"))]

    # -- phases -------------------------------------------------------------
    def train(self):
        tag, n = "train", self.dims["updates"]
        stats = self.run("train", tag, self.train_args(tag, n, max(1, n // 6)))
        costs = self.costs(tag)
        say(f"train: cost {costs[0]:.4f} -> {costs[-1]:.4f} over {n} updates")
        check(len(costs) >= 2 and all(c == c and abs(c) != float("inf")
                                      for c in costs), f"train: costs {costs}")
        check(costs[-1] < costs[0], f"train: cost did not fall: {costs}")
        bundles = self.model + ".bundles"
        check(os.path.isdir(bundles) and any(
            os.path.exists(os.path.join(bundles, b, "MANIFEST.json"))
            for b in os.listdir(bundles)), "train: no committed bundle")
        # the trainer's own --dump-hlo is the witness here: no kernel of
        # the step it ran gave way to a reference
        dumped = self.read(self.work, f"{tag}.step.hlo_opt.txt")
        self.need_kernels(kernels_in(dumped), tag, [
            "fused_ce_fwd", "fused_ce_dx", "fused_ce_dw"])

    def decode(self, beam):
        tag = f"decode-beam{beam}"
        out = os.path.join(self.work, f"{tag}.out")
        stats = self.run("decode", tag, [
            "--models", self.model, "--vocabs", self.vocab, self.vocab,
            "--beam-size", str(beam), "--normalize", "1",
            "--mini-batch", "16", "--maxi-batch", "10",
            "--input", f"{self.work}/test.src", "--output", out])
        hyps = self.read(out).split("\n")[:-1]
        check(len(hyps) == len(self.test),
              f"{tag}: {len(hyps)} lines out for {len(self.test)} in")
        n_empty = sum(not h.strip() for h in hyps)
        say(f"{tag}: {len(hyps)} lines, {n_empty} empty, "
            f"{sum(h == s for h, s in zip(hyps, self.test))} exact copies")
        check(n_empty < len(hyps), f"{tag}: every translation is empty")
        self.need_kernels(stats["kernels"], tag, ["decode_attention"])
        return hyps

    def serve(self, beam, expect=None):
        sys.path.insert(0, os.path.join(ROOT, "scripts"))
        import asyncio
        import loadgen              # stdlib + websockets client, no JAX
        try:
            import websockets       # noqa: F401 — the server's own choice
            request = loadgen._request_ws
        except ImportError:
            request = loadgen._request_tcp
        tag = f"serve-beam{beam}"
        ports = []
        for _ in range(2):
            with socket.socket() as s:
                s.bind(("127.0.0.1", 0))
                ports.append(s.getsockname()[1])
        port, mport = ports
        t0 = time.time()
        proc, stats_path, dump = self.spawn("serve", tag, [
            "--models", self.model, "--vocabs", self.vocab, self.vocab,
            "--batching-mode", "iteration", "--beam-size", str(beam),
            "--normalize", "1",
            "--port", str(port), "--metrics-port", str(mport)])

        def get(path):
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{mport}{path}", timeout=10) as fh:
                return fh.read().decode()
        while True:
            check(proc.poll() is None,
                  f"{tag}: server died at boot\n{self.log_tail(tag)}")
            check(time.time() - t0 < 600, f"{tag}: not ready after 600s")
            try:
                if get("/readyz").strip() == "ready":
                    break
            except OSError:
                pass
            time.sleep(0.5)
        say(f"{tag}: ready after {time.time() - t0:.1f}s")

        async def traffic():
            # 8-sentence requests: two alone, then two at once (a join
            # into a running decode)
            reqs = ["\n".join(self.test[i:i + 8])
                    for i in range(0, len(self.test), 8)]
            out = [await request("127.0.0.1", port, r) for r in reqs[:2]]
            out += await asyncio.gather(*[request("127.0.0.1", port, r)
                                          for r in reqs[2:]])
            return [line for reply, _ in out for line in reply.split("\n")]
        replies = asyncio.run(asyncio.wait_for(traffic(), 600))
        bad = [r for r in replies if r.startswith("!!SERVER-")]
        check(not bad, f"{tag}: server error replies: {bad[:3]}")
        check(len(replies) == len(self.test),
              f"{tag}: {len(replies)} replies for {len(self.test)} sentences")
        n_empty = sum(not r.strip() for r in replies)
        check(n_empty < len(replies), f"{tag}: every reply is empty")
        pool = json.loads(get("/poolz?check=1"))
        check(pool.get("consistency") == [],
              f"{tag}: /poolz?check=1: {pool.get('consistency')}")
        if expect is not None:
            diff = [i for i, (a, b) in enumerate(zip(replies, expect))
                    if a != b]
            say(f"{tag}: {len(replies) - len(diff)}/{len(replies)} replies "
                f"equal marian-decoder --beam-size {beam}")
            if diff:
                i = diff[0]
                raise SmokeFailure(f"{tag}: sentence {i}: server "
                                   f"{replies[i]!r} != decoder {expect[i]!r}")
        os.killpg(proc.pid, signal.SIGTERM)
        stats = self.finish(proc, stats_path, dump, t0, 120)
        say(f"{tag}: {len(replies)} replies, {n_empty} empty, /poolz clean, "
            f"SIGTERM drained with exit 0")
        self.need_kernels(stats["kernels"], tag, ["paged_decode_attention"])

    def zero1(self):
        """--chips 4: the ZeRO-1 data-parallel trainer on four chips
        against the same updates on one, and nothing else."""
        runs = {}
        for tag, extra in (("train-4chip", ["--devices", "0", "1", "2", "3"]),
                           ("train-1chip", ["--num-devices", "1"])):
            stats = self.run("train", tag,
                             self.train_args(tag, 10, 1) + extra)
            stats["costs"] = self.costs(tag)
            say(f"{tag}: costs {stats['costs']}")
            say(f"{tag}: collectives {stats['collectives']}, optimizer "
                f"bytes per device {stats['opt_bytes_per_device']} of "
                f"{stats['opt_logical_bytes']} logical")
            runs[tag] = stats
        four, one = runs["train-4chip"], runs["train-1chip"]
        check(four["device"]["count"] == 4,
              f"--chips 4 found {four['device']['count']} devices")
        check(len(four["costs"]) == len(one["costs"]) == 10,
              "a run did not display 10 costs")
        # the goldens' rtol (first v5e run, bf16 compute: 2.4e-05)
        rel = max(abs(a - b) / abs(b)
                  for a, b in zip(four["costs"], one["costs"]))
        say(f"zero1: max relative cost difference {rel:.2e} over 10 updates")
        check(rel < 1e-3, f"zero1: cost trajectories differ by {rel:.2e}")
        per_dev, logical = four["opt_bytes_per_device"], \
            four["opt_logical_bytes"]
        check(len(per_dev) == 4, f"zero1: optimizer state on {len(per_dev)} "
                                 f"devices: {per_dev}")
        share = max(per_dev.values()) / logical
        say(f"zero1: largest per-device optimizer share {share:.3f} "
            f"(1/4 = 0.250)")
        check(share < 0.30, f"zero1: a device holds {share:.2f} of the "
                            f"optimizer state")
        check(four["collectives"].get("reduce-scatter", 0) > 0
              and four["collectives"].get("all-gather", 0) > 0,
              f"zero1: step HLO lacks the reduce-scatter/all-gather pair: "
              f"{four['collectives']}")
        check(not one["collectives"],
              "zero1: the one-chip comparison ran collectives")
        for tag in runs:
            self.need_kernels(runs[tag]["kernels"], tag, ["fused_ce_fwd"])

    def main(self):
        self.make_data()
        if self.args.chips == 4:
            self.zero1()
        else:
            self.train()
            self.decode(6)
            greedy = self.decode(1)
            self.serve(1, expect=greedy)
            self.serve(6)
        check(self.device["count"] == self.args.chips,
              f"ran on {self.device['count']} devices, not {self.args.chips}")
        if self.args.rehearse or self.device["platform"] != "tpu":
            say("rehearsal complete: not the real size on a TPU, no result")
            return EXIT_NO_CHIP
        say(json.dumps({"ok": True, "device": self.device}))
        return 0


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=1111)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny widths on any platform: checks control flow, "
                         "never prints a result")
    ap.add_argument("--child", help=argparse.SUPPRESS)
    ap.add_argument("--stats", help=argparse.SUPPRESS)
    ap.add_argument("argv", nargs="*", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child:
        return child_main(args.child, args.stats, args.rehearse, args.argv)
    if not os.path.isdir(os.path.join(ROOT, "marian_tpu")):
        print("chip_smoke: no marian_tpu package beside this script",
              file=sys.stderr)
        return EXIT_NO_CHIP
    smoke = Smoke(args)
    try:
        return smoke.main()
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    finally:
        smoke.close()


if __name__ == "__main__":
    sys.exit(main())
