#!/usr/bin/env python
"""Micro load generator for marian-server (ISSUE 1 CI/tooling satellite).
Imports no JAX, so a server in another process keeps the chip.

Drives N concurrent clients against a running server, each sending R
requests of S sentences, and reports client-side p50/p99/mean latency and
throughput plus — when ``--metrics-port`` is given — the server-side batch
fill ratio, batches, and shed/timeout counts scraped from /metrics (delta
over the run, so a long-lived server's history doesn't pollute the numbers).

Transports: ``ws`` (the Marian WebSocket protocol, needs the ``websockets``
package) or ``tcp`` (the dependency-free ``MTPU <nbytes>\\n`` framing the
server falls back to without websockets). ``auto`` picks ws when available.

Example (CPU-backed acceptance run):

    python -m marian_tpu.cli.marian_server --models m.npz \\
        --vocabs v.yml v.yml --port 8765 --metrics-port 9090 \\
        --batch-token-budget 1024 --max-queue 256 &
    python scripts/loadgen.py --port 8765 --metrics-port 9090 \\
        --clients 8 --requests 4 --sentences 4

Streaming mode (``--duration N``, ISSUE 5): constant OPEN-LOOP arrival —
``--rate`` requests/s are fired on schedule for N seconds regardless of
completions, so a serving-side stall shows up as queued latency instead
of quietly throttling the generator (closed-loop clients self-soothe).
Latency is reported per ``--window``-second window (p50/p99/max), which
is how a hot-swap under load becomes visible: a swap that costs anything
shows as a one-window blip instead of averaging away over the run.

Swap-under-load recipe (docs/DEPLOYMENT.md walks through it):

    python -m marian_tpu.cli.marian_server --models m.npz \\
        --vocabs v.yml v.yml --port 8765 --metrics-port 9090 \\
        --model-watch 1 &
    python scripts/loadgen.py --port 8765 --metrics-port 9090 \\
        --duration 60 --rate 8 &
    # mid-run: commit a new bundle (e.g. a training save) and watch the
    # per-window table + the marian_lifecycle_swaps_total delta; zero
    # failed requests and at most a one-window p99 blip is the contract.

Capacity sweep mode (``--sweep "1,2,4,8"``, ISSUE 9 / ROADMAP 4): step
through offered rates (open loop, ``--duration`` seconds each) and
print the capacity table — per-step client p50/p99, shed counts, the
server's chip-seconds/token delta (``marian_perf_*`` integrals) and the
``marian_capacity_headroom_ratio`` reading. Requires ``--metrics-port``
and a server running with ``--perf-accounting`` (the default);
docs/DEPLOYMENT.md "Capacity & autoscaling" interprets the table.

Retries (``--retries N``, ISSUE 11, default 0 = old behavior): a
``!!SERVER-RETRY`` reply (watchdog trip, quiesce-deadline or brownout
row eviction) is resent with capped jittered exponential backoff;
retry/evicted counts are reported per stream window and in the summary.
``--priority N`` sends every request in that lane via the
``#priority:N`` header (brownout level 3 sheds lanes below the server's
``--brownout-min-priority`` first).

Request tracing (ISSUE 8, default ON — ``--no-trace`` to disable): each
request carries a ``#trace:<id>`` header; the server's reply metadata
splits latency into queue wait vs device service per request, reported
as an overall breakdown (closed-loop mode) and as q_p50/q_p99 +
svc_p50/svc_p99 window columns (streaming mode) — so a swap blip is
attributable client-side, and any request's id can be looked up on the
server's ``/tracez`` or in a flight-recorder dump
(docs/OBSERVABILITY.md).
"""

from __future__ import annotations

import argparse
import asyncio
import os
import random
import statistics
import sys
import time
import urllib.request

# ---------------------------------------------------------------------------
# request tracing (ISSUE 8): unless --no-trace, every request carries a
# `#trace:<id>` first line; the server strips it, labels the request's
# span tree with the id, and prepends a reply-metadata line
#   #trace:<id> outcome=.. queue_ms=.. service_ms=.. model_version=..
# so the client can split its measured latency into queue wait vs device
# service — a swap/canary blip becomes attributable CLIENT-side (is the
# p99 bump queueing behind the warmup, or slower decodes on the canary?)
# and the id links to the server's /tracez span tree / flight dumps.
# ---------------------------------------------------------------------------

TRACE_PREFIX = "#trace:"


def make_trace_id(i: int) -> str:
    return f"lg{os.getpid() % 100000:05d}{i:06d}{random.getrandbits(24):06x}"


PRIORITY_PREFIX = "#priority:"

# fleet tenancy (ISSUE 20): --tenants 'A:0.5,B:0.3,C:0.2' stamps a
# deterministic per-request `#model:<tag>` header, so one generator
# drives a --fleet server's N model families in a fixed mix; the
# per-window table and the summary then split by tenant — a cold start
# or brownout on tenant B must show up in B's columns and ONLY B's.
MODEL_PREFIX = "#model:"

# streaming (ISSUE 16): --stream sends the `#stream:1` header; the
# server then delivers `#partial:<idx> <text>` frames as the decode
# progresses, before the normal final reply frame. The client-side
# time-to-first-token (send → first partial) is reported next to ttfj;
# against a non-streaming server no partial ever arrives and the ttft
# columns are NaN-suppressed, mirroring the pool%/cow% convention.
STREAM_PREFIX = "#stream:"
PARTIAL_PREFIX = "#partial:"

RETRY_CAP_S = 2.0       # backoff ceiling per attempt


def retry_backoff_s(attempt: int, base_s: float = 0.1,
                    jitter=random.random) -> float:
    """Capped, jittered exponential backoff for attempt N (0-based):
    base * 2^N, capped at RETRY_CAP_S, scaled by a uniform [0.5, 1.5)
    jitter so a fleet of retrying clients doesn't stampede the replica
    that just evicted them."""
    return min(RETRY_CAP_S, base_s * (2 ** attempt)) * (0.5 + jitter())


async def send_with_retries(request_fn, host: str, port: int, text: str,
                            retries: int, base_s: float = 0.1):
    """Send one request, honoring the server's retriable ``!!SERVER-
    RETRY`` reply (watchdog trip, quiesce-deadline or brownout row
    eviction — ISSUE 11) with capped jittered backoff. Returns
    ``(final_reply, n_retries, ttft_s)`` where n_retries counts the
    RETRY replies received (== resends attempted when the budget
    allows) and ttft_s is the streaming time-to-first-token of the
    FINAL attempt (None without --stream or against a non-streaming
    server); with ``retries=0`` (the default) behavior is exactly the
    old single-shot send."""
    n_retries = 0
    while True:
        reply, ttft = await request_fn(host, port, text)
        _, body = split_reply_meta(reply)
        if not body.startswith("!!SERVER-RETRY") or n_retries >= retries:
            return reply, n_retries, ttft
        await asyncio.sleep(retry_backoff_s(n_retries, base_s))
        n_retries += 1


def split_reply_meta(reply: str):
    """(meta dict | None, body) — parse the server's reply-metadata line.
    queue/service come back in seconds (floats) under 'queue_s'/
    'service_s'; other keys stay strings."""
    if not reply.startswith(TRACE_PREFIX):
        return None, reply
    first, _, body = reply.partition("\n")
    meta = {"trace_id": first.split()[0][len(TRACE_PREFIX):]}
    for part in first.split()[1:]:
        k, _, v = part.partition("=")
        if k.endswith("_ms"):
            # queue_ms/service_ms, and the iteration-mode row breakdown's
            # ttfj_ms (ISSUE 14) — all land as seconds under *_s
            try:
                meta[k[:-3] + "_s"] = float(v) / 1e3
            except ValueError:
                pass
        else:
            meta[k] = v
    return meta, body


# ---------------------------------------------------------------------------
# transports
# ---------------------------------------------------------------------------

async def _request_tcp(host: str, port: int, text: str):
    """(final_reply, ttft_s | None). With --stream the server sends
    `#partial:` frames before the final reply; the first one stamps the
    client-side time-to-first-token. A non-streaming reply is one
    frame, exactly the old protocol."""
    reader, writer = await asyncio.open_connection(host, port)
    try:
        payload = text.encode("utf-8")
        t_send = time.perf_counter()
        writer.write(b"MTPU %d\n" % len(payload) + payload)
        await writer.drain()
        ttft = None
        while True:
            header = await reader.readline()
            if not header.startswith(b"MTPU "):
                raise RuntimeError(f"bad reply frame: {header!r}")
            frame = (await reader.readexactly(
                int(header.split()[1]))).decode("utf-8")
            if frame.startswith(PARTIAL_PREFIX):
                if ttft is None:
                    ttft = time.perf_counter() - t_send
                continue
            return frame, ttft
    finally:
        writer.close()


async def _request_ws(host: str, port: int, text: str):
    import websockets
    async with websockets.connect(f"ws://{host}:{port}") as ws:
        t_send = time.perf_counter()
        await ws.send(text)
        ttft = None
        while True:
            frame = await ws.recv()
            if isinstance(frame, str) and frame.startswith(PARTIAL_PREFIX):
                if ttft is None:
                    ttft = time.perf_counter() - t_send
                continue
            return frame, ttft


# ---------------------------------------------------------------------------
# /metrics scraping (minimal Prometheus text parsing)
# ---------------------------------------------------------------------------

def scrape(host: str, port: int) -> dict:
    """name -> summed value across label children (enough for counters,
    and for histogram _sum/_count series)."""
    url = f"http://{host}:{port}/metrics"
    out: dict = {}
    with urllib.request.urlopen(url, timeout=5) as fh:
        for raw in fh.read().decode("utf-8").splitlines():
            if not raw or raw.startswith("#"):
                continue
            try:
                key, val = raw.rsplit(" ", 1)
                name = key.split("{", 1)[0]
                out[name] = out.get(name, 0.0) + float(val)
            except ValueError:
                continue
    return out


def _delta(before: dict, after: dict, name: str) -> float:
    return after.get(name, 0.0) - before.get(name, 0.0)


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def parse_len_mix(raw: str):
    """--len-mix 'short:long[:p_short]' → (short, long, p_short) or None.
    Bimodal sentence lengths so a mixed-length open-loop run actually
    exercises iteration mode's mid-decode join path: short sentences
    finish and leave a running decode while long ones keep it running,
    so the next arrival joins mid-decode (ISSUE 10 A/B)."""
    if not raw:
        return None
    parts = raw.split(":")
    if len(parts) not in (2, 3):
        raise ValueError(f"--len-mix wants short:long[:p_short], got "
                         f"{raw!r}")
    short, long_ = int(parts[0]), int(parts[1])
    p_short = float(parts[2]) if len(parts) == 3 else 0.7
    if short <= 0 or long_ <= 0 or not 0.0 <= p_short <= 1.0:
        raise ValueError(f"--len-mix values out of range: {raw!r}")
    return short, long_, p_short


def parse_tenants(raw: str):
    """--tenants 'A:0.5,B:0.3,C:0.2' → [(tag, cum_weight)] with weights
    normalized to cumulative [0, 1] boundaries, or None. Tags must be
    the server's #model: alphabet ([A-Za-z0-9_.-]); weights must be
    positive (they need not sum to 1 — the mix is the ratio)."""
    if not raw:
        return None
    entries = []
    for part in raw.split(","):
        part = part.strip()
        if not part:
            continue
        tag, sep, w = part.partition(":")
        tag = tag.strip()
        if not tag or any(not (c.isalnum() or c in "-_.") for c in tag):
            raise ValueError(f"--tenants: bad tag in {part!r}")
        try:
            weight = float(w) if sep else 1.0
        except ValueError:
            raise ValueError(f"--tenants: bad weight in {part!r}")
        if weight <= 0:
            raise ValueError(f"--tenants: weight must be > 0 in {part!r}")
        entries.append((tag, weight))
    if not entries:
        return None
    total = sum(w for _, w in entries)
    out, acc = [], 0.0
    for tag, w in entries:
        acc += w / total
        out.append((tag, acc))
    out[-1] = (out[-1][0], 1.0)        # close the interval exactly
    return out


def tenant_for(i: int, tenant_mix) -> str:
    """Deterministic tenant for request i ('' without --tenants). A
    different hash multiplier than mixed_words' draw, so tenant and
    sentence length stay independent — tenant A must not accidentally
    receive all the short sentences."""
    if not tenant_mix:
        return ""
    u = ((i * 2246822519 + 3) % 1000) / 1000.0
    for tag, cum in tenant_mix:
        if u < cum:
            return tag
    return tenant_mix[-1][0]


def mixed_words(i: int, words: int, len_mix) -> int:
    """Deterministic bimodal length for request i (no RNG state — the
    A/B's two runs see the same traffic)."""
    if len_mix is None:
        return words
    short, long_, p_short = len_mix
    # low-discrepancy threshold draw keyed by i: reproducible mix
    u = ((i * 2654435761) % 1000) / 1000.0
    return short if u < p_short else long_


def make_sentence(client: int, req: int, sent: int, words: int) -> str:
    return " ".join(f"w{(client * 7 + req * 3 + sent + w) % 20}"
                    for w in range(words))


# --prefix-mix: shared-source pool size. Small on purpose — redundant
# traffic (doc re-sends, templated requests, retries) repeats a handful
# of sources many times; that's the regime prefix sharing targets.
PREFIX_POOL = 4


def request_text(args, i: int, words: int) -> str:
    """Body of request ``i``. With --prefix-mix P, a deterministic
    fraction P of requests draw their sentences from a small SHARED
    pool (exact repeats across the run) — the traffic shape the
    server's --prefix-cache turns into page-table hits. Deterministic
    per request index, so A/B runs (cold vs warm cache) see identical
    traffic and must produce identical translations. With --force-mix F
    (checked first), a fraction F are ``source<TAB>prefix`` force-decode
    lines from the same pool — exact (source, trunk) repeats for
    --force-decode + --prefix-cache servers."""
    f = float(getattr(args, "force_mix", 0.0) or 0.0)
    if f > 0.0:
        u = ((i * 69069 + 1) % 1000) / 1000.0
        if u < f:
            # force-decode lines (ISSUE 16): source<TAB>target-prefix,
            # both drawn from the shared pool so (source, trunk) pairs
            # repeat exactly — a --prefix-cache server shares/replays
            # the constrained trunk (the /poolz "forced" cache keys)
            j = i % PREFIX_POOL
            return "\n".join(
                make_sentence(991, j, s, words) + "\t"
                + make_sentence(991, j, s, 2)
                for s in range(args.sentences))
    p = float(getattr(args, "prefix_mix", 0.0) or 0.0)
    if p > 0.0:
        u = ((i * 1103515245 + 12345) % 1000) / 1000.0
        if u < p:
            j = i % PREFIX_POOL
            return "\n".join(make_sentence(991, j, s, words)
                             for s in range(args.sentences))
    return "\n".join(make_sentence(i, i >> 3, s, words)
                     for s in range(args.sentences))


def _apply_headers(args, text: str, i: int) -> str:
    """Stack the protocol headers this run asked for: #trace outermost
    (the server strips it first), then #model, then #priority, then
    #stream — the order server.handle_frame peels them."""
    if getattr(args, "stream", False):
        text = f"{STREAM_PREFIX}1\n" + text
    if getattr(args, "priority", None) is not None:
        text = f"{PRIORITY_PREFIX}{args.priority}\n" + text
    tag = tenant_for(i, getattr(args, "tenant_mix", None))
    if tag:
        text = MODEL_PREFIX + tag + "\n" + text
    if not args.no_trace:
        text = TRACE_PREFIX + make_trace_id(i) + "\n" + text
    return text


async def run_clients(args, request_fn):
    latencies: list = []
    queue_waits: list = []
    service_times: list = []
    errors = {"overloaded": 0, "timeout": 0, "other": 0}

    async def one_client(cid: int):
        for r in range(args.requests):
            text = request_text(args, cid * args.requests + r,
                                args.words)
            text = _apply_headers(args, text, cid * args.requests + r)
            t0 = time.perf_counter()
            try:
                reply, _, _ = await send_with_retries(
                    request_fn, args.host, args.port, text,
                    args.retries, args.retry_base_ms / 1e3)
            except Exception as e:  # noqa: BLE001
                errors["other"] += 1
                print(f"client {cid} req {r}: {e}", file=sys.stderr)
                continue
            dt = time.perf_counter() - t0
            meta, reply = split_reply_meta(reply)
            if reply.startswith("!!SERVER-OVERLOADED"):
                errors["overloaded"] += 1
            elif reply.startswith("!!SERVER-TIMEOUT"):
                errors["timeout"] += 1
            elif reply.startswith("!!SERVER-RETRY"):
                # --retries budget exhausted: a failed request, not a
                # latency sample (run_stream's 'retry' kind, mirrored)
                errors["other"] += 1
            else:
                latencies.append(dt)
                if meta and "queue_s" in meta:
                    queue_waits.append(meta["queue_s"])
                    service_times.append(meta.get("service_s", 0.0))

    t0 = time.perf_counter()
    await asyncio.gather(*[one_client(c) for c in range(args.clients)])
    wall = time.perf_counter() - t0
    return latencies, errors, wall, queue_waits, service_times


def pct(vals, q):
    if not vals:
        return float("nan")
    vals = sorted(vals)
    return vals[min(len(vals) - 1, int(q * len(vals)))]


# ---------------------------------------------------------------------------
# streaming (open-loop) mode: --duration N --rate R
# ---------------------------------------------------------------------------

async def run_stream(args, request_fn, rate=None, duration=None,
                     pool_samples=None):
    """Fire requests at a constant --rate for --duration seconds, start
    times fixed by the schedule (open loop). Returns
    [(t_start_rel, latency_s, kind, queue_s, service_s, n_retries,
    ttft_s, tenant, tokens_sent)] with kind in
    ok/overloaded/timeout/retry/other;
    queue_s/service_s are None without reply metadata (--no-trace);
    ttft_s is the streaming time-to-first-token (None without --stream
    or when the server sent no partials). NOTE: the #trace header is an
    extension of THIS repo's server — against a server without it, the
    header line would be translated as an extra sentence; pass
    --no-trace there.

    ``pool_samples`` (ISSUE 14): a list to receive ~1 Hz
    ``(t_rel, occupancy, cow_alias_ratio)`` scrapes of the server's KV
    pool gauges during the run — the per-window report prints them next
    to the latency percentiles, so a swap/brownout p99 blip is
    attributable to pool pressure from the CLIENT side. Requires
    --metrics-port; gauges absent (request mode) sample as NaN and the
    columns are suppressed."""
    results: list = []
    rate = args.rate if rate is None else rate
    duration = args.duration if duration is None else duration

    len_mix = parse_len_mix(getattr(args, "len_mix", ""))

    async def fire(i: int):
        words = mixed_words(i, args.words, len_mix)
        text = request_text(args, i, words)
        text = _apply_headers(args, text, i)
        tenant = tenant_for(i, getattr(args, "tenant_mix", None))
        tokens = words * args.sentences
        rel = time.perf_counter() - t0
        t = time.perf_counter()
        try:
            # --retries: a retriable eviction (!!SERVER-RETRY — quiesce
            # deadline, brownout, watchdog) is resent with capped
            # jittered backoff; the measured latency is the CLIENT-
            # VISIBLE one, backoff included
            reply, n_retries, ttft = await send_with_retries(
                request_fn, args.host, args.port, text,
                args.retries, args.retry_base_ms / 1e3)
        except Exception as e:  # noqa: BLE001
            results.append((rel, time.perf_counter() - t, "other",
                            None, None, 0, None, tenant, tokens))
            if args.verbose:
                print(f"req {i}: {e}", file=sys.stderr)
            return
        dt = time.perf_counter() - t
        meta, reply = split_reply_meta(reply)
        if reply.startswith("!!SERVER-OVERLOADED"):
            kind = "overloaded"
        elif reply.startswith("!!SERVER-TIMEOUT"):
            kind = "timeout"
        elif reply.startswith("!!SERVER-RETRY"):
            kind = "retry"          # retriable but budget exhausted
        else:
            kind = "ok"
        results.append((rel, dt, kind,
                        meta.get("queue_s") if meta else None,
                        meta.get("service_s") if meta else None,
                        n_retries, ttft, tenant, tokens))

    t0 = time.perf_counter()

    async def sample_pool():
        # blocking urllib scrape on a worker thread so sampling never
        # skews the open-loop firing schedule
        loop = asyncio.get_event_loop()
        while time.perf_counter() - t0 < duration:
            try:
                vals = await loop.run_in_executor(
                    None, scrape, args.host, args.metrics_port)
            except Exception:  # noqa: BLE001 — sampling is best-effort
                vals = {}
            pool_samples.append((
                time.perf_counter() - t0,
                vals.get("marian_serving_kv_pool_occupancy_ratio",
                         float("nan")),
                vals.get("marian_serving_kv_pool_cow_alias_ratio",
                         float("nan"))))
            await asyncio.sleep(1.0)

    sampler = asyncio.ensure_future(sample_pool()) \
        if pool_samples is not None and args.metrics_port else None
    tasks = []
    i = 0
    while True:
        now = time.perf_counter() - t0
        if now >= duration:
            break
        target = i / rate
        if target >= duration:
            break
        if target > now:
            await asyncio.sleep(target - now)
        tasks.append(asyncio.ensure_future(fire(i)))
        i += 1
    if tasks:
        await asyncio.gather(*tasks)
    if sampler is not None:
        sampler.cancel()
        try:
            await sampler
        except asyncio.CancelledError:
            pass
    return results


# ---------------------------------------------------------------------------
# capacity sweep mode: --sweep "1,2,4,8" (ISSUE 9 / ROADMAP 4)
# ---------------------------------------------------------------------------

async def run_sweep(args, request_fn, rates):
    """Step through offered rates (open loop, --duration seconds each),
    recording per-step client latency AND the server's perf-plane
    readings: chip-seconds/token (delta of the device-seconds and token
    integrals over the step) and the capacity headroom gauge after the
    step. The printed table IS the capacity model ROADMAP 4 describes —
    per-model chip-seconds/token under increasing load, and where the
    headroom signal says to scale out."""
    rows = []
    for rate in rates:
        before = scrape(args.host, args.metrics_port)
        t0 = time.perf_counter()
        results = await run_stream(args, request_fn, rate=rate,
                                   duration=args.duration)
        # run_stream gathers the queue DRAIN too — the device seconds in
        # the delta happened over this elapsed span, not args.duration;
        # dividing by the shorter duration would overstate busy and
        # understate headroom at exactly the rates worth measuring
        elapsed = max(time.perf_counter() - t0, args.duration, 1e-9)
        after = scrape(args.host, args.metrics_port)
        lat = [r[1] for r in results if r[2] == "ok"]
        dev = _delta(before, after, "marian_perf_device_seconds_total")
        toks = _delta(before, after, "marian_perf_tokens_total")
        # device_seconds_total is WALL seconds of the device worker;
        # chip-seconds scales by the replica's device count (all chips
        # are reserved while the worker runs) — same factor the
        # marian_perf_chip_seconds_per_token gauge applies
        n_dev = after.get("marian_perf_devices", 1.0) or 1.0
        # STEP-LOCAL headroom from the deltas, not the server's
        # rolling-window gauge: the gauge averages over its whole
        # window (60s default), so with short steps the earlier,
        # lighter rates would contaminate the later steps' readings and
        # overstate sustainable capacity. Queue pressure at step end
        # shows up in the shed/err columns instead.
        busy = min(1.0, dev / elapsed)
        rows.append({
            "rate": rate,
            "offered": len(results),
            "ok": len(lat),
            "shed": sum(1 for r in results if r[2] == "overloaded"),
            "err": sum(1 for r in results
                       if r[2] in ("timeout", "retry", "other")),
            "p50_ms": pct(lat, 0.50) * 1e3,
            "p99_ms": pct(lat, 0.99) * 1e3,
            "chip_s_per_token": dev * n_dev / toks if toks
            else float("nan"),
            "headroom": max(0.0, 1.0 - busy),
            # the server's rolling-window gauge, read back for
            # cross-checking (it lags the step-local number by design)
            "hr_gauge": after.get("marian_capacity_headroom_ratio",
                                  float("nan")),
        })
        # settle between steps so one step's queue does not bleed into
        # the next step's measurements
        await asyncio.sleep(min(2.0, args.duration / 4))
    return rows


def report_sweep(rows) -> None:
    # headroom = step-local (1 - device-busy fraction over the step);
    # hr_gauge = the server's rolling-window marian_capacity_headroom_
    # ratio at step end (lags across short steps by design)
    print(f"{'rate/s':>7} {'offered':>8} {'ok':>6} {'shed':>5} {'err':>5} "
          f"{'p50_ms':>8} {'p99_ms':>8} {'chip_s/tok':>12} "
          f"{'headroom':>9} {'hr_gauge':>9}")
    for r in rows:
        print(f"{r['rate']:>7g} {r['offered']:>8} {r['ok']:>6} "
              f"{r['shed']:>5} {r['err']:>5} {r['p50_ms']:>8.1f} "
              f"{r['p99_ms']:>8.1f} {r['chip_s_per_token']:>12.3e} "
              f"{r['headroom']:>9.3f} {r['hr_gauge']:>9.3f}")
    ok_rows = [r for r in rows if r["ok"] and not r["shed"]
               and not r["err"] and r["headroom"] == r["headroom"]
               and r["headroom"] > 0.1]
    if ok_rows:
        best = max(ok_rows, key=lambda r: r["rate"])
        print(f"capacity: highest clean rate {best['rate']:g} req/s "
              f"(headroom {best['headroom']:.2f}, "
              f"{best['chip_s_per_token']:.3e} chip-s/token); scale out "
              f"before headroom reaches 0 (docs/DEPLOYMENT.md)")
    else:
        print("capacity: no clean step (sheds/errors at every rate, or "
              "headroom exhausted) — this replica is over capacity at "
              "the lowest offered rate")


def report_windows(results, window_s: float, pool_samples=None) -> None:
    """Per-window latency table keyed by request START time — a queued
    request that started before a swap and resolved after it lands in
    the window where its latency was incurred. With reply metadata
    (tracing on), each window also splits latency into queue wait vs
    device service, so a swap blip is attributable at a glance: q_p99
    jumping = queued behind the swap; svc_p99 jumping = the new version
    decodes slower. With pool samples (ISSUE 14: --metrics-port against
    an iteration-mode server), pool%/cow% columns print the window's
    mean KV-pool occupancy and COW alias ratio, so a p99/evict blip is
    attributable to pool pressure at a glance. With tenants in the
    results (--tenants against a --fleet server), each window grows
    per-tenant q/svc p50/p99 columns — a cold start or brownout on one
    tenant must blip that tenant's columns and only those."""
    if not results:
        print("stream: no requests completed")
        return
    last = max(r[0] for r in results)
    n_windows = int(last // window_s) + 1
    have_meta = any(r[3] is not None for r in results)
    tenants = sorted({r[7] for r in results if len(r) > 7 and r[7]})
    # pool columns only when at least one sample carried the gauges
    # (a request-mode server exports neither — all-NaN suppresses them)
    pool_samples = [s for s in (pool_samples or [])
                    if s[1] == s[1]]                     # drop NaN
    have_pool = bool(pool_samples)
    # retry column (ISSUE 11): !!SERVER-RETRY replies received per
    # window — the client-visible count of evict-with-retry events
    # (quiesce deadline, brownout, watchdog) plus any that exhausted
    # the --retries budget
    have_retries = any(len(r) > 5 and (r[5] or r[2] == "retry")
                       for r in results)
    # ttft columns only when at least one request saw a #partial: frame
    # (a non-streaming server, or a run without --stream, sends none —
    # all-None suppresses them, mirroring the pool%/cow% convention)
    have_ttft = any(len(r) > 6 and r[6] is not None for r in results)
    hdr = (f"{'window':>12} {'req':>5} {'ok':>5} {'shed':>5} {'err':>5} "
           f"{'p50_ms':>8} {'p99_ms':>8} {'max_ms':>8}")
    if have_retries:
        hdr += f" {'retry':>6}"
    if have_meta:
        hdr += f" {'q_p50':>7} {'q_p99':>7} {'svc_p50':>7} {'svc_p99':>7}"
    if tenants and have_meta:
        for tag in tenants:
            short = tag[:4]
            hdr += (f" {short + ':q50':>9} {short + ':q99':>9}"
                    f" {short + ':s50':>9} {short + ':s99':>9}")
    if have_ttft:
        hdr += f" {'ttft50':>7} {'ttft99':>7}"
    if have_pool:
        hdr += f" {'pool%':>6} {'cow%':>6}"
    print(hdr)
    ttfj = [r[3] for r in results if r[2] == "ok" and r[3] is not None]
    if ttfj:
        # time-to-first-join: the server stamps queue_ms at the moment
        # the request's first sentence ENTERED a decode (join time in
        # iteration mode, first batch dispatch in request mode) — the
        # client-visible number mid-decode admission improves
        print(f"time-to-first-join p50={pct(ttfj, 0.50) * 1e3:.1f}ms "
              f"p99={pct(ttfj, 0.99) * 1e3:.1f}ms "
              f"max={max(ttfj) * 1e3:.1f}ms")
    if have_ttft:
        # time-to-first-TOKEN: client-side stamp at the first #partial:
        # frame of the FINAL (successful) attempt — the streaming
        # latency a user actually perceives, ttfj + one engine round
        ttft = [r[6] for r in results
                if len(r) > 6 and r[6] is not None and r[2] == "ok"]
        if ttft:
            print(f"time-to-first-token p50={pct(ttft, 0.50) * 1e3:.1f}ms "
                  f"p99={pct(ttft, 0.99) * 1e3:.1f}ms "
                  f"max={max(ttft) * 1e3:.1f}ms")
    for w in range(n_windows):
        rows = [r for r in results
                if w * window_s <= r[0] < (w + 1) * window_s]
        if not rows:
            continue
        lat = [r[1] for r in rows if r[2] == "ok"]
        shed = sum(1 for r in rows if r[2] == "overloaded")
        err = sum(1 for r in rows if r[2] in ("timeout", "retry", "other"))
        line = (f"[{w * window_s:4.0f}-{(w + 1) * window_s:4.0f}s)"
                f" {len(rows):>5} {len(lat):>5} {shed:>5} {err:>5} "
                f"{pct(lat, 0.50) * 1e3:>8.1f} "
                f"{pct(lat, 0.99) * 1e3:>8.1f} "
                f"{max(lat) * 1e3 if lat else float('nan'):>8.1f}")
        if have_retries:
            n_retry = sum((r[5] if len(r) > 5 else 0)
                          + (1 if r[2] == "retry" else 0) for r in rows)
            line += f" {n_retry:>6}"
        if have_meta:
            qs = [r[3] for r in rows if r[2] == "ok" and r[3] is not None]
            ss = [r[4] for r in rows if r[2] == "ok" and r[4] is not None]
            line += (f" {pct(qs, 0.50) * 1e3:>7.1f}"
                     f" {pct(qs, 0.99) * 1e3:>7.1f}"
                     f" {pct(ss, 0.50) * 1e3:>7.1f}"
                     f" {pct(ss, 0.99) * 1e3:>7.1f}")
        if tenants and have_meta:
            for tag in tenants:
                tq = [r[3] for r in rows if len(r) > 7 and r[7] == tag
                      and r[2] == "ok" and r[3] is not None]
                ts_ = [r[4] for r in rows if len(r) > 7 and r[7] == tag
                       and r[2] == "ok" and r[4] is not None]
                if tq or ts_:
                    line += (f" {pct(tq, 0.50) * 1e3:>9.1f}"
                             f" {pct(tq, 0.99) * 1e3:>9.1f}"
                             f" {pct(ts_, 0.50) * 1e3:>9.1f}"
                             f" {pct(ts_, 0.99) * 1e3:>9.1f}")
                else:
                    line += f" {'-':>9} {'-':>9} {'-':>9} {'-':>9}"
        if have_ttft:
            ts = [r[6] for r in rows
                  if len(r) > 6 and r[6] is not None and r[2] == "ok"]
            if ts:
                line += (f" {pct(ts, 0.50) * 1e3:>7.1f}"
                         f" {pct(ts, 0.99) * 1e3:>7.1f}")
            else:
                line += f" {'-':>7} {'-':>7}"
        if have_pool:
            ws = [s for s in pool_samples
                  if w * window_s <= s[0] < (w + 1) * window_s]
            if ws:
                occ = 100.0 * sum(s[1] for s in ws) / len(ws)
                cow = 100.0 * sum(s[2] for s in ws) / len(ws)
                line += f" {occ:>6.1f} {cow:>6.1f}"
            else:
                line += f" {'-':>6} {'-':>6}"
        print(line)


def report_tenants(results) -> None:
    """Per-tenant summary table (--tenants, ISSUE 20): request
    outcomes, success rate, latency percentiles and source tokens
    offered/served per tenant. The server-side mirror is
    marian_fleet_request_outcomes_total{outcome,tenant} — this is the
    client-visible cross-check (an ok here that the server counted as
    someone else's would be the routing bug the fleet must never
    have)."""
    tenants = sorted({r[7] for r in results if len(r) > 7 and r[7]})
    if not tenants:
        return
    print(f"{'tenant':>10} {'req':>6} {'ok':>6} {'shed':>5} {'retry':>6} "
          f"{'err':>5} {'ok%':>6} {'p50_ms':>8} {'p99_ms':>8} "
          f"{'tok_sent':>9} {'tok_ok':>8}")
    for tag in tenants:
        rows = [r for r in results if len(r) > 7 and r[7] == tag]
        lat = [r[1] for r in rows if r[2] == "ok"]
        shed = sum(1 for r in rows if r[2] == "overloaded")
        err = sum(1 for r in rows if r[2] in ("timeout", "other"))
        # retry column = resends honored + budget-exhausted finals,
        # same accounting as the window table
        n_retry = sum(r[5] + (1 if r[2] == "retry" else 0) for r in rows)
        tok = sum(r[8] for r in rows if len(r) > 8)
        tok_ok = sum(r[8] for r in rows if len(r) > 8 and r[2] == "ok")
        print(f"{tag[:10]:>10} {len(rows):>6} {len(lat):>6} {shed:>5} "
              f"{n_retry:>6} {err:>5} "
              f"{100.0 * len(lat) / len(rows) if rows else 0:>6.1f} "
              f"{pct(lat, 0.50) * 1e3:>8.1f} {pct(lat, 0.99) * 1e3:>8.1f} "
              f"{tok:>9} {tok_ok:>8}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8080)
    ap.add_argument("--transport", choices=("auto", "ws", "tcp"),
                    default="auto")
    ap.add_argument("--clients", type=int, default=8,
                    help="concurrent clients")
    ap.add_argument("--requests", type=int, default=4,
                    help="sequential requests per client")
    ap.add_argument("--sentences", type=int, default=4,
                    help="sentences per request")
    ap.add_argument("--words", type=int, default=6,
                    help="words per sentence")
    ap.add_argument("--metrics-port", type=int, default=0,
                    help="scrape /metrics before+after and report deltas")
    ap.add_argument("--duration", type=float, default=0.0,
                    help="streaming mode: constant open-loop arrival for "
                         "N seconds (replaces --clients/--requests)")
    ap.add_argument("--rate", type=float, default=4.0,
                    help="streaming mode arrival rate in requests/s")
    ap.add_argument("--window", type=float, default=10.0,
                    help="streaming mode: report p50/p99 per N-second "
                         "window (a hot-swap under load shows as a "
                         "window blip, not an averaged-away artifact)")
    ap.add_argument("--len-mix", default="",
                    help="streaming mode: bimodal sentence lengths "
                         "'short:long[:p_short]' (e.g. '4:24:0.7') — "
                         "mixed-length traffic is what exercises "
                         "iteration mode's mid-decode join path "
                         "(--batching-mode iteration A/B; the server's "
                         "marian_serving_mid_decode_joins_total delta "
                         "proves joins happened). Deterministic per "
                         "request index, so A/B runs see identical "
                         "traffic")
    ap.add_argument("--prefix-mix", type=float, default=0.0,
                    help="fraction of requests drawn from a small "
                         "SHARED sentence pool (exact repeats — the "
                         "redundant-traffic shape --prefix-cache turns "
                         "into page-table hits). Deterministic per "
                         "request index, so cold-vs-warm A/B runs see "
                         "identical traffic; with --metrics-port the "
                         "summary adds the server's prefix hit rate, "
                         "tokens saved and pages reused")
    ap.add_argument("--force-mix", type=float, default=0.0,
                    help="fraction of requests sent as force-decode "
                         "lines ('source<TAB>target-prefix', ISSUE 16 "
                         "iteration servers with --force-decode), "
                         "drawn from the same small shared pool as "
                         "--prefix-mix so a --prefix-cache server "
                         "sees exact (source, forced-trunk) repeats — "
                         "the traffic shape that makes constrained "
                         "prefixes share pages. Deterministic per "
                         "request index")
    ap.add_argument("--tenants", default="",
                    help="mixed-tenant traffic against a --fleet "
                         "server: 'A:0.5,B:0.3,C:0.2' stamps a "
                         "deterministic per-request '#model:<tag>' "
                         "header in those ratios (weights normalize; "
                         "deterministic per request index, so A/B runs "
                         "see identical traffic). Streaming mode adds "
                         "per-tenant q/svc p50/p99 window columns and "
                         "a per-tenant summary table (ok/shed/retry, "
                         "success rate, tokens)")
    ap.add_argument("--sweep", default="",
                    help="capacity mode (ISSUE 9 / ROADMAP 4): comma-"
                         "separated offered rates in req/s (e.g. "
                         "'1,2,4,8'); each runs open-loop for "
                         "--duration seconds and the table reports "
                         "per-step p50/p99, shed counts, the server's "
                         "chip-seconds/token delta and the capacity "
                         "headroom gauge. Requires --metrics-port and "
                         "a server running with --perf-accounting")
    ap.add_argument("--retries", type=int, default=0,
                    help="resend a request up to N times when the "
                         "server replies !!SERVER-RETRY (retriable row "
                         "eviction: quiesce deadline, brownout, "
                         "watchdog trip), with capped jittered "
                         "exponential backoff. 0 (default) keeps the "
                         "old single-shot behavior; retry/evicted "
                         "counts are reported per stream window")
    ap.add_argument("--retry-base-ms", type=float, default=100.0,
                    help="base backoff before the first retry "
                         "(doubles per attempt, capped at 2s, jittered "
                         "x[0.5,1.5))")
    ap.add_argument("--priority", type=int, default=None,
                    help="send every request in this priority lane via "
                         "the '#priority:N' protocol header (this "
                         "repo's server; brownout level 3 sheds lanes "
                         "below --brownout-min-priority first)")
    ap.add_argument("--stream", action="store_true",
                    help="send the '#stream:1' protocol header (this "
                         "repo's server, iteration mode): the server "
                         "pushes '#partial:<idx> <text>' frames per "
                         "engine round before the final reply; the "
                         "client stamps time-to-first-token at the "
                         "first partial and reports ttft p50/p99 next "
                         "to ttfj (columns suppressed when no partials "
                         "arrive, e.g. a request-mode server)")
    ap.add_argument("--verbose", action="store_true",
                    help="print per-request transport errors")
    ap.add_argument("--no-trace", action="store_true",
                    help="do not send #trace request ids (drops the "
                         "queue-wait vs service-time breakdown the "
                         "server's reply metadata provides). REQUIRED "
                         "against servers without this repo's #trace "
                         "protocol extension — they would translate the "
                         "header as an extra sentence")
    args = ap.parse_args(argv)

    try:
        args.tenant_mix = parse_tenants(args.tenants)
    except ValueError as e:
        ap.error(str(e))

    transport = args.transport
    if transport == "auto":
        try:
            import websockets  # noqa: F401
            transport = "ws"
        except ImportError:
            transport = "tcp"
    request_fn = _request_ws if transport == "ws" else _request_tcp

    if args.sweep:
        if not args.metrics_port:
            ap.error("--sweep needs --metrics-port (it reads the "
                     "chip-seconds/token and headroom gauges back)")
        try:
            rates = [float(r) for r in args.sweep.split(",") if r.strip()]
        except ValueError:
            ap.error(f"--sweep: unparseable rate list {args.sweep!r}")
        if not rates or any(r <= 0 for r in rates):
            ap.error("--sweep rates must be positive")
        if args.duration <= 0:
            args.duration = 10.0
        rows = asyncio.run(run_sweep(args, request_fn, rates))
        print(f"transport={transport} sweep rates={rates} "
              f"{args.duration:g}s/step "
              f"sentences/request={args.sentences}")
        report_sweep(rows)
        return 0 if any(r["ok"] for r in rows) else 1

    before = scrape(args.host, args.metrics_port) if args.metrics_port \
        else {}
    if args.duration > 0:
        if args.rate <= 0:
            ap.error("--duration streaming mode requires --rate > 0")
        pool_samples: list = [] if args.metrics_port else None
        results = asyncio.run(run_stream(args, request_fn,
                                         pool_samples=pool_samples))
        after = scrape(args.host, args.metrics_port) if args.metrics_port \
            else {}
        latencies = [r[1] for r in results if r[2] == "ok"]
        errors = {"overloaded": sum(1 for r in results
                                    if r[2] == "overloaded"),
                  "timeout": sum(1 for r in results if r[2] == "timeout"),
                  "other": sum(1 for r in results
                               if r[2] in ("retry", "other"))}
        wall = args.duration
        n_ok = len(latencies)
        print(f"transport={transport} stream duration={args.duration}s "
              f"rate={args.rate}/s sentences/request={args.sentences}")
        print(f"ok={n_ok} shed={errors['overloaded']} "
              f"timeout={errors['timeout']} other_errors={errors['other']}")
        retried = sum(r[5] for r in results if len(r) > 5)
        if retried or any(r[2] == "retry" for r in results):
            retried_ok = sum(1 for r in results
                             if len(r) > 5 and r[5] and r[2] == "ok")
            exhausted = sum(1 for r in results if r[2] == "retry")
            print(f"retries: {retried} resends after !!SERVER-RETRY "
                  f"(evictions), {retried_ok} requests ok after retry, "
                  f"{exhausted} exhausted the --retries budget")
        report_windows(results, args.window, pool_samples=pool_samples)
        report_tenants(results)
        if before or after:
            swaps = _delta(before, after, "marian_lifecycle_swaps_total")
            rollbacks = _delta(before, after,
                               "marian_lifecycle_rollbacks_total")
            if swaps or rollbacks:
                print(f"server: swaps={swaps:.0f} rollbacks={rollbacks:.0f} "
                      f"during the run")
        _report_server_delta(before, after)
        return 0 if n_ok and not errors["other"] else 1
    latencies, errors, wall, queue_waits, service_times = asyncio.run(
        run_clients(args, request_fn))
    after = scrape(args.host, args.metrics_port) if args.metrics_port \
        else {}

    n_ok = len(latencies)
    n_req = args.clients * args.requests
    print(f"transport={transport} clients={args.clients} "
          f"requests={n_req} sentences/request={args.sentences}")
    print(f"ok={n_ok} shed={errors['overloaded']} "
          f"timeout={errors['timeout']} other_errors={errors['other']}")
    if latencies:
        print(f"latency p50={pct(latencies, 0.50) * 1e3:.1f}ms "
              f"p99={pct(latencies, 0.99) * 1e3:.1f}ms "
              f"mean={statistics.mean(latencies) * 1e3:.1f}ms")
        print(f"throughput {n_ok / wall:.2f} req/s "
              f"{n_ok * args.sentences / wall:.2f} sentences/s "
              f"(wall {wall:.2f}s)")
    if queue_waits:
        # server-reported split of the latency above (reply metadata):
        # how much was queueing vs device service
        print(f"breakdown queue p50={pct(queue_waits, 0.50) * 1e3:.1f}ms "
              f"p99={pct(queue_waits, 0.99) * 1e3:.1f}ms | "
              f"service p50={pct(service_times, 0.50) * 1e3:.1f}ms "
              f"p99={pct(service_times, 0.99) * 1e3:.1f}ms")
    _report_server_delta(before, after)
    return 0 if n_ok and not errors["other"] else 1


def _report_server_delta(before: dict, after: dict) -> None:
    if not (before or after):
        return
    batches = _delta(before, after, "marian_serving_batches_total")
    fill_sum = _delta(before, after,
                      "marian_serving_batch_fill_ratio_sum")
    fill_n = _delta(before, after,
                    "marian_serving_batch_fill_ratio_count")
    shed = _delta(before, after, "marian_serving_shed_total")
    timeouts = _delta(before, after, "marian_serving_timeouts_total")
    sent = _delta(before, after,
                  "marian_serving_admitted_sentences_total")
    print(f"server: batches={batches:.0f} "
          f"sentences/batch={sent / batches if batches else 0:.2f} "
          f"mean_fill={fill_sum / fill_n if fill_n else 0:.3f} "
          f"shed={shed:.0f} timeouts={timeouts:.0f}")
    hits = _delta(before, after, "marian_prefix_hits_total")
    misses = _delta(before, after, "marian_prefix_misses_total")
    if hits or misses:
        # prefix-sharing column (ISSUE 12): the --prefix-mix acceptance
        # reads this line — hits > 0 and pages_reused > 0 prove repeats
        # became page-table hits instead of recompute
        print(f"server: prefix_hit_rate="
              f"{hits / (hits + misses) if hits + misses else 0:.3f} "
              f"prefix_hits={hits:.0f} "
              f"tokens_saved="
              f"{_delta(before, after, 'marian_prefix_tokens_saved_total'):.0f} "
              f"pages_reused="
              f"{_delta(before, after, 'marian_prefix_pages_reused_total'):.0f} "
              f"prefix_evictions="
              f"{_delta(before, after, 'marian_prefix_evictions_total'):.0f}")
    fleet_req = _delta(before, after,
                       "marian_fleet_request_outcomes_total")
    if fleet_req:
        # fleet deltas (ISSUE 20): cold starts during the run are the
        # warm-on-demand events; evictions are the HBM-budget pressure
        print(f"server: fleet_requests={fleet_req:.0f} "
              f"cold_starts="
              f"{_delta(before, after, 'marian_fleet_cold_starts_total'):.0f} "
              f"fleet_evictions="
              f"{_delta(before, after, 'marian_fleet_evictions_total'):.0f} "
              f"fleet_shed="
              f"{_delta(before, after, 'marian_fleet_shed_total'):.0f}")
    joins = _delta(before, after, "marian_serving_joins_total")
    if joins:
        # iteration-mode deltas: mid-decode joins are the proof that
        # sentences actually entered RUNNING decodes (the ISSUE 10 A/B
        # acceptance reads this line)
        print(f"server: joins={joins:.0f} "
              f"mid_decode_joins="
              f"{_delta(before, after, 'marian_serving_mid_decode_joins_total'):.0f} "
              f"evictions="
              f"{_delta(before, after, 'marian_serving_evictions_total'):.0f} "
              f"decode_steps="
              f"{_delta(before, after, 'marian_serving_decode_steps_total'):.0f}")


if __name__ == "__main__":
    sys.exit(main())
