#!/usr/bin/env python
"""Load generator for `ccs serve`: latency/throughput vs the offline driver.

Generates a simulated multi-ZMW workload (simulate.simulate_zmw), then:

  1. OFFLINE BASELINE -- times pipeline.process_chunks over the whole
     workload in chunkSize batches (the batch CLI's execution shape) and
     records every consensus sequence;
  2. SERVING RUN -- starts a CcsEngine + CcsServer in-process (or targets
     --connect HOST:PORT), drives it with --clients concurrent sessions
     submitting the same ZMWs, and records per-request admission-to-result
     latency (client-side wall) and total throughput;
  3. CORRECTNESS -- every served Success must match the offline sequence
     for the same ZMW bit-for-bit (same chunks, same polish core);
  4. RESILIENCE PROBES (--no-chaos to skip) -- a client that disconnects
     mid-stream with requests in flight, a malformed frame, and a request
     that raises inside the engine (empty SNR): the server must keep
     answering afterwards.

Reports p50/p99 latency, ZMW/s for both drivers, and the final engine
status snapshot as JSON (stdout, plus --out FILE).

Usage:
    JAX_PLATFORMS=cpu python tools/serve_bench.py --zmws 32 --clients 4
"""

from __future__ import annotations

import argparse
import json
import socket
import sys
import threading
import time

import numpy as np

sys.path.insert(0, ".")  # runnable as tools/serve_bench.py from the repo root

from pbccs_tpu.pipeline import Chunk, ConsensusSettings, Subread, process_chunks
from pbccs_tpu.runtime.logging import Logger, LogLevel
from pbccs_tpu.serve.client import CcsClient, ServeError
from pbccs_tpu.serve.engine import CcsEngine, ServeConfig
from pbccs_tpu.serve.server import CcsServer
from pbccs_tpu.simulate import simulate_zmw


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--zmws", type=int, default=32)
    p.add_argument("--tplLen", type=int, default=120)
    p.add_argument("--passes", type=int, default=6)
    p.add_argument("--clients", type=int, default=4)
    p.add_argument("--maxBatch", type=int, default=8)
    p.add_argument("--maxWaitMs", type=float, default=500.0)
    p.add_argument("--maxPending", type=int, default=256)
    p.add_argument("--deadlineMs", type=float, default=600_000.0)
    p.add_argument("--chunkSize", type=int, default=64,
                   help="offline driver's ZMWs per batch")
    p.add_argument("--seed", type=int, default=20260803)
    p.add_argument("--connect", default=None, metavar="HOST:PORT",
                   help="target an external `ccs serve` instead of the "
                        "in-process engine")
    p.add_argument("--no-offline", action="store_true",
                   help="skip the offline baseline (and the correctness "
                        "diff against it)")
    p.add_argument("--no-chaos", action="store_true",
                   help="skip the resilience probes")
    p.add_argument("--out", default=None, help="also write the JSON here")
    return p


def make_workload(args) -> list[Chunk]:
    rng = np.random.default_rng(args.seed)
    chunks = []
    for i in range(args.zmws):
        _, reads, _, snr = simulate_zmw(rng, args.tplLen, args.passes)
        chunks.append(Chunk(
            f"bench/{i}",
            [Subread(f"bench/{i}/{k}", r) for k, r in enumerate(reads)],
            snr))
    return chunks


def run_offline(chunks, settings, chunk_size: int) -> tuple[float, dict]:
    t0 = time.monotonic()
    by_id: dict[str, str] = {}
    statuses: dict[str, int] = {}
    for lo in range(0, len(chunks), chunk_size):
        tally = process_chunks(chunks[lo: lo + chunk_size], settings)
        for r in tally.results:
            by_id[r.id] = r.sequence
        for f, c in tally.counts.items():
            statuses[f.value] = statuses.get(f.value, 0) + c
    return time.monotonic() - t0, {"sequences": by_id, "statuses": statuses}


def run_clients(host, port, chunks, n_clients, deadline_ms):
    """Drive the server with n_clients concurrent sessions; returns
    (wall_s, per-request latency ms list, replies by zmw id)."""
    shares = [chunks[i::n_clients] for i in range(n_clients)]
    latencies: list[float] = []
    replies: dict[str, dict] = {}
    errors: list[str] = []
    lock = threading.Lock()

    def one_client(share):
        with CcsClient(host, port) as cli:
            pending = []
            for chunk in share:
                t0 = time.monotonic()
                pending.append((chunk, t0, cli.submit_chunk(
                    chunk, deadline_ms=deadline_ms)))
            for chunk, t0, handle in pending:
                try:
                    msg = handle.reply(timeout=600.0)
                except (ServeError, ConnectionError, TimeoutError) as e:
                    with lock:
                        errors.append(f"{chunk.id}: {e}")
                    continue
                dt_ms = (time.monotonic() - t0) * 1e3
                with lock:
                    latencies.append(dt_ms)
                    replies[chunk.id] = msg

    threads = [threading.Thread(target=one_client, args=(s,))
               for s in shares if s]
    t0 = time.monotonic()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return time.monotonic() - t0, latencies, replies, errors


def run_chaos(host, port) -> dict:
    """Server-resilience probes; returns what the server survived."""
    out = {}
    # 1. disconnect mid-stream: submits in flight, then slam the socket
    cli = CcsClient(host, port)
    cli.submit("chaos/disconnect", ["ACGTACGTACGTACGTACGT"] * 4)
    cli.close()
    out["disconnect_mid_stream"] = True
    # 2. malformed frame (session-level error, session stays open)
    raw = socket.create_connection((host, port), timeout=30.0)
    raw.sendall(b"this is not json\n")
    rf = raw.makefile("rb")
    reply = json.loads(rf.readline())
    out["malformed_frame_reply"] = reply.get("code")
    # same session must still answer
    raw.sendall(b'{"verb":"ping","id":"p1"}\n')
    out["session_survives_bad_frame"] = \
        json.loads(rf.readline()).get("type") == "pong"
    raw.close()
    # 3. a request that raises inside the engine: the in-process engine's
    # prep_fn is wrapped (main) to raise on this ZMW id, so the request
    # passes wire validation and fails INSIDE the engine -> structured
    # `internal` error, server stays up (an external --connect server has
    # no fault hook; the probe then just checks the reply is structured)
    with CcsClient(host, port) as cli2:
        handle = cli2.submit("chaos/raise", ["ACGTACGTACGTACGT"] * 4)
        try:
            msg = handle.reply(timeout=60.0)
            out["raising_request"] = msg.get("status", "no_error")
        except ServeError as e:
            out["raising_request"] = e.code
        # the engine and server must still serve AFTER the failure
        out["status_after_raise"] = cli2.status()["engine"] == "ccs-serve"
    return out


def pctl(xs, q):
    return float(np.percentile(np.asarray(xs), q)) if xs else float("nan")


def parse_prometheus(body: str) -> dict[str, float]:
    """Prometheus text -> {metric_with_labels: value} (comments dropped)."""
    out: dict[str, float] = {}
    for line in body.splitlines():
        if not line or line.startswith("#"):
            continue
        name, _, value = line.rpartition(" ")
        try:
            out[name] = float(value)
        except ValueError:
            continue
    return out


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    Logger.default(Logger(level=LogLevel.WARN))
    settings = ConsensusSettings()
    chunks = make_workload(args)

    report: dict = {
        "workload": {"zmws": args.zmws, "tpl_len": args.tplLen,
                     "passes": args.passes, "clients": args.clients,
                     "max_batch": args.maxBatch,
                     "max_wait_ms": args.maxWaitMs},
    }

    offline = None
    if not args.no_offline:
        offline_s, offline = run_offline(chunks, settings, args.chunkSize)
        report["offline"] = {
            "wall_s": round(offline_s, 3),
            "zmws_per_s": round(args.zmws / offline_s, 3),
            "statuses": offline["statuses"],
        }

    engine = server = None
    if args.connect:
        host, port_s = args.connect.rsplit(":", 1)
        host, port = host or "127.0.0.1", int(port_s)
    else:
        from pbccs_tpu.pipeline import prepare_chunk

        def prep_with_fault(chunk, settings):
            # chaos-probe fault injection: a request that raises INSIDE
            # the engine (everything else takes the real pipeline path)
            if chunk.id.startswith("chaos/raise"):
                raise RuntimeError("injected fault (serve_bench chaos)")
            return prepare_chunk(chunk, settings)

        engine = CcsEngine(settings, ServeConfig(
            max_batch=args.maxBatch, max_wait_ms=args.maxWaitMs,
            max_pending=args.maxPending,
            default_deadline_ms=args.deadlineMs),
            prep_fn=prep_with_fault).start()
        server = CcsServer(engine, port=0).start()
        host, port = server.host, server.port

    try:
        serve_s, lat, replies, errors = run_clients(
            host, port, chunks, args.clients, args.deadlineMs)
        statuses: dict[str, int] = {}
        for msg in replies.values():
            s = msg.get("status", "error")
            statuses[s] = statuses.get(s, 0) + 1
        report["serve"] = {
            "wall_s": round(serve_s, 3),
            "zmws_per_s": round(args.zmws / serve_s, 3),
            "latency_ms": {"p50": round(pctl(lat, 50), 1),
                           "p99": round(pctl(lat, 99), 1),
                           "max": round(max(lat), 1) if lat else None},
            "statuses": statuses,
            "client_errors": errors,
        }
        if offline is not None:
            match = sum(
                1 for zid, msg in replies.items()
                if msg.get("sequence") and
                msg["sequence"] == offline["sequences"].get(zid))
            served_success = sum(1 for m in replies.values()
                                 if m.get("sequence"))
            report["correctness"] = {
                "served_success": served_success,
                "offline_success": len(offline["sequences"]),
                "sequences_match_offline": match,
                "all_match": match == served_success ==
                len(offline["sequences"]),
            }
            off_rate = report["offline"]["zmws_per_s"]
            srv_rate = report["serve"]["zmws_per_s"]
            report["serve_vs_offline"] = round(srv_rate / off_rate, 3) \
                if off_rate else None

        if not args.no_chaos:
            report["chaos"] = run_chaos(host, port)
        with CcsClient(host, port) as cli:
            report["engine_status"] = cli.status(timeout=30.0)
            # end-of-run metrics snapshot (the Prometheus scrape the
            # `metrics` verb serves), parsed into name -> value so the
            # JSON report stays greppable
            report["metrics"] = parse_prometheus(cli.metrics(timeout=30.0))
    finally:
        if server is not None:
            server.shutdown()
        if engine is not None:
            engine.close()

    out = json.dumps(report, indent=2)
    print(out)
    if args.out:
        with open(args.out, "w") as f:
            f.write(out + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
