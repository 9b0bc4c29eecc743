"""Driver ``mine``: a solo miner at its RPC door, with readers beside it.

The process runs the lines of cli/bcpd.main (Config.parse_args -> Node ->
start_rpc) on the configuration's flags and then talks to the node over
loopback JSON-RPC from client threads of its own:

* one closed-loop client calls ``generatetoaddress 1 <addr> <maxtries>``
  back to back until --seconds have passed, and finishes the call in flight;
* an open-loop client sends ``reads_per_s`` reads a second (the traffic
  file's ``reads`` in rotation), each timed from when it was due.

Traffic parameters (chipbench/traffic/<mix>.json): maxtries, warm_tries,
reads_per_s, reads, read_threads, trace_seconds, rehearse.
"""

from __future__ import annotations

import base64
import hashlib
import http.client
import json
import os
import socket
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import checks
import reference

RPC_TIMEOUT_S = 20 * 60  # a first generatetoaddress pays a cold compile
FAULTS = ("harder-target",)
FAULT_SHIFT = 16  # harder-target: hold every header to target >> 16
B58 = "123456789ABCDEFGHJKLMNPQRSTUVWXYZabcdefghijkmnopqrstuvwxyz"


class RpcFailure(Exception):
    pass


def rpc_call(port: int, auth: str, method: str, *params):
    """chip_smoke.rpc_call: a minimal JSON-RPC client, one connection a
    call, cookie auth."""
    conn = http.client.HTTPConnection("127.0.0.1", port,
                                      timeout=RPC_TIMEOUT_S)
    try:
        conn.request("POST", "/", json.dumps({
            "jsonrpc": "1.0", "id": 1, "method": method,
            "params": list(params)}), {
            "Authorization": f"Basic {auth}",
            "Content-Type": "application/json"})
        body = json.loads(conn.getresponse().read())
    finally:
        conn.close()
    if body.get("error"):
        raise RpcFailure(f"RPC {method} failed: {body['error']}")
    return body["result"]


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def address_from_seed(seed: int, prefix: int) -> str:
    """A pay-to-pubkey-hash address nobody holds the key of."""
    payload = bytes([prefix]) + hashlib.new(
        "ripemd160", hashlib.sha256(
            b"chipbench-miner-" + str(int(seed)).encode()).digest()).digest()
    raw = payload + hashlib.sha256(
        hashlib.sha256(payload).digest()).digest()[:4]
    n, out = int.from_bytes(raw, "big"), ""
    while n:
        n, rem = divmod(n, 58)
        out = B58[rem] + out
    return "1" * (len(raw) - len(raw.lstrip(b"\0"))) + out


def snapshot(node) -> dict:
    """gettpuinfo, read in-process (the RPC would wait for cs_main)."""
    from bitcoincashplus_tpu.rpc.control import gettpuinfo

    return gettpuinfo(node, [])


def setup(ctx) -> None:
    if ctx.fault and ctx.fault not in FAULTS:
        raise ValueError(f"driver mine knows the faults {FAULTS}, "
                         f"not {ctx.fault!r}")


def warm(ctx) -> None:
    from bitcoincashplus_tpu.node.config import Config
    from bitcoincashplus_tpu.node.node import Node

    st, traffic = ctx.state, ctx.traffic
    flags = list(ctx.config["flags"])
    if ctx.rehearse:
        traffic = ctx.traffic = dict(traffic, **traffic["rehearse"])
        flags = list(ctx.config["rehearse_flags"])
    config = Config()
    config.parse_args(flags + [f"-datadir={os.path.join(ctx.workdir, 'd')}",
                               f"-rpcport={free_port()}"])
    node = st["node"] = Node(config)
    st["port"] = node.start_rpc()
    with open(os.path.join(node.datadir, ".cookie")) as f:
        st["auth"] = base64.b64encode(f.read().strip().encode()).decode()
    st["address"] = address_from_seed(ctx.seed,
                                      node.params.pubkey_addr_prefix)
    rpc = st["rpc"] = lambda m, *p: rpc_call(st["port"], st["auth"], m, *p)
    t0 = time.monotonic()
    st["warm_blocks"] = rpc("generatetoaddress", 1, st["address"],
                            traffic["warm_tries"])
    for method in traffic["reads"]:
        rpc(*_read_call(method, rpc("getbestblockhash")))
    st["setup"] = snapshot(node)
    st["setup_report"] = {"warm_call_s": time.monotonic() - t0,
                          "warm_blocks": len(st["warm_blocks"]),
                          "network": node.params.network}


def _read_call(method: str, tip: str) -> tuple:
    return (method, tip) if method == "getblockheader" else (method,)


def window(ctx) -> dict:
    st, traffic = ctx.state, ctx.traffic
    rpc, node = st["rpc"], st["node"]
    seconds = traffic["trace_seconds"] if ctx.trace else ctx.seconds
    tip = [rpc("getbestblockhash")]
    height_before = rpc("getblockcount")
    calls: list = []     # (start, end, hashes | None, error | None)
    reads: list = []     # (due, sent, done, error | None)

    before = snapshot(node)
    t0 = time.monotonic()
    deadline = t0 + seconds

    def miner() -> None:
        while time.monotonic() < deadline:
            start = time.monotonic()
            try:
                with ctx.annotate("generatetoaddress"):
                    hashes, err = rpc("generatetoaddress", 1, st["address"],
                                      traffic["maxtries"]), None
            except (RpcFailure, OSError, ValueError) as e:
                hashes, err = None, repr(e)
            calls.append((start, time.monotonic(), hashes, err))
            if hashes:
                tip[0] = hashes[-1]

    def one_read(due: float, method: str) -> None:
        sent = time.monotonic()
        try:
            rpc(*_read_call(method, tip[0]))
            err = None
        except (RpcFailure, OSError, ValueError) as e:
            err = repr(e)
        reads.append((due, sent, time.monotonic(), err))

    def reader(pool: ThreadPoolExecutor) -> list:
        futures, k = [], 0
        while True:
            due = t0 + k / traffic["reads_per_s"]
            if due >= deadline:
                return futures
            delay = due - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            futures.append(pool.submit(
                one_read, due, traffic["reads"][k % len(traffic["reads"])]))
            k += 1

    mining = threading.Thread(target=miner, name="chipbench-miner")
    mining.start()
    with ThreadPoolExecutor(traffic["read_threads"],
                            thread_name_prefix="chipbench-read") as pool:
        for future in reader(pool):
            future.result()
    mining.join()
    t1 = time.monotonic()
    after = snapshot(node)

    window_s = t1 - t0
    swept = (after["mining"].get("nonces_swept", 0)
             - before["mining"].get("nonces_swept", 0))
    # the work done, counted by the harness: a miss has hashed maxtries
    # nonces, a hit the nonces up to the one in the header it returned
    headers = {}
    hashed = 0
    for _, _, hashes, err in calls:
        if err is None and not hashes:
            hashed += traffic["maxtries"]
        for h in hashes or []:
            try:
                headers[h] = bytes.fromhex(rpc("getblockheader", h, False))
                hashed += int.from_bytes(headers[h][76:80], "little") + 1
            except RpcFailure:  # the hash that was returned names no block
                headers[h] = None
    latencies = sorted(done - due for due, _, done, _ in reads)
    lateness = [sent - due for due, sent, _, _ in reads]
    hits = [h for _, _, hashes, _ in calls for h in (hashes or [])]
    failed = (sum(1 for c in calls if c[3])
              + sum(1 for r in reads if r[3]))
    p95 = latencies[min(len(latencies) - 1,
                        int(0.95 * len(latencies)))] if latencies else 0.0
    return {
        "before": before, "after": after, "window_s": window_s,
        "attempted": len(calls) + len(reads), "failed": failed,
        "values": {"mine_ghps": hashed / window_s / 1e9,
                   "rpc_read_p95_ms": p95 * 1e3},
        "calls": calls, "hits": hits, "headers": headers,
        "hashed": hashed, "swept": swept,
        "height_before": height_before,
        "report": {
            "mining_calls": len(calls), "blocks_found": len(hits),
            "call_s_median": sorted(e - s for s, e, _, _ in calls)[
                len(calls) // 2] if calls else None,
            "nonces_hashed": hashed, "nonces_swept_counter": swept,
            "reads": len(reads),
            "read_p50_ms": (latencies[len(latencies) // 2] * 1e3
                            if latencies else None),
            "read_max_ms": latencies[-1] * 1e3 if latencies else None,
            "reads_beyond_p95": (len(latencies) - 1
                                 - int(0.95 * len(latencies))
                                 if latencies else 0),
            "generator_late_max_ms": max(lateness, default=0.0) * 1e3,
            "generator_late_mean_ms": (sum(lateness) / len(lateness) * 1e3
                                       if lateness else 0.0),
            "errors": [c[3] for c in calls if c[3]][:3]
            + [r[3] for r in reads if r[3]][:3],
        },
    }


def check(ctx, result: dict) -> list:
    """Every block the node returned, held to the plain reference: the
    header's double SHA-256 is the hash that was returned, and is no higher
    than the target its nBits decode to."""
    st, traffic = ctx.state, ctx.traffic
    rpc = st["rpc"]
    shift = FAULT_SHIFT if ctx.fault == "harder-target" else 0
    headers = dict(result["headers"])
    for h in st["warm_blocks"]:
        try:
            headers[h] = bytes.fromhex(rpc("getblockheader", h, False))
        except RpcFailure:
            headers[h] = None
    mismatched, worst = 0, 0.0
    for h, header in headers.items():
        if header is None:
            mismatched += 1
            continue
        ref_hash, value, target = reference.header_pow(header)
        mismatched += ref_hash != h
        worst = max(worst, value / (target >> shift))
    calls = result["calls"]
    misses = sum(1 for _, _, hashes_, err in calls
                 if err is None and not hashes_)
    floor = misses * traffic["maxtries"]
    advance = rpc("getblockcount") - result["height_before"]
    mining = result["after"]["mining"]
    # the program's counter against the harness's count: the counter takes
    # a hit's tile whole (under one tile a hit by construction; 62,442 was
    # the most a sound run read), segments in flight behind it not at all
    slack = 3 * mining.get("tile", 0)
    out = [
        checks.compared("headers_hash_mismatched", mismatched, 0,
                        note=f"of {len(headers)} returned blocks"),
        checks.compared("pow_hash_over_target_worst", worst, 1.0,
                        note=f"target >> {shift}" if shift else ""),
        checks.compared("tip_advance_minus_blocks_returned",
                        abs(advance - len(result["hits"])), 0),
    ]
    if not ctx.rehearse:  # a rehearsal's scalar host loop counts nothing
        out += [
            checks.compared("nonces_below_floor",
                            max(0, floor - result["swept"]), 0,
                            note=f"{misses} misses x maxtries"),
            checks.compared("nonces_counter_minus_harness_count",
                            abs(result["swept"] - result["hashed"]),
                            len(result["hits"]) * slack,
                            note=f"{len(result['hits'])} hits x three "
                                 f"tiles of {mining.get('tile')}")]
        want = ctx.config["guarantees"]["mining_engine"]
        out.append(checks.compared(
            "engine_is_not_" + want, int(mining.get("engine") != want), 0,
            note=str(mining.get("engine"))))
    return out


def close(ctx) -> None:
    node = ctx.state.pop("node", None)
    if node is not None:
        node.close()
