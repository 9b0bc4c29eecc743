#!/usr/bin/env python3
"""chip_smoke.py — bcpd's main path on one TPU chip, through its own doors.

Generates a signature-dense regtest chain (tools/gen_sigchain: about 1 MB
and 6,750 P2PKH inputs a block), then starts ONE child,
``bcpd -regtest -tpu=1 -reindex``, and drives it over JSON-RPC:

  reindex  the native import verifies every signature on the device
           (dispatch_packed -> the fused GLV program, 8,192-lane buckets)
  serve    gettxoutsetinfo / getblock / gettxout / gettpuinfo
  mine     generatetoaddress 3 on the device-resident h7 sweep

and fails if any answer is wrong or if gettpuinfo shows that any part of
it ran anywhere but on the TPU (a fallback counter, an open breaker, a
latched kernel, a program over its shape budget).

This process never imports JAX: the child holds the chip for the whole run.
There is no CPU mode — without a TPU the child refuses to start (InitError)
and this script exits non-zero without printing a result. Earlier output
lines are one JSON object per phase; the seconds in them include
compilation and are smoke timings, not measurements. The last line is
``{"ok": true, "device": {"platform", "kind", "count"}}`` from
gettpuinfo.device.
"""

import argparse
import base64
import http.client
import json
import os
import select
import shutil
import socket
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# the import runs inside Node.__init__, so "bcpd started" comes after every
# verify-bucket compile (minutes each, cold)
START_TIMEOUT_S = 40 * 60
RPC_TIMEOUT_S = 20 * 60  # generatetoaddress pays the miner's cold compile


class SmokeFailure(Exception):
    pass


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def tail(path: str, nbytes: int = 4000) -> str:
    try:
        with open(path, "rb") as f:
            f.seek(0, os.SEEK_END)
            f.seek(max(0, f.tell() - nbytes))
            return f.read().decode("utf-8", "replace")
    except OSError as e:
        return f"<{path}: {e}>"


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def rpc_call(port: int, cookie_dir: str, method: str, *params):
    """Minimal JSON-RPC client (cookie auth) — importing the package's own
    client would import JAX into this process."""
    with open(os.path.join(cookie_dir, ".cookie")) as f:
        auth = base64.b64encode(f.read().strip().encode()).decode()
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
        raise SmokeFailure(f"RPC {method} failed: {body['error']}")
    return body["result"]


def build_native() -> None:
    """Force-build the native library from the committed sources: a stale
    libbcpnative.so copied along with the tree must not be what runs."""
    proc = subprocess.run(["make", "-B", "-C", os.path.join(REPO, "native")],
                          capture_output=True, text=True)
    if proc.returncode != 0:
        raise SmokeFailure(
            f"native build failed (rc={proc.returncode}):\n"
            f"{proc.stdout[-1500:]}\n{proc.stderr[-2500:]}")


def generate_chain(work: str, sigs: int, txs_per_block: int) -> dict:
    """tools/gen_sigchain in a child pinned to the CPU (its imports pull in
    JAX; it must neither load it here nor reach for the chip)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "gen_sigchain.py"),
         "--datadir", work, "--sigs", str(sigs),
         "--txs-per-block", str(txs_per_block), "--quiet"],
        env=env, cwd=REPO, capture_output=True, text=True)
    if proc.returncode != 0:
        raise SmokeFailure(f"gen_sigchain failed (rc={proc.returncode}):\n"
                           f"{proc.stderr[-3000:]}")
    summary = json.loads(proc.stdout.strip().splitlines()[-1])
    summary["generate_s"] = round(time.monotonic() - t0, 1)
    return summary


def wait_started(proc: subprocess.Popen, work: str, stderr_path: str) -> None:
    fd = proc.stdout.fileno()
    deadline = time.monotonic() + START_TIMEOUT_S
    buf = b""
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            raise SmokeFailure(
                f"bcpd exited with code {proc.returncode} before it "
                f"started:\n{tail(stderr_path)}")
        ready, _, _ = select.select([fd], [], [], 0.5)
        if ready:
            buf += os.read(fd, 4096)
            if b"bcpd started" in buf:
                return
    raise SmokeFailure(
        f"bcpd printed no start marker within {START_TIMEOUT_S} s; "
        f"debug.log ends:\n"
        f"{tail(os.path.join(work, 'regtest', 'debug.log'))}")


def check(failures: list, name: str, ok: bool, read) -> None:
    if not ok:
        failures.append({"check": name, "read": read})


def no_fallback_checks(info: dict, gen: dict, cache_dir: str) -> list:
    """Every way the run could have happened somewhere else than on the
    TPU, read from gettpuinfo; each one is fatal."""
    bad: list = []
    dev, batch, ecdsa = info["device"], info["batch"], info["ecdsa"]
    dd = ecdsa["dev_decompose"]
    check(bad, "device.platform == tpu", dev["platform"] == "tpu",
          dev["platform"])
    check(bad, "batch.sigs_verified >= generated sigs",
          batch["sigs_verified"] >= gen["sigs"], batch["sigs_verified"])
    for key in ("cpu_fallback_sigs", "fault_fallback_sigs", "kat_failures",
                "pallas_fallbacks"):
        check(bad, f"batch.{key} == 0", batch[key] == 0, batch[key])
    check(bad, "ecdsa.kernel == glv", ecdsa["kernel"] == "glv",
          ecdsa["kernel"])
    check(bad, "ecdsa.glv_broken false", ecdsa["glv_broken"] is False,
          ecdsa["glv_broken"])
    check(bad, "ecdsa.glv_fallbacks == 0", ecdsa["glv_fallbacks"] == 0,
          ecdsa["glv_fallbacks"])
    check(bad, "dev_decompose.broken false", dd["broken"] is False,
          dd["broken"])
    check(bad, "dev_decompose.fallbacks == 0", dd["fallbacks"] == 0,
          dd["fallbacks"])
    check(bad, "dev_decompose.dispatches >= full buckets",
          dd["dispatches"] >= gen["sigs"] // 8190, dd["dispatches"])
    for name, br in info["breakers"].items():
        check(bad, f"breaker {name} closed, no fallbacks",
              br["state"] == "closed" and br["fallback_calls"] == 0
              and br["fallback_items"] == 0, br)
    for name, pw in dev["programs"].items():
        check(bad, f"program {name} within its shape budget",
              pw["retraces_unexpected"] == 0
              and (pw["shape_budget"] is None
                   or pw["shapes"] <= pw["shape_budget"]),
              {k: pw[k] for k in ("shapes", "shape_budget",
                                  "retraces_unexpected")})
    cc = dev["compilation_cache"]
    check(bad, "compilation_cache enabled at the expected dir",
          cc["enabled"] is True and cc["dir"] == cache_dir, cc)
    return bad


def run(sigs: int, txs_per_block: int, work: str) -> dict:
    build_native()
    gen = generate_chain(work, sigs, txs_per_block)
    emit({"phase": "generate", **gen})

    cache_dir = os.path.abspath(
        os.environ.get(CACHE_ENV) or os.path.join(REPO, ".jax_cache"))
    rpc_port, p2p_port = free_port(), free_port()
    env = dict(os.environ)  # JAX_COMPILATION_CACHE_DIR rides along if set
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    stderr_path = os.path.join(work, "bcpd.stderr")
    net_dir = os.path.join(work, "regtest")
    failures: list = []
    t_spawn = time.monotonic()
    with open(stderr_path, "wb") as errf:
        proc = subprocess.Popen(
            [sys.executable, "-m", "bitcoincashplus_tpu.cli.bcpd",
             "-regtest", "-tpu=1", "-reindex", f"-datadir={work}",
             f"-rpcport={rpc_port}", f"-port={p2p_port}"],
            env=env, cwd=REPO, stdout=subprocess.PIPE, stderr=errf)
    try:
        wait_started(proc, work, stderr_path)
        emit({"phase": "start",
              "note": "smoke timing, includes compilation; not a "
                      "measurement",
              "spawn_to_started_s": round(time.monotonic() - t_spawn, 1)})

        def rpc(method, *params):
            return rpc_call(rpc_port, net_dir, method, *params)

        # -- reindex: the imported chain is the generated chain ----------
        chain = rpc("getblockchaininfo")
        check(failures, "reindex tip height", chain["blocks"]
              == gen["tip_height"], chain["blocks"])
        check(failures, "reindex tip hash", chain["bestblockhash"]
              == gen["tip_hash"], chain["bestblockhash"])
        emit({"phase": "reindex", "blocks": chain["blocks"],
              "bestblockhash": chain["bestblockhash"],
              "want_blocks": gen["tip_height"],
              "want_hash": gen["tip_hash"]})

        # -- serve ---------------------------------------------------------
        utxo = rpc("gettxoutsetinfo")
        check(failures, "gettxoutsetinfo tip",
              (utxo["height"], utxo["bestblock"])
              == (gen["tip_height"], gen["tip_hash"]),
              [utxo["height"], utxo["bestblock"]])
        check(failures, "gettxoutsetinfo txouts", utxo["txouts"]
              == gen["txouts"], utxo["txouts"])
        block = rpc("getblock", gen["tip_hash"])
        txid = block["tx"][1]  # first non-coinbase tx of the dense tip block
        txout = rpc("gettxout", txid, 0)
        check(failures, "gettxout answers an unspent output",
              bool(txout) and txout["value"] > 0, txout)
        emit({"phase": "serve", "txouts": utxo["txouts"],
              "want_txouts": gen["txouts"], "block_txs": len(block["tx"]),
              "gettxout": {"txid": txid, "value": txout and txout["value"]}})

        # -- mine ----------------------------------------------------------
        t0 = time.monotonic()
        mined = rpc("generatetoaddress", 3, rpc("getnewaddress"))
        height = rpc("getblockcount")
        check(failures, "mined 3 blocks", len(mined) == 3
              and height == gen["tip_height"] + 3, [mined, height])
        info = rpc("gettpuinfo")
        check(failures, "mining.engine == resident-h7",
              info["mining"]["engine"] == "resident-h7",
              info["mining"]["engine"])
        emit({"phase": "mine", "height": height,
              "engine": info["mining"]["engine"],
              "mine_s": round(time.monotonic() - t0, 1)})

        # -- nothing fell back ---------------------------------------------
        failures += no_fallback_checks(info, gen, cache_dir)
        dev = info["device"]
        for name, pw in dev["programs"].items():
            emit({"phase": "program", "program": name,
                  "compiles": pw["compiles"],
                  "compile_seconds": pw["compile_seconds"],
                  "dispatches": pw["dispatches"],
                  "shapes": pw["signatures"]})
        dd = info["ecdsa"]["dev_decompose"]
        emit({"phase": "device", "sigs_verified":
              info["batch"]["sigs_verified"],
              "cpu_fallback_sigs": info["batch"]["cpu_fallback_sigs"],
              "fault_fallback_sigs": info["batch"]["fault_fallback_sigs"],
              "kat_failures": info["batch"]["kat_failures"],
              "pallas_fallbacks": info["batch"]["pallas_fallbacks"],
              "kernel": info["ecdsa"]["kernel"],
              "glv_fallbacks": info["ecdsa"]["glv_fallbacks"],
              "dev_decompose": dd,
              "breakers": {n: b["state"]
                           for n, b in info["breakers"].items()},
              "compilation_cache": dev["compilation_cache"]["dir"],
              "cache_hits": dev["compilation_cache"]["cache_hits"]})
        try:
            rpc("stop")
            proc.wait(timeout=120)
        except (SmokeFailure, OSError, subprocess.TimeoutExpired) as e:
            failures.append({"check": "clean stop", "read": str(e)})
        if failures:
            raise SmokeFailure("checks failed:\n" + "\n".join(
                json.dumps(f) for f in failures))
        return {k: dev[k] for k in ("platform", "kind", "count")}
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
        proc.stdout.close()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--sigs", type=int, default=65520,
                    help="signatures in the generated chain (default: "
                         "8 x 8,190 = eight full 8,192-lane dispatches)")
    ap.add_argument("--txs-per-block", type=int, default=27,
                    help="250-input txs per dense block (default 27: "
                         "about 1 MB a block)")
    args = ap.parse_args()
    work = tempfile.mkdtemp(prefix="chip_smoke-")
    try:
        device = run(args.sigs, args.txs_per_block, work)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    finally:
        shutil.rmtree(work, ignore_errors=True)
    assert "jax" not in sys.modules, "the smoke's parent must stay off JAX"
    emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
