"""The node runtime — AppInitMain and friends.

Reference: src/init.cpp:~1200 (AppInitMain): logging, datadir, DB opens,
LoadBlockIndex, optional -reindex import, CVerifyDB startup integrity check,
mempool + validation-interface wiring, then servers (RPC here; P2P via
p2p/connman). Shutdown = flush everything, close stores (Shutdown(),
src/init.cpp:~150).

The whole node shares one re-entrant lock (`cs_main`) — RPC worker threads
and the P2P event loop serialize on it exactly like the reference's cs_main.
The exception, as in the reference, is the miner's nonce search:
`generate_to_script` holds `cs_main` for the template and for the connect,
and the `miner` lock (one search at a time; taken before `cs_main`, never
under it) for the whole call.
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque
from contextlib import contextmanager, nullcontext
from typing import Optional

from ..consensus.block import CBlock
from ..consensus.versionbits import VersionBitsCache
from ..consensus.serialize import hash_to_hex
from ..mempool.accept import accept_to_memory_pool
from ..mempool.mempool import CTxMemPool, MempoolError
from ..mining.assembler import BlockAssembler
from ..mining.generate import MAX_TRIES_DEFAULT, search_block
from ..store.blockstore import BlockStore
from ..store.chainstatedb import BlockIndexDB, CoinsDB
from ..store.kvstore import KVStore
from ..store.sharded import MANIFEST_NAME as _COINS_MANIFEST
from ..store.sharded import ShardedCoinsDB
from ..util import lockwatch, telemetry
from ..util.log import log_init, log_print, log_printf
from ..validation.chain import BlockStatus
from ..validation.chainstate import BlockValidationError, ChainstateManager
from ..validation.scriptcheck import BlockScriptVerifier
from ..validation.sigcache import SignatureCache
from .config import Config, ConfigError

DEFAULT_FLUSH_INTERVAL = 64  # blocks between periodic FlushStateToDisk calls

# last_import_stats' older times as sums of the native import's spans (each
# span's whole duration, wherever it nests): verify_s is the lanes' joining
# (the script leg inside it), the pack, the enqueue, the wait for verdicts
# and their settlement; fallback_s, the script leg, is inside verify_s too.
_IMPORT_LEGS = {
    "native_connect_s": ("import.connect",),
    "verify_s": ("import.lanes", "import.pack", "import.enqueue",
                 "import.settle_wait", "import.settle"),
    "fallback_s": ("import.script_leg",),
    "flush_s": ("import.flush",),
}

# explicit -telemetry levels a -tracefile sink contradicts (node startup
# rejects the combination rather than writing an empty dump)
MODES_BELOW_TRACE = ("off", "counters")


class InitError(Exception):
    pass


class _NativeImportAbort(Exception):
    """A staged fast-import block's signature batch failed after commit —
    recover by rebuilding from the last flush and replaying through the
    Python engine (node.import_block_files)."""


class _MultisigSettler:
    """Deferred OP_CHECKMULTISIG groups of the native import, settled as
    its dispatches settle (script/interpreter.py, module docstring).

    Lanes are numbered over the whole import, in the order they entered the
    aggregation; dispatches settle first in, first out, each a contiguous
    slice of those numbers, so a group is whole once the slice that holds
    its last lane has settled, whichever slice its first lane rode (a group
    straddling two dispatches settles with the later). A group whose walk
    fails on the verdicts goes to ``confirm(owner)``, which decides on the
    host: the device's word alone rejects nothing."""

    def __init__(self, confirm):
        self.confirm = confirm
        self.pending = deque()   # (first lane, MultisigGroup), lane order
        self.slices = deque()    # (first lane, verdicts) still needed

    def add(self, base: int, groups) -> None:
        self.pending.extend((base + g.start, g) for g in groups)

    def settled(self, first: int, ok) -> None:
        import numpy as np

        from ..ops import ecdsa_batch
        from ..script.interpreter import multisig_walk

        self.slices.append((first, ok))
        end = first + len(ok)
        with telemetry.span("import.multisig_settle", lanes=len(ok)):
            while self.pending and (
                    self.pending[0][0] + self.pending[0][1].lanes <= end):
                a, g = self.pending.popleft()
                b = a + g.lanes
                parts = [v[max(a - f, 0):b - f] for f, v in self.slices
                         if f < b and f + len(v) > a]
                verdicts = parts[0] if len(parts) == 1 else (
                    np.concatenate(parts))
                if not multisig_walk(g.m, g.n, verdicts):
                    ecdsa_batch.STATS.multisig_group_confirms += 1
                    self.confirm(g.owner)
        keep = self.pending[0][0] if self.pending else end
        while self.slices and (
                self.slices[0][0] + len(self.slices[0][1]) <= keep):
            self.slices.popleft()


class _StartupPhases:
    """Node.__init__'s stages as ``node.init.<phase>`` spans, one open at a
    time: enter(name) ends the stage before it. ``seconds`` is what
    gettpuinfo["startup"] shows: {phase: seconds}, in the order they
    ran."""

    def __init__(self):
        self.seconds: dict[str, float] = {}
        self._name = ""
        self._span = None

    def enter(self, name: str) -> None:
        self.close()
        self._name = name
        self._span = telemetry.span("node.init." + name)
        self._span.__enter__()

    def close(self) -> None:
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self.seconds[self._name] = (self.seconds.get(self._name, 0.0)
                                        + self._span.seconds)
            self._span = None


class _ShadowBlockStore:
    """Block-store facade for the assumeutxo shadow chainstate: reads
    delegate to the node's real store (under cs_main — BlockStore file
    handles aren't thread-safe against the main validation path), every
    write is a no-op (the real store already holds the data; the shadow
    exists only to re-derive the UTXO set)."""

    def __init__(self, node: "Node"):
        self._node = node

    def get_block(self, h: bytes):
        with self._node.cs_main:
            return self._node.block_store.get_block(h)

    def have_block(self, h: bytes) -> bool:
        return self.get_block(h) is not None

    def put_block(self, h: bytes, raw: bytes) -> None:
        pass

    def put_undo(self, h: bytes, raw: bytes) -> None:
        pass

    def get_undo(self, h: bytes):
        return None

    def flush(self) -> None:
        pass

    def close(self) -> None:
        pass


class Node:
    """One full node over a datadir. Construct → (optionally) start_rpc/start_p2p
    → work → close(). Usable in-process (tests) or via bcpd (cli/)."""

    # machine-enforced by bcplint BCP009 (the CConnman.GUARDED_BY
    # pattern): the sweep engine and the mining calls' tallies are written
    # by the one generate_to_script call that holds the miner lock (and by
    # close(), which takes it). mining_snapshot() reads them unlocked.
    GUARDED_BY = {
        "resident_miner": "miner_lock",
        "sweep_engine": "miner_lock",
        "_mining_calls": "miner_lock",
        "_mining_search_s": "miner_lock",
        "_mining_held_s": "miner_lock",
    }

    def __init__(self, config: Optional[Config] = None, datadir: Optional[str] = None,
                 network: Optional[str] = None):
        # gettpuinfo["startup"]: what a restart costs, stage by stage
        self._startup = _StartupPhases()
        # one generate_to_script call at a time owns the sweep engine
        # (ResidentSweep has no lock of its own: set_template / sweep from
        # two threads would interleave template generations). Order:
        # miner before cs_main, never the other way.
        self.miner_lock = lockwatch.watched_lock("miner")
        lockwatch.declare_guards("miner", self.GUARDED_BY)
        self.resident_miner = None
        self.sweep_engine = "unselected"
        # gettpuinfo.mining: calls, search_s, cs_main_held_s
        self._mining_calls = 0
        self._mining_search_s = 0.0
        self._mining_held_s = 0.0
        try:
            self._init(config, datadir, network)
        finally:
            self._startup.close()

    def _init(self, config: Optional[Config], datadir: Optional[str],
              network: Optional[str]) -> None:
        phase = self._startup.enter
        phase("config")
        if config is None:
            config = Config()
            if datadir:
                config.args["datadir"] = [datadir]
            if network == "regtest":
                config.args["regtest"] = ["1"]
            elif network in ("test", "testnet"):
                config.args["testnet"] = ["1"]
        self.config = config
        self.params = config.chain_params()
        self.datadir = config.datadir
        os.makedirs(self.datadir, exist_ok=True)
        log_init(
            logfile_path=os.path.join(self.datadir, "debug.log"),
            categories=config.get_multi("debug"),
            print_to_console=config.get_bool("printtoconsole"),
            json_mode=config.get_bool("logjson"),
        )
        # -tpu=1 is a requirement, not a preference: without a TPU the node
        # refuses to start (checked before any store opens)
        if config.tpu_backend == "tpu":
            import jax

            platform = jax.devices()[0].platform
            if platform != "tpu":
                raise InitError(
                    f"-tpu=1 needs a TPU, but JAX's first device is on "
                    f"platform {platform!r}")
        # -telemetry=<off|counters|trace> / -tracefile=<path>: resolved
        # BEFORE any import/reindex work so startup spans are captured.
        # Validated here — an unknown level must fail init like any other
        # malformed flag, not degrade silently (telemetry.set_mode raises).
        self.tracefile = config.get("tracefile") or None
        tmode = config.get("telemetry", "")
        if self.tracefile and tmode and tmode in MODES_BELOW_TRACE:
            # an explicit lower level with a trace sink would silently
            # write an empty dump — reject the contradiction instead
            raise ConfigError(
                f"-tracefile requires -telemetry=trace "
                f"(got -telemetry={tmode})")
        if self.tracefile and not tmode:
            tmode = "trace"  # a trace sink implies span tracing
        if tmode:
            try:
                telemetry.set_mode(tmode)
            except ValueError as e:
                raise ConfigError(str(e)) from None
        self.telemetry_mode = telemetry.mode()
        log_printf("bcpd init: network=%s datadir=%s", self.params.network, self.datadir)
        phase("stores")  # what ran before it: the log, the device's platform

        # -par=<n>: thread budget for the native CPU verify fallback
        # (src/init.cpp -par -> CCheckQueue worker count; here the TPU batch
        # is the worker pool, so -par bounds the HOST-side native threads).
        # Reference semantics kept: 0 = auto, -N = leave N cores free.
        from .. import native as _native

        par = config.get_int("par", 0)
        if par < 0:
            par = max(1, (os.cpu_count() or 1) + par)
        _native.PAR_THREADS = par

        # cs_main — one lock serializing all chainstate/mempool access.
        # Plain RLock normally; BCP_LOCKWATCH=1 substitutes the lockwatch
        # sentinel wrapper (util/lockwatch) that feeds the lock-order
        # graph behind gettpuinfo.lockwatch and the atexit cycle report.
        self.cs_main = lockwatch.watched_rlock("cs_main")
        self.shutdown_event = threading.Event()
        self.start_time = int(time.time())
        # wake channel for blocking RPCs (getblocktemplate longpoll,
        # waitfornewblock): notified on tip/mempool change. Waiters poll
        # their predicate under cs_main between short cv waits — notifiers
        # fire while holding cs_main, so waiters must never hold the cv
        # while taking cs_main in the other order.
        self.notify_cv = lockwatch.watched_condition("notify_cv")

        reindex = config.get_bool("reindex")
        self.last_import_stats: Optional[dict] = None
        blocks_dir = os.path.join(self.datadir, "blocks")
        index_path = os.path.join(blocks_dir, "index.sqlite")
        coins_path = os.path.join(self.datadir, "chainstate.sqlite")
        journal_path = os.path.join(self.datadir, "chainstate.journal")
        # -coinshards=<n>: hash-partition fan-out for the sharded coins
        # store (power of two, 1..256; validated by ShardedCoinsDB). An
        # existing sharded datadir's manifest pins the count — the flag
        # only picks the layout for a fresh datadir or a -reindex.
        coinshards = config.get_int("coinshards", 4)
        # -assumeutxo=<blockhash>:<muhash>: authorize loadtxoutset to
        # adopt a UTXO snapshot with exactly this tip hash and set digest
        # (both 32-byte hex, display order). Without it, loadtxoutset is
        # refused — snapshot trust is an explicit operator decision.
        self.assumeutxo: Optional[tuple[bytes, bytes]] = None
        au = config.get("assumeutxo", "")
        if au:
            try:
                h_hex, _, d_hex = au.partition(":")
                h_raw, d_raw = bytes.fromhex(h_hex), bytes.fromhex(d_hex)
                if len(h_raw) != 32 or len(d_raw) != 32:
                    raise ValueError
            except ValueError:
                raise ConfigError(
                    f"-assumeutxo={au!r}: expected "
                    "<blockhash_hex>:<muhash_hex> (32 bytes each)")
            # display order -> internal little-endian hash
            self.assumeutxo = (h_raw[::-1], d_raw)
        # Proof-carrying snapshot knobs (store/certificate.py):
        #  -snapshotepoch=<E>     checkpoint stride for the certificate's
        #                         MuHash trajectory built at dumptxoutset
        #  -snapshotspotcheck=<K> shadow validation re-runs full script
        #                         checks on only K seeded-drawn certified
        #                         epochs (0 = full re-validation); digest
        #                         checkpoints still fire at EVERY boundary
        #  -snapshotcertrequired  refuse certificate-less snapshots at
        #                         loadtxoutset instead of quarantining
        self.snapshot_epoch = config.get_int("snapshotepoch", 64)
        if self.snapshot_epoch < 1:
            raise ConfigError(
                f"-snapshotepoch={self.snapshot_epoch}: must be >= 1")
        self.snapshot_spotcheck = config.get_int("snapshotspotcheck", 0)
        if self.snapshot_spotcheck < 0:
            raise ConfigError(
                f"-snapshotspotcheck={self.snapshot_spotcheck}: must be "
                ">= 0")
        self.snapshot_cert_required = config.get_bool("snapshotcertrequired")
        # the seeded draw reuses -netseed so one seed replays an identical
        # spot-check drill end to end (orphan eviction included)
        self._spotcheck_seed: Optional[int] = (
            config.get_int("netseed", -1)
            if config.get_int("netseed", -1) >= 0 else None)
        if reindex:
            # wipe the derived state; blk*.dat files are the source of truth
            for p in (index_path, coins_path):
                for suffix in ("", "-wal", "-shm"):
                    if os.path.exists(p + suffix):
                        os.remove(p + suffix)
            for p in (journal_path, journal_path + ".tmp"):
                if os.path.exists(p):
                    os.remove(p)
            ShardedCoinsDB.wipe(self.datadir)
            import shutil as _shutil

            _shutil.rmtree(os.path.join(self.datadir, "chainstate_shadow"),
                           ignore_errors=True)
            if os.path.exists(self._snapshot_cert_path()):
                os.remove(self._snapshot_cert_path())
            # undo data is derived too: the import rebuilds every record,
            # and the wiped undo_positions would otherwise leave the old
            # records stranded in the rev files forever (the reference
            # rewrites undo during a reindex as well)
            import glob as _glob

            for p in _glob.glob(os.path.join(blocks_dir, "rev*.dat")):
                with open(p, "wb"):
                    pass
            log_printf("-reindex: wiped block index and chainstate")

        os.makedirs(blocks_dir, exist_ok=True)
        self._index_kv = KVStore(index_path)
        # -maxblockfilesize: test/debug knob for block-file rotation (the
        # reference's MAX_BLOCKFILE_SIZE constant) — lets functional tests
        # exercise pruning without writing 128 MiB of chain
        self.block_store = BlockStore(
            self.datadir, self.params.netmagic,
            max_file_size=config.get_int("maxblockfilesize",
                                         128 * 1024 * 1024),
        )
        self.index_db = BlockIndexDB(self._index_kv)
        # journaled coins commits: every connect/disconnect batch is made
        # durable (fsync-before-rename) before it touches the DB, and
        # ChainstateManager replays/rolls back the journal at startup —
        # a crash at ANY point inside a commit leaves the UTXO set at
        # exactly the pre- or post-block state, never a torn mix.
        # Layout selection: a datadir with the legacy single chainstate
        # file and no shard manifest keeps the old CoinsDB unchanged (the
        # 1-shard degenerate case with the old paths); everything else —
        # fresh datadirs, -reindex, existing sharded datadirs — goes
        # through the sharded facade (store/sharded.py).
        manifest_path = os.path.join(self.datadir, _COINS_MANIFEST)
        if os.path.exists(coins_path) and not os.path.exists(manifest_path):
            self._coins_kv: Optional[KVStore] = KVStore(coins_path)
            self.coins_db = CoinsDB(self._coins_kv,
                                    journal_path=journal_path)
            log_printf("chainstate: legacy single-file layout "
                       "(-reindex migrates to the sharded store)")
        else:
            self._coins_kv = None
            try:
                self.coins_db = ShardedCoinsDB(
                    self.datadir, n_shards=coinshards,
                    wal=config.get_bool("coinswal"))
            except ValueError as e:
                raise ConfigError(f"-coinshards={coinshards}: {e}")
            if self.coins_db.n_shards != coinshards:
                log_printf("chainstate: manifest pins %d shard(s) "
                           "(-coinshards=%d ignored)",
                           self.coins_db.n_shards, coinshards)
        # assumeutxo bookkeeping: a loaded-but-unvalidated snapshot serves
        # RPC at its tip while a background thread re-validates history
        # into a shadow chainstate (load_utxo_snapshot / _snapshot_verify)
        self.snapshot_state: Optional[dict] = getattr(
            self.coins_db, "snapshot_state", None)
        self._snapshot_pending = bool(
            self.snapshot_state
            and not self.snapshot_state.get("validated"))
        self._snapshot_thread: Optional[threading.Thread] = None
        # certificate epoch checkpoints {height: digest_hex} persisted at
        # load time so a restarted shadow validation keeps its O(E)
        # divergence detection instead of regressing to trust-until-tip
        self._cert_checkpoints: Optional[dict] = None
        if self._snapshot_pending:
            from ..store.kvstore import read_json as _read_json

            doc = _read_json(self._snapshot_cert_path())
            if doc and doc.get("checkpoints"):
                self._cert_checkpoints = {
                    int(h): d for h, d in doc["checkpoints"].items()}

        phase("device")  # kernel selection, the compile cache, the services
        # -maxsigcachesize=<MiB>: byte budget for the signature cache
        # (src/init.cpp DEFAULT_MAX_SIG_CACHE_SIZE). The entry cap is
        # derived FROM the byte budget so the knob governs alone — a fixed
        # entry default would silently bind first above ~17 MiB
        from ..validation.sigcache import ENTRY_COST_BYTES

        sc_bytes = max(1, config.get_int("maxsigcachesize", 32)) * 1024 * 1024
        self.sigcache = SignatureCache(
            max_entries=max(1024, sc_bytes // ENTRY_COST_BYTES),
            max_bytes=sc_bytes,
        )
        self.versionbits_cache = VersionBitsCache()
        backend = config.tpu_backend
        self.backend = backend
        # -ecdsakernel=<glv|w4|msm>: device verify kernel selection. Validated
        # HERE, at startup — an unknown value must fail init (like a
        # malformed -maxsigcachesize), not surface as a per-batch fallback
        # at the first block (ops/ecdsa_batch.set_kernel raises on junk)
        from ..ops import ecdsa_batch as _eb

        if config.has("ecdsakernel"):
            try:
                self.ecdsa_kernel = _eb.set_kernel(config.get("ecdsakernel"))
            except ValueError as e:
                raise ConfigError(str(e)) from None
        else:
            self.ecdsa_kernel = _eb.active_kernel()
        # under -tpu=1 a kernel the chip's compiler refuses stops the node
        # instead of degrading a rung
        _eb.require_device(backend == "tpu")
        # what the dispatch layer understands: block connect is forced to
        # the device under -tpu=1; the mempool and SigService keep the lane
        # floor (a one-signature tx is not padded to a 1,024-lane dispatch)
        self.connect_backend = _eb.dispatch_backend(backend)
        self.serve_backend = _eb.dispatch_backend(backend, lane_floor=True)
        # -compilecache=<dir>: persistent XLA compilation cache, default ON
        # at util/devicewatch.compile_cache_dir (JAX_COMPILATION_CACHE_DIR
        # beats the flag; <checkout>/.jax_cache when neither is given). The
        # GLV verify programs are minutes of cold compile per bucket —
        # every restart and child process after the first pays a disk read
        # instead; cache hits surface in gettpuinfo.device.
        from ..util import devicewatch as _dwcc

        cache_flag = config.get("compilecache", "")
        try:
            self.compile_cache = _dwcc.enable_compile_cache(
                cache_flag)["dir"]
        except (OSError, ValueError) as e:
            raise ConfigError(
                f"-compilecache={cache_flag}: {e}") from None
        # -cashdaa / -daaheight=<n>: enable the BCH-lineage difficulty
        # rules (EDA from activation, cw-144 DAA from daaheight) on this
        # chain — the fork-storm harness crosses the EDA->DAA boundary
        # mid-reorg with these (consensus/pow.py). Applied to the frozen
        # params BEFORE any consensus object is built so every consumer
        # (chainstate, assembler, P2P header checks) sees one rule set.
        if config.get_bool("cashdaa"):
            import dataclasses as _dc

            daa_height = config.get_int("daaheight", 0)
            if daa_height < 0:
                raise ConfigError(
                    f"-daaheight={daa_height}: must be >= 0")
            self.params = _dc.replace(
                self.params,
                consensus=_dc.replace(self.params.consensus,
                                      use_cash_daa=True,
                                      daa_height=daa_height))
        # -uahfheight=<n>: the height from which blocks carry the fork's
        # flag bundle (FORKID, STRICTENC, LOW_S, NULLFAIL, NULLDUMMY), on
        # regtest only, where it is 0 otherwise: the door to a chain with
        # history below the fork height (ABC's -uahfstarttime was this
        # door). block_script_flags reads it from the parameters.
        if config.has("uahfheight"):
            import dataclasses as _dc

            if self.params.network != "regtest":
                raise ConfigError(
                    "-uahfheight is a regtest option: the fork height of "
                    f"{self.params.network} is part of its consensus")
            uahf_height = config.get_int("uahfheight", 0)
            if uahf_height < 0:
                raise ConfigError(
                    f"-uahfheight={uahf_height}: must be >= 0")
            self.params = _dc.replace(
                self.params,
                consensus=_dc.replace(self.params.consensus,
                                      uahf_height=uahf_height))
        verifier = BlockScriptVerifier(self.params,
                                       backend=self.connect_backend,
                                       sigcache=self.sigcache,
                                       kernel=self.ecdsa_kernel)
        self.chainstate = ChainstateManager(
            self.params, self.coins_db, self.block_store,
            script_verifier=verifier, index_db=self.index_db,
        )
        # -sigservice=<on|off> / -sigservicedeadline=<ms> /
        # -sigservicelanes=<n>: the always-on micro-batching signature
        # service (serving/sigservice). Default ON — with the service off
        # every caller runs the unchanged synchronous path (verdicts
        # identical by construction). Validated here: junk must fail init,
        # not surface at the first transaction.
        svc_mode = config.get("sigservice", "on")
        if svc_mode not in ("on", "off", "1", "0"):
            raise ConfigError(
                f"-sigservice={svc_mode!r}: must be on or off")
        # -watchdogquiet=<seconds>: stall-watchdog quiet period for the
        # SigService flush thread and the pipeline settle horizon
        # (util/devicewatch; observe-only — a stall fires a gauge, a log
        # warning, and a trace instant, never a kill). 0 disables
        # detection; the gauges still export.
        self.watchdog_quiet = config.get_int("watchdogquiet", 10)
        from ..util import devicewatch as _dw

        _dw.WATCHDOG.register(
            "pipeline",
            pending_fn=lambda: len(self.chainstate._spec),
            quiet_s=self.watchdog_quiet)
        # -residentminer=<on|off>: the device-resident mining loop
        # (mining/resident.ResidentSweep — ISSUE 10). Default ON where a
        # batched sweep runs at all; regtest CPU nodes keep the scalar
        # host fast path regardless (see _select_sweep). off = the
        # per-dispatch sweep shapes of PR <=9.
        res_mode = config.get("residentminer", "on")
        if res_mode not in ("on", "off", "1", "0", "force"):
            raise ConfigError(
                f"-residentminer={res_mode!r}: must be on, off or force")
        self.resident_mode = res_mode in ("on", "1", "force")
        # "force" overrides the regtest-CPU scalar fast path too (test/
        # bench hook: exercises the resident loop where mining is trivial)
        self.resident_force = res_mode == "force"
        self.sigservice = None
        if svc_mode in ("on", "1"):
            from ..serving import SigService

            try:
                self.sigservice = SigService(
                    sigcache=self.sigcache,
                    backend=self.serve_backend,
                    kernel=self.ecdsa_kernel,
                    deadline_ms=config.get_int("sigservicedeadline", 4),
                    lanes=config.get_int("sigservicelanes", 2046),
                    watchdog_quiet=self.watchdog_quiet,
                    # -sigservicebuffers=<n>: in-flight flush slots — 2
                    # overlaps host pack of flush N+1 with device verify
                    # of flush N (1 = the single-slot PR 7 loop)
                    buffers=config.get_int("sigservicebuffers", 2),
                ).start()
            except ValueError as e:
                raise ConfigError(str(e)) from None
            self.chainstate.sig_service = self.sigservice
        # -pipelinedepth=<n>: settle-horizon depth for the Python IBD
        # engine — up to n blocks speculatively connected while their
        # signature batches are in flight (1 = serial; see README
        # "Pipelined validation & the settle horizon")
        self.pipeline_depth = max(1, config.get_int("pipelinedepth", 4))
        self.chainstate.pipeline_depth = self.pipeline_depth
        # -specbranches=<n>: cap on concurrently-validating speculation-
        # tree branches (competing tips); -spechold=<ms>: live-path settle
        # grace — a tip younger than this stays speculative so a fork-race
        # competitor can join the tree (0 = settle eagerly, the default;
        # see README "Speculation tree & fork storms")
        self.spec_branches = config.get_int("specbranches", 4)
        if self.spec_branches < 1:
            raise ConfigError(
                f"-specbranches={self.spec_branches}: must be >= 1")
        spec_hold_ms = config.get_int("spechold", 0)
        if spec_hold_ms < 0:
            raise ConfigError(f"-spechold={spec_hold_ms}: must be >= 0")
        self.spec_hold_s = spec_hold_ms / 1e3
        self.chainstate.max_branches = self.spec_branches
        self.chainstate.spec_hold_s = self.spec_hold_s
        phase("index")
        loaded = self.chainstate.load_block_index()
        if loaded:
            log_printf("block index loaded: tip height %d",
                       self.chainstate.tip().height)
        if self._snapshot_pending and loaded:
            # restart mid-assumeutxo: headers along the snapshot chain
            # have no block data yet, so load_block_index left their
            # chain_tx at 0 and parked every descendant — restore the
            # fake linkage before candidate selection runs
            self._fake_snapshot_chaintx()

        phase("import")
        if reindex:
            n = self.import_block_files()
            log_printf("-reindex: imported %d blocks, tip height %d",
                       n, self.chainstate.tip().height)
        else:
            # pick up blocks whose index rows were flushed but that were not
            # yet connected at crash time
            self.chainstate.activate_best_chain()
        # -loadblock=<file>: bootstrap.dat-style external imports
        # (init.cpp ThreadImport's vImportFiles leg)
        load_files = config.get_multi("loadblock")
        if load_files:
            n = self.import_block_files(list(load_files))
            log_printf("-loadblock: imported %d blocks, tip height %d",
                       n, self.chainstate.tip().height)

        phase("verify_db")
        if self._snapshot_pending:
            # -checkblocks replays recent blocks from local data; below an
            # unvalidated snapshot tip there is none yet. The background
            # verify thread is the (much stronger) integrity check here.
            log_printf("assumeutxo: skipping -checkblocks replay — "
                       "history below the snapshot tip is not local yet")
        else:
            self.verify_db(
                n_blocks=config.get_int("checkblocks", 6),
                level=config.get_int("checklevel", 3),
            )

        phase("services")  # mempool, collectors, indexes, the last flush
        self.mempool = CTxMemPool(
            max_size_bytes=config.get_int("maxmempool", 300) * 1_000_000,
            expiry_seconds=config.get_int("mempoolexpiry", 336) * 3600,
            # -mempoolbatch=0 pins the per-tx reference paths everywhere
            # (the differential suite's control); -mempoolselfcheck=1
            # runs the batched-vs-reference gate on every template
            # selection / eviction verdict (debug, like -checkmempool)
            batch=config.get_bool("mempoolbatch", True),
            selfcheck=config.get_bool("mempoolselfcheck", False),
        )
        self.min_relay_fee_rate = config.get_int("minrelaytxfee", 1000)
        # registry collectors (util/telemetry): project this node's
        # sigcache / pipeline / bench / mempool state into the unified
        # metrics namespace at scrape time — the STATS-migration pattern
        # (gettpuinfo keeps reading the same sources directly). A fresh
        # node replaces a closed one's collectors by name.
        telemetry.register_collector("sigcache", self._sigcache_families)
        telemetry.register_collector("pipeline", self._pipeline_families)
        telemetry.register_collector("mempool", self._mempool_families)
        telemetry.register_collector("mempool_perf",
                                     self._mempool_perf_families)
        telemetry.register_collector("mining", self._mining_families)
        telemetry.register_collector("store", self._store_families)
        if self.sigservice is not None:
            telemetry.register_collector("serving", self._serving_families)
        if lockwatch.enabled():
            telemetry.register_collector("lockwatch",
                                         self._lockwatch_families)
        # P2P adversarial-supervision limits (p2p/connman.py): the
        # ban-score discharge threshold, the block-download stall timeout,
        # the supervision tick cadence, the per-peer receive-rate ceiling
        # (bytes/sec, 0 = unlimited), and the deterministic net rng seed
        # (-1 = OS entropy; chaos campaigns pin it for replayability)
        self.net_limits = {
            "banscore": config.get_int("banscore", 100),
            "blockdownloadtimeout":
                config.get_int("blockdownloadtimeout", 60),
            "nettick": config.get_int("nettick", 5),
            "maxrecvrate": config.get_int("maxrecvrate", 4_000_000),
            "netseed": config.get_int("netseed", -1),
            "maxunconnectingheaders":
                config.get_int("maxunconnectingheaders", 10),
        }
        bft = config.get_int("backfilltimeout", 0)
        if bft > 0:
            self.net_limits["backfilltimeout"] = bft
        # -limitancestorcount/-limitancestorsize (kB)/-limitdescendantcount/
        # -limitdescendantsize (kB): ATMP chain limits (validation.h defaults)
        self.ancestor_limits = {
            "limit_count": config.get_int("limitancestorcount", 25),
            "limit_size": config.get_int("limitancestorsize", 101) * 1000,
            "limit_desc": config.get_int("limitdescendantcount", 25),
            "limit_desc_size":
                config.get_int("limitdescendantsize", 101) * 1000,
        }
        # CBlockPolicyEstimator (src/policy/fees.cpp): bucketed
        # confirmation-target tracking with exponential decay, persisted
        # across restarts (mempool/fees.py); fed from accept_to_mempool
        # (entry), _on_block_connected (confirmation), and the mempool
        # removal hook (eviction/expiry/conflict = drop tracking).
        from ..mempool.fees import FeeEstimator

        self.fee_estimator = FeeEstimator(
            os.path.join(self.datadir, "fee_estimates.json")
        )
        # non-block removals (expiry, eviction, conflict) drop tracking;
        # block confirmations are consumed by _on_block_connected FIRST
        self.mempool.on_removed = self.fee_estimator.remove_tx
        self.chainstate.on_block_connected.append(self._on_block_connected)
        self.chainstate.on_block_disconnected.append(self._on_block_disconnected)

        self.flush_interval = config.get_int("flushinterval", DEFAULT_FLUSH_INTERVAL)
        self._blocks_since_flush = 0
        # -dbcache=<MiB>: coins-cache memory budget (init.cpp nCoinCacheUsage
        # -> the FlushStateToDisk IfNeeded trigger). Exceeding it forces a
        # flush regardless of the block-interval policy.
        self.dbcache_bytes = max(1, config.get_int("dbcache", 300)) * 1024 * 1024
        # -prune: 0 = off, 1 = manual (pruneblockchain RPC), >1 = target MB
        prune_arg = config.get_int("prune", 0)
        self.prune_mode = prune_arg > 0
        self.prune_target_bytes = prune_arg * 1_000_000 if prune_arg > 1 else 0
        stored_ph = self._index_kv.get(b"Fpruneheight")
        self.prune_height = int(stored_ph) if stored_ph else 0
        self.txindex = config.get_bool("txindex")
        if self.txindex and self.prune_mode:
            raise InitError("Prune mode is incompatible with -txindex.")
        self._txindex_thread = None
        self._txindex_synced = not self.txindex
        if self.txindex:
            self._start_txindex_backfill()
        self.chainstate.flush()  # persist the (possibly fresh) index/genesis

        self.rpc_server = None
        self.connman = None  # set by start_p2p
        # fleet serving front door (serving/gateway, ISSUE 16):
        # -gateway=<port> binds the admission-controlled load balancer,
        # -replicas=<host:port,...> names the snapshot-bootstrapped read
        # replicas, -maxreplicalag bounds how far a served replica may
        # trail the pool fan-out height (the consistency gate). Flags are
        # validated here so a malformed fleet spec fails init, not the
        # first probe.
        self.gateway = None  # set by start_gateway
        self.gateway_port = config.get_int("gateway", 0)
        if self.gateway_port < 0 or self.gateway_port > 65535:
            raise ConfigError(f"-gateway: invalid port {self.gateway_port}")
        self.max_replica_lag = config.get_int("maxreplicalag", 2)
        if self.max_replica_lag < 0:
            raise ConfigError(
                f"-maxreplicalag must be >= 0 (got {self.max_replica_lag})")
        self.replica_addrs: list[tuple[str, int]] = []
        for spec in str(config.get("replicas", "")).split(","):
            spec = spec.strip()
            if not spec:
                continue
            host, _, port = spec.rpartition(":")
            try:
                self.replica_addrs.append((host or "127.0.0.1", int(port)))
            except ValueError:
                raise ConfigError(
                    f"-replicas: malformed entry '{spec}' "
                    f"(want host:port[,host:port...])") from None
        self.wallet = None  # set by load_wallet
        # wallet-load coordination: RPC threads arriving while another
        # thread is mid-rescan must NOT see partial coin state (the rescan
        # yields cs_main between chunks); they wait on this event instead
        self._wallet_ready = threading.Event()
        self._wallet_loader: Optional[int] = None

        # -zmqpub<topic>=<endpoint> (src/zmq/): like the reference, each
        # distinct endpoint gets its own PUB socket; topics sharing an
        # endpoint share a socket. Accepted forms: tcp://host:port,
        # host:port, or a bare port (host defaults to loopback).
        self.zmq_publishers = []
        by_endpoint: dict[tuple[str, int], set] = {}
        for topic in ("hashblock", "hashtx", "rawblock", "rawtx"):
            val = config.get(f"zmqpub{topic}")
            if not val:
                continue
            spec = str(val)
            if spec.startswith("tcp://"):
                spec = spec[len("tcp://"):]
            host, _, port = spec.rpartition(":")
            by_endpoint.setdefault(
                (host or "127.0.0.1", int(port)), set()).add(topic)
        if by_endpoint:
            from ..rpc.zmq import ZMQPublisher

            for (host, port), topics in by_endpoint.items():
                pub = ZMQPublisher(self, port, topics, host=host)
                pub.start()
                self.zmq_publishers.append(pub)
            self.chainstate.on_block_connected.append(self._zmq_block)

        # LoadMempool (src/validation.cpp): replay mempool.dat unless
        # -persistmempool=0 or we just rebuilt the chainstate
        self.persist_mempool = config.get_bool("persistmempool", True)
        self._mempool_dat = os.path.join(self.datadir, "mempool.dat")
        if self.persist_mempool and not reindex:
            from ..mempool.persist import load_mempool

            load_mempool(self, self._mempool_dat)

        if self._snapshot_pending:
            # restart with an unvalidated snapshot: resume background
            # history validation (the shadow chainstate persisted its own
            # progress, so this picks up where the last run stopped)
            self._start_snapshot_verify()

    # -- telemetry collectors (util/telemetry registry) -----------------

    def _sigcache_families(self) -> list:
        return telemetry.flat_families(
            "bcp_sigcache", self.sigcache.snapshot(), typ="gauge",
            help="validation/sigcache state (entries/bytes gauges, "
                 "hit/miss/insert/eviction tallies)")

    def _pipeline_families(self) -> list:
        cs = self.chainstate
        out = telemetry.flat_families(
            "bcp_pipeline", cs.pipeline_snapshot(), typ="gauge",
            help="pipelined-IBD settle horizon (chainstate.pipeline_stats "
                 "+ cross-block lane packer)")
        out += telemetry.flat_families(
            "bcp_connectblock", cs.bench, typ="counter",
            help="cumulative ConnectBlock phase timings (ms)")
        out += telemetry.flat_families(
            "bcp_bip30", cs.bip30_stats, typ="counter",
            help="BIP30 pre-scan fast-path counters")
        return out

    def _serving_families(self) -> list:
        snap = self.sigservice.snapshot()
        # queue_depth excluded: the native bcp_sigservice_queue_depth
        # gauge owns that name (re-emitting it here would duplicate the
        # family with a conflicting TYPE — the PR 6 in_flight lesson).
        # typ="gauge" like the sibling sigcache collector: the snapshot
        # mixes monotonic tallies with genuinely non-monotonic values
        # (priority_depth, inflight_keys) and config scalars — a TYPE of
        # counter would make rate()/increase() fabricate resets on every
        # decrease.
        snap.pop("queue_depth", None)
        scalars = {k: v for k, v in snap.items()
                   if isinstance(v, (int, float)) and not isinstance(v, bool)}
        return telemetry.flat_families(
            "bcp_sigservice", scalars, typ="gauge",
            help="serving/sigservice micro-batching state (flush reasons, "
                 "dedup/cache hits, preemptions, config)")

    def _mining_families(self) -> list:
        # bcp_mining_state_* prefix: the NATIVE bcp_mining_* counter/
        # histogram families (mining/resident module-level) own their
        # names — re-emitting fifo_depth/tiles under them would duplicate
        # a family with a conflicting TYPE (the PR 6 in_flight lesson).
        # Everything here is a point-in-time projection, so typ="gauge".
        snap = self.mining_snapshot()
        scalars = {k: v for k, v in snap.items()
                   if isinstance(v, (int, float)) and not isinstance(v, bool)}
        return telemetry.flat_families(
            "bcp_mining_state", scalars, typ="gauge",
            help="device-resident mining loop state (template generation, "
                 "segment pipeline, candidate FIFO, rollover passes)")

    def _store_families(self) -> list:
        # bcp_store_state_* prefix: the NATIVE bcp_store_flush_seconds
        # histogram and bcp_store_shard_bytes gauge (store/sharded
        # module-level) own their names — this collector only projects
        # the facade's scalar state (same PR 6 name-ownership lesson as
        # the mining/serving collectors).
        stats_fn = getattr(self.coins_db, "stats", None)
        if stats_fn is None:
            scalars = {"shards": 1, "snapshot_pending": 0}
        else:
            s = stats_fn()
            lf = s.get("last_flush") or {}
            scalars = {
                "shards": s["shards"],
                "epoch": s["epoch"],
                "last_flush_seconds": lf.get("seconds", 0.0),
                "last_flush_coins": lf.get("coins", 0),
                "snapshot_pending": 1 if self._snapshot_pending else 0,
            }
        return telemetry.flat_families(
            "bcp_store_state", scalars, typ="gauge",
            help="sharded chainstate facade state (fan-out, commit epoch, "
                 "last flush, assumeutxo progress)")

    def _mempool_families(self) -> list:
        return [
            {"name": "bcp_mempool_size", "type": "gauge",
             "help": "Transactions in the mempool",
             "samples": [({}, len(self.mempool.entries))]},
            {"name": "bcp_mempool_bytes", "type": "gauge",
             "help": "Serialized mempool size (bytes)",
             "samples": [({}, self.mempool.total_size)]},
        ]

    def _mempool_perf_families(self) -> list:
        # batch-shape observability (ISSUE 20): frontier depths and
        # column occupancy as gauges, the monotone tallies as counters
        snap = self.mempool.perf_snapshot()
        gauges = {
            "frontier_depth_mining": snap["frontier_depth"]["mining"],
            "frontier_depth_evict": snap["frontier_depth"]["evict"],
            "columns_live": snap["columns"]["live"],
            "columns_capacity": snap["columns"]["capacity"],
            "batch": 1 if snap["batch"] else 0,
        }
        counters = {
            "column_syncs": snap["column_syncs"],
            "rows_synced": snap["rows_synced"],
            "frontier_pushes": snap["frontier_pushes"],
            "frontier_stale_pops": snap["frontier_stale_pops"],
            "frontier_rebuilds": snap["frontier_rebuilds"],
            "bulk_evict_episodes": snap["bulk_evict_episodes"],
            "bulk_evicted": snap["bulk_evicted"],
            "staged_removals": snap["staged_removals"],
            "select_batched": snap["select_batched"],
            "select_fallbacks": snap["select_fallbacks"],
            "trim_fallbacks": snap["trim_fallbacks"],
            "selfchecks": snap["selfchecks"],
            "poisoned_verdicts": snap["poisoned_verdicts"],
        }
        return (telemetry.flat_families(
                    "bcp_mempool_perf", gauges, typ="gauge",
                    help="flood-scale mempool state (frontier depth, "
                         "column occupancy, batch mode)")
                + telemetry.flat_families(
                    "bcp_mempool_perf", counters, typ="counter",
                    help="flood-scale mempool tallies (column syncs, "
                         "stale pops, bulk evictions, fallback/gate "
                         "verdicts)"))

    def _lockwatch_families(self) -> list:
        # only registered when the BCP_LOCKWATCH sentinel is on; the
        # bcp_lockwatch prefix owns its namespace (no native families)
        snap = lockwatch.snapshot()
        scalars = {
            "locks": len(snap.get("locks", ())),
            "acquisitions_total": snap.get("acquisitions_total", 0),
            "max_depth": snap.get("max_depth", 0),
            "order_edges": len(snap.get("order_edges", ())),
            "inversions": snap.get("inversions", 0),
        }
        return telemetry.flat_families(
            "bcp_lockwatch", scalars, typ="gauge",
            help="runtime lock-order sentinel (util/lockwatch, "
                 "BCP_LOCKWATCH=1)")

    # -- validation-interface callbacks (CMainSignals analogues) --------

    def notify_waiters(self) -> None:
        """Wake longpoll/waitforblock RPC waiters."""
        with self.notify_cv:
            self.notify_cv.notify_all()

    def wait_for(self, pred, timeout: float):
        """Run pred() under cs_main until it returns non-None or timeout
        (seconds). Returns pred's value or the final (timed-out) value."""
        deadline = time.time() + max(timeout, 0.0)
        while True:
            with self.cs_main:
                val = pred()
            if val is not None:
                return val
            remaining = deadline - time.time()
            if remaining <= 0 or self.shutdown_event.is_set():
                with self.cs_main:
                    return pred()
            with self.notify_cv:
                # bounded wait: a notify can race the re-check, so cap the
                # sleep instead of trusting wakeups alone
                self.notify_cv.wait(min(remaining, 0.5))

    def _on_block_connected(self, block: CBlock, idx) -> None:
        # fee estimator: confirmations MUST be processed before
        # remove_for_block fires on_removed, or confirmed txs would be
        # dropped from tracking as if they failed (fees.py contract)
        self.fee_estimator.process_block(
            idx.height, [tx.txid for tx in block.vtx[1:]]
        )
        self.mempool.remove_for_block(block.vtx)
        if self.txindex:
            self._txindex_add(block, idx)
        self._blocks_since_flush += 1
        if (self._blocks_since_flush >= self.flush_interval
                or self.chainstate.coins.estimated_bytes()
                >= self.dbcache_bytes):
            self.chainstate.flush()
            self._blocks_since_flush = 0
            if self.prune_mode:
                self.auto_prune()
        # -blocknotify=<cmd>: run the shell hook with %s = new block hash
        # (init.cpp BlockNotifyCallback); fire-and-forget, never blocks
        # validation, only on the active tip like the reference. Settled
        # tip, not chain.tip(): during a pipelined import this callback
        # fires at settle time while newer SPECULATIVE blocks sit ahead on
        # the in-memory chain — idx IS the externalizable tip then.
        cmd = self.config.get("blocknotify")
        if cmd and self.chainstate.settled_tip() is idx:
            import subprocess

            from ..consensus.serialize import hash_to_hex as _h2h

            try:
                subprocess.Popen(cmd.replace("%s", _h2h(idx.hash)), shell=True)
            except OSError as e:
                log_printf("blocknotify failed: %r", e)
        self.notify_waiters()

    def _on_block_disconnected(self, block: CBlock, idx) -> None:
        # BlockDisconnected: return the block's transactions to the mempool
        # (reference: DisconnectTip -> mempool resurrection)
        for tx in block.vtx[1:]:
            try:
                # resurrection: entry height unknowable -> no fee sample;
                # use_service=False — this runs mid-disconnect and must
                # never release cs_main around the verdict
                self.accept_to_mempool(tx, fee_estimate=False,
                                       use_service=False)
            except MempoolError:
                pass  # no-longer-valid txs just drop

    def _zmq_publish(self, topic: str, body: bytes) -> None:
        for pub in self.zmq_publishers:  # each filters by its own topics
            pub.publish(topic, body)

    def _zmq_block(self, block: CBlock, idx) -> None:
        """CZMQNotificationInterface::BlockConnected +
        TransactionAddedToMempool-for-confirmed-txs: hashblock/rawblock for
        the block, hashtx/rawtx per transaction."""
        if not self.zmq_publishers:  # torn down mid-shutdown
            return
        if self.chainstate.settled_tip() is not idx:
            return  # only settled-tip connects notify (see -blocknotify)
        self._zmq_publish("hashblock", idx.hash[::-1])  # RPC byte order
        self._zmq_publish("rawblock", block.serialize())
        for tx in block.vtx:
            self._zmq_publish("hashtx", tx.txid[::-1])
            self._zmq_publish("rawtx", tx.serialize())

    # -- mempool entry point -------------------------------------------

    @contextmanager
    def _verify_wait(self):
        """SigService verdict-wait window: release cs_main (when held by
        this thread, exactly one level deep) so concurrent accepts can
        scan and share the in-flight device bucket; reacquire before the
        caller resumes. A deeper re-entrant hold just skips the release —
        correct (the post-wait stale-context re-check finds an unchanged
        world), only less concurrent."""
        released = False
        try:
            self.cs_main.release()
            released = True
        except RuntimeError:
            pass  # not held by us — nothing to release
        try:
            yield
        finally:
            if released:
                self.cs_main.acquire()

    def accept_to_mempool(self, tx, now: Optional[int] = None,
                          fee_estimate: bool = True,
                          use_service: bool = True):
        """AcceptToMemoryPool with this node's policy knobs; caller holds
        cs_main (or is single-threaded). fee_estimate=False for replayed
        txs (mempool.dat reload, reorg resurrection) — their true entry
        height is unknown, and counting them from the current tip would
        bias tight-target estimates low (the reference's
        validFeeEstimate=false). use_service=False keeps the verdict
        synchronous AND the lock held throughout — required on the reorg
        resurrection path, where releasing cs_main mid-disconnect would
        expose half-reorged chainstate to other threads."""
        svc = self.sigservice if use_service else None
        entry = accept_to_memory_pool(
            self.mempool, self.chainstate, tx,
            sigcache=self.sigcache,
            min_fee_rate=self.min_relay_fee_rate,
            backend=self.serve_backend,
            now=now,
            ancestor_limits=self.ancestor_limits,
            sig_service=svc,
            wait_ctx=self._verify_wait if svc is not None else None,
        )
        # fee estimator: track entry height + what the tx actually pays
        # (base fee, not prioritisetransaction-modified fees)
        if fee_estimate and entry.size > 0:
            self.fee_estimator.process_tx(
                tx.txid, self.chainstate.tip().height,
                entry.base_fee * 1000 / entry.size,
            )
        # TransactionAddedToMempool (validationinterface): a loaded wallet
        # tracks unconfirmed receives/spends so it won't double-spend coins
        # already committed by in-pool txs (e.g. after a mempool.dat reload)
        if self.wallet is not None:
            self.wallet.add_tx_if_mine(tx, -1, False)
        if self.zmq_publishers:
            # TransactionAddedToMempool → hashtx/rawtx
            self._zmq_publish("hashtx", tx.txid[::-1])
            self._zmq_publish("rawtx", tx.serialize())
        self.notify_waiters()
        return entry

    # -- mining ---------------------------------------------------------

    def assembler(self) -> BlockAssembler:
        return BlockAssembler(self.chainstate, self.mempool,
                              versionbits_cache=self.versionbits_cache)

    def _select_sweep(self):
        """Pick the PoW sweep for this backend. Default: the DEVICE-
        RESIDENT loop (mining/resident.ResidentSweep, -residentminer=on) —
        a persistent segment pipeline over long-lived template buffers,
        h7-truncated kernel on a real accelerator (fewest ops/nonce,
        candidates host-verified bit-identical) and the exact-compare
        kernel on CPU backends (where the sweep's digest is the looped
        compress, which has no truncated form to save ops with —
        ops/miner._sweep_tile). With
        -residentminer=off, the PR<=9 per-dispatch shapes: truncated-h7
        sweep_header_fast on the accelerator, the generic looped sweep on
        CPU. Every choice runs under miner-breaker supervision
        (ops/dispatch.supervised_sweep): failures degrade to the scalar
        host loop without stalling block production.

        Regtest on a CPU backend takes the scalar host loop DIRECTLY: the
        trivial target hits within a couple of nonces, so the batched
        sweep's per-dispatch latency (~160 ms of device round-trip per
        block on the CPU jit) dominates a ~2-hash search — generatetoaddress
        at functional-test scale was paying minutes of pure dispatch
        overhead. Real networks keep the batched sweep, where throughput,
        not latency, is what matters."""
        from ..ops.dispatch import supervised_resident_sweep, supervised_sweep
        from ..ops.sha256 import backend_is_cpu

        inner = None
        engine = "generic-dispatch"
        on_cpu = backend_is_cpu()
        if (on_cpu and self.params.network == "regtest"
                and not self.resident_force):
            from ..ops.miner import sweep_header_cpu

            engine = "scalar-host"

            def inner(header80, target, start_nonce=0,
                      max_nonces=1 << 32, tile=None):
                return sweep_header_cpu(header80, target,
                                        start_nonce=start_nonce,
                                        max_nonces=max_nonces)
        elif self.resident_mode:
            if self.resident_miner is None:
                from ..mining.resident import ResidentSweep

                kernel = "exact" if on_cpu else "h7"
                # CPU backends take a smaller tile: a hit costs at
                # least one whole tile of the looped compress, 48 ms
                # at 16Ki against 82 ms at 64Ki (XLA:CPU, 8 cores)
                self.resident_miner = ResidentSweep(
                    tile=(1 << 14) if on_cpu else (1 << 16),
                    kernel=kernel)
                self.resident_miner.register_watchdog(
                    self.watchdog_quiet)
            engine = f"resident-{self.resident_miner.kernel}"
        elif not on_cpu:
            from ..ops.sha256_sweep import sweep_header_fast

            engine = "h7-dispatch"
            inner = sweep_header_fast
        self.sweep_engine = engine
        if engine.startswith("resident-"):
            return supervised_resident_sweep(self.resident_miner)
        return supervised_sweep(inner)

    def mining_snapshot(self) -> dict:
        """gettpuinfo's ``mining`` section: the active sweep engine, what
        the generate_to_script calls spent where (``calls``; ``search_s``
        in the nonce search, under no chain lock; ``cs_main_held_s``
        holding cs_main for the templates and the connects) and, when the
        resident loop is live, its full state (template
        generation, tiles swept, candidate FIFO, buffer swaps, poll
        cadence). It runs beside a live search and takes no lock: not
        ``miner``, which the mining call holds to its end."""
        out = {"engine": self.sweep_engine, "resident": False,
               "resident_enabled": self.resident_mode,
               "calls": self._mining_calls,
               "search_s": self._mining_search_s,
               "cs_main_held_s": self._mining_held_s}
        miner = self.resident_miner
        if miner is not None:
            out.update(miner.snapshot())
        return out

    def generate_to_script(self, script_pubkey: bytes, n_blocks: int,
                           max_tries: int = MAX_TRIES_DEFAULT) -> list[bytes]:
        """generatetoaddress backend (src/rpc/mining.cpp generateBlocks).
        As there, cs_main is held for the template (the tip, the mempool
        selection, the target) and again for ProcessNewBlock, and the
        nonce search between them runs under no chain lock: reads, block
        arrival and mempool accepts interleave with it. A tip that moved
        during the search is ProcessNewBlock's to deal with, as for any
        block whose parent is no longer the tip. The ``miner`` lock is
        held for the whole call: one search at a time owns the sweep
        engine. Lock order: miner, then cs_main. A caller that already
        holds cs_main (it is re-entrant) stays correct while no other
        thread mines — the holds below nest inside its own — and merely
        keeps the lock through the search, as every caller did before the
        split; beside a second mining thread that order can deadlock, so
        call with no lock held."""
        hashes: list[bytes] = []
        clock = time.monotonic
        with self.miner_lock:
            self._mining_calls += 1
            asm, sweep = self.assembler(), None
            for _ in range(n_blocks):
                if self.shutdown_event.is_set():
                    break  # close() waits on the miner lock for this
                sweep = sweep or self._select_sweep()
                # the two holds are `with self.cs_main` blocks here, not a
                # helper's: bcplint reads them where they stand
                with self.cs_main, telemetry.span("miner.template"):
                    t0 = clock()
                    tmpl = asm.create_new_block(script_pubkey)
                    t1 = clock()
                # per-block extranonce entropy: with sub-second mining the
                # header time pins to MTP+1, and two nodes extending the
                # same parent toward the same script would otherwise
                # assemble byte-identical blocks — a reorg race that never
                # forks
                block = search_block(tmpl, max_tries=max_tries, sweep=sweep,
                                     extranonce_start=int.from_bytes(
                                         os.urandom(4), "little"))
                self._mining_held_s += t1 - t0
                self._mining_search_s += clock() - t1
                if block is None:
                    break
                with self.cs_main, telemetry.span("miner.connect"):
                    t0 = clock()
                    self.chainstate.process_new_block(block)
                    self._mining_held_s += clock() - t0
                hashes.append(block.get_hash())
        return hashes

    def submit_block(self, block: CBlock) -> Optional[str]:
        """submitblock semantics: None on accept, reject-reason string
        otherwise ('duplicate' when we already have full data)."""
        idx = self.chainstate.block_index.get(block.get_hash())
        if idx is not None and (idx.status & BlockStatus.HAVE_DATA):
            if idx.status & BlockStatus.FAILED_MASK:
                return "duplicate-invalid"
            return "duplicate"
        try:
            self.chainstate.process_new_block(block)
        except BlockValidationError as e:
            return e.reason
        if self.connman is not None:
            self.connman.relay_block(block.get_hash())
        return None

    # -- startup integrity + import ------------------------------------

    def verify_db(self, n_blocks: int = 6, level: int = 3) -> bool:
        """CVerifyDB::VerifyDB (src/validation.cpp:~3700): walk back from the
        tip re-checking recent blocks. Level >=1 re-runs CheckBlock; >=2
        checks undo data presence/decodability; >=3 replays
        disconnect/reconnect on a scratch view checking UTXO consistency."""
        cs = self.chainstate
        tip = cs.tip()
        if tip is None or tip.height == 0 or n_blocks <= 0:
            return True
        from ..validation.coins import BlockUndo, CoinsCache

        # blocks at or below an adopted snapshot tip carry no undo data
        # (history was re-validated by digest in the shadow chainstate,
        # never connected here) — the replay walk must stop above them
        snap = getattr(self, "snapshot_state", None) or {}
        floor = int(snap.get("height", 0))

        checked = 0
        idx = tip
        scratch = CoinsCache(cs.coins)
        to_reconnect = []
        while idx is not None and idx.height > floor and checked < n_blocks:
            raw = cs.block_store.get_block(idx.hash)
            if raw is None:
                raise InitError(f"VerifyDB: missing block data at height {idx.height}")
            block = CBlock.from_bytes(raw)
            if level >= 1:
                cs.check_block(block)
            if level >= 2:
                undo_raw = cs.block_store.get_undo(idx.hash)
                if undo_raw is None:
                    raise InitError(f"VerifyDB: missing undo data at height {idx.height}")
                undo = BlockUndo.from_bytes(undo_raw)
                if level >= 3:
                    cs.disconnect_block(block, idx, undo, view=scratch)
                    to_reconnect.append((block, idx))
            checked += 1
            idx = idx.prev
        if level >= 4:
            for block, bidx in reversed(to_reconnect):
                cs.connect_block(block, bidx, check_scripts=False, view=scratch)
        # scratch view is discarded — this was a read-only replay
        log_print("db", "VerifyDB: %d blocks verified at level %d", checked, level)
        return True

    # -- assumeutxo snapshot onboarding ---------------------------------
    # Reference: Bitcoin Core's assumeutxo (src/node/utxo_snapshot,
    # doc/design/assumeutxo.md): adopt an operator-authorized UTXO
    # snapshot at its tip and serve immediately, while a background
    # chainstate re-validates all of history from genesis into a SHADOW
    # store and promotes the node to fully-validated only when the
    # shadow's recomputed set digest equals the snapshot's.

    def store_info(self) -> dict:
        """The gettpuinfo 'store' section."""
        stats_fn = getattr(self.coins_db, "stats", None)
        if stats_fn is None:
            info: dict = {"backend": "single"}
        else:
            info = stats_fn()
            info["backend"] = "sharded"
        info["snapshot"] = self.snapshot_state
        return info

    def _snapshot_cert_path(self) -> str:
        return os.path.join(self.datadir, "snapshot_cert.json")

    def snapshot_info(self) -> Optional[dict]:
        """The getblockchaininfo 'snapshot' sub-doc — the certificate /
        quarantine view the fleet probe keys on. ``certificate_verified``
        is the serving gate: True when the snapshot carried a verified
        certificate (trust established at load, in seconds) OR when the
        background replay finished (trust established the slow way).
        Absent entirely on nodes that never onboarded from a snapshot."""
        snap = self.snapshot_state
        if not snap:
            return None
        cert = snap.get("cert") or {}
        validated = bool(snap.get("validated"))
        return {
            "height": snap.get("height"),
            "validated": validated,
            "cert_present": bool(cert.get("present")),
            "cert_verified": bool(cert.get("verified")),
            "certificate_verified": bool(cert.get("verified")) or validated,
        }

    def build_snapshot_certificate(self, height: int) -> Optional[dict]:
        """Produce the proof-carrying certificate for a dumptxoutset at
        ``height`` (store/certificate.py), or None when this node cannot
        attest (it onboarded from a snapshot itself and lacks undo data
        below the snapshot tip, or the legacy store has no accumulator).

        The epoch trajectory is reconstructed EXACTLY from undo data by
        walking blocks tip->1 and dividing each block's delta out of the
        live accumulator state — no new runtime bookkeeping, and reorgs
        are a non-issue because the walk happens under cs_main against
        the settled chain."""
        import struct as _struct

        from ..store import certificate as cert_mod
        from ..validation.coins import BlockUndo, Coin

        state_fn = getattr(self.coins_db, "muhash_state", None)
        if state_fn is None:
            return None
        cs = self.chainstate
        header_hashes = [cs.chain[h].hash for h in range(height + 1)]

        def deltas():
            for h in range(height, 0, -1):
                idx = cs.chain[h]
                raw = self.block_store.get_block(idx.hash)
                if raw is None:
                    raise cert_mod.CertificateError(
                        f"no block data at height {h} (snapshot-onboarded "
                        "node without full backfill cannot attest)")
                block = CBlock.from_bytes(raw)
                created = []
                for tx in block.vtx:
                    txid = tx.txid
                    cb = tx is block.vtx[0]
                    for i, out in enumerate(tx.vout):
                        created.append((
                            txid + _struct.pack("<I", i),
                            Coin(out, h, cb).serialize()))
                spent = []
                if len(block.vtx) > 1:
                    rawu = self.block_store.get_undo(idx.hash)
                    if rawu is None:
                        raise cert_mod.CertificateError(
                            f"no undo data at height {h}")
                    undo = BlockUndo.from_bytes(rawu)
                    for t, tx in enumerate(block.vtx[1:]):
                        for vin, coin in zip(tx.vin, undo.vtxundo[t].prevouts):
                            spent.append((
                                vin.prevout.hash
                                + _struct.pack("<I", vin.prevout.n),
                                coin.serialize()))
                yield h, created, spent

        return cert_mod.build_certificate(
            header_hashes, height, self.snapshot_epoch, state_fn(), deltas())

    def load_utxo_snapshot(self, path: str) -> dict:
        """loadtxoutset: adopt the snapshot directory at ``path``.

        Requires -assumeutxo authorization and a fresh node (tip still at
        genesis). On return the node serves RPC at the snapshot tip;
        history validation proceeds in the background."""
        from ..consensus.block import CBlockHeader
        from ..consensus.serialize import ByteReader
        from ..store import snapshot as snapshot_mod
        from ..validation.coins import CoinsCache

        if self.assumeutxo is None:
            raise ValueError(
                "loadtxoutset requires -assumeutxo=<blockhash>:<muhash> "
                "authorization")
        if not isinstance(self.coins_db, ShardedCoinsDB):
            raise ValueError("loadtxoutset requires the sharded chainstate "
                             "layout (-reindex migrates legacy datadirs)")
        exp_hash, exp_digest = self.assumeutxo
        with self.cs_main:
            if self.chainstate.tip().height != 0:
                raise ValueError(
                    "loadtxoutset requires a fresh node (tip at genesis)")
            self.chainstate.flush()  # settle genesis state first
            info = snapshot_mod.load_snapshot(
                path, self.coins_db, self.params.network,
                expected_hash=exp_hash, expected_digest=exp_digest,
                require_certificate=self.snapshot_cert_required)
            cs = self.chainstate
            # headers go through the normal PoW/contextual checks — the
            # snapshot is trusted for the COIN SET only, never for work
            for raw80 in info["headers"]:
                hdr = CBlockHeader.deserialize(ByteReader(raw80))
                if hdr.get_hash() in cs.block_index:
                    continue  # genesis (and any already-known header)
                cs.accept_block_header(hdr)
            tip_idx = cs.block_index.get(info["best_block"])
            if tip_idx is None or tip_idx.height != info["height"]:
                raise snapshot_mod.SnapshotError(
                    "snapshot headers do not reach the snapshot tip")
            cs.chain.set_tip(tip_idx)
            self._fake_snapshot_chaintx()
            # fresh cache over the loaded store — the old one cached
            # genesis-era state that the bulk load just superseded
            cs.coins = CoinsCache(self.coins_db)
            cs.flush()
            self.snapshot_state = self.coins_db.snapshot_state
            self._snapshot_pending = True
            self._cert_checkpoints = info.get("cert_checkpoints")
            if self._cert_checkpoints:
                # persist for restart-resume: the shadow validator must
                # keep its epoch-divergence tripwires across restarts
                from ..store.kvstore import atomic_write_json

                atomic_write_json(self._snapshot_cert_path(), {
                    "checkpoints": {str(h): d for h, d in
                                    self._cert_checkpoints.items()},
                    "epoch_blocks": info["certificate"]["epoch_blocks"],
                })
            log_printf("assumeutxo: serving at snapshot tip %s (height %d)"
                       " — background validation starting%s",
                       hash_to_hex(tip_idx.hash)[:16], tip_idx.height,
                       "" if info.get("certificate") else
                       "; UNCERTIFIED snapshot — replica serving "
                       "quarantined until validation completes")
        with self.notify_cv:
            self.notify_cv.notify_all()
        self._start_snapshot_verify()
        return {"height": info["height"],
                "hash": info["manifest"]["best_block"],
                "coins": info["manifest"]["coins"],
                "muhash": info["manifest"]["muhash"]}

    def _fake_snapshot_chaintx(self) -> None:
        """Core's fake-nChainTx trick: blocks along the snapshot chain
        have headers but (until backfill) no data, so their true tx counts
        are unknown — stamp placeholder n_tx/chain_tx so candidate
        selection and descendant linkage work above the snapshot tip.
        Real counts overwrite the fakes as block data arrives."""
        cs = self.chainstate
        tip = cs.chain.tip()
        if tip is None:
            return
        running = 0
        for h in range(tip.height + 1):
            idx = cs.chain[h]
            if idx.n_tx == 0:
                idx.n_tx = 1
            running += idx.n_tx
            idx.chain_tx = running
            cs._dirty_index.add(idx)
        # relink descendants parked behind chain_tx==0 ancestors
        for h in range(tip.height + 1):
            idx = cs.chain[h]
            for child in cs._unlinked.pop(idx, []):
                cs._link_chain_tx(child)

    def _start_snapshot_verify(self) -> None:
        if self._snapshot_thread is not None and \
                self._snapshot_thread.is_alive():
            return
        self._snapshot_thread = threading.Thread(
            target=self._snapshot_verify_loop,
            name="assumeutxo-verify", daemon=True)
        self._snapshot_thread.start()

    def _snapshot_verify_loop(self) -> None:
        """Background history validation (the assumeutxo promise).

        A SHADOW chainstate — its own sharded coins store + block index
        under datadir/chainstate_shadow, block/undo writes discarded —
        replays every block genesis..snapshot-tip through the full
        consensus path. Blocks not yet local are pulled from peers via
        connman.request_backfill. On reaching the snapshot height the
        shadow's recomputed MuHash digest must equal the snapshot digest;
        only then is the chain marked fully validated. The shadow persists
        its own progress, so a restart resumes instead of starting over."""
        import shutil

        state = dict(self.snapshot_state or {})
        target_h = int(state["height"])
        shadow_dir = os.path.join(self.datadir, "chainstate_shadow")
        os.makedirs(shadow_dir, exist_ok=True)
        shadow_coins = ShardedCoinsDB(
            shadow_dir, n_shards=getattr(self.coins_db, "n_shards", 1))
        shadow_index_kv = KVStore(os.path.join(shadow_dir, "index.sqlite"))
        verifier = BlockScriptVerifier(self.params,
                                       backend=self.connect_backend,
                                       sigcache=SignatureCache(),
                                       kernel=self.ecdsa_kernel)
        shadow = ChainstateManager(
            self.params, shadow_coins, _ShadowBlockStore(self),
            script_verifier=verifier,
            index_db=BlockIndexDB(shadow_index_kv))
        # the shadow's ctor re-registered the pipeline watchdog against
        # ITSELF (registration replaces by name) — restore the live
        # manager's probe immediately
        from ..util import devicewatch as _dw

        _dw.WATCHDOG.register(
            "pipeline",
            pending_fn=lambda: len(self.chainstate._spec),
            quiet_s=self.watchdog_quiet)
        # certificate epoch tripwires: {checkpoint height: expected digest}
        # verified as the replay crosses each boundary — a forged epoch is
        # caught O(E) blocks past the forgery, not at height H
        import bisect as _bisect

        cps = self._cert_checkpoints or {}
        cp_heights = sorted(cps)
        sampled: Optional[set] = None
        if cps and self.snapshot_spotcheck > 0:
            from ..store import certificate as _cert_mod

            sampled = set(_cert_mod.sample_epochs(
                cp_heights, self.snapshot_spotcheck, self._spotcheck_seed))
            log_printf("assumeutxo: spot-check mode — full script "
                       "re-validation on %d/%d certified epochs %s; digest "
                       "tripwires stay armed at every boundary",
                       len(sampled), len(cp_heights), sorted(sampled))

        def _epoch_end(height: int) -> Optional[int]:
            i = _bisect.bisect_left(cp_heights, height)
            return cp_heights[i] if i < len(cp_heights) else None

        ok = False
        try:
            shadow.load_block_index()
            h = shadow.tip().height + 1
            if h > 1:
                log_printf("assumeutxo: shadow validation resuming at "
                           "height %d/%d", h, target_h)
            since_flush = 0
            while h <= target_h and not self.shutdown_event.is_set():
                with self.cs_main:
                    idx = self.chainstate.chain[h]
                    raw = (self.block_store.get_block(idx.hash)
                           if idx is not None else None)
                if raw is None:
                    # history not local yet — name the missing heights to
                    # the P2P layer (header sync can't: peers announce
                    # nothing below our locator's snapshot tip)
                    missing = []
                    with self.cs_main:
                        for hh in range(h, min(h + 64, target_h + 1)):
                            i2 = self.chainstate.chain[hh]
                            if i2 is not None and \
                                    not (i2.status & BlockStatus.HAVE_DATA):
                                missing.append(i2.hash)
                    if missing and self.connman is not None:
                        self.connman.request_backfill(missing)
                    self.shutdown_event.wait(0.25)
                    continue
                if sampled is not None:
                    # spot-check: blocks outside the K sampled epochs
                    # replay without script verification (UTXO algebra,
                    # PoW and digest tripwires still fully enforced) —
                    # the onboarding-economics lever the certificate buys
                    shadow.script_verifier = (
                        verifier if _epoch_end(h) in sampled else None)
                if not shadow.process_new_block(CBlock.from_bytes(raw)):
                    log_printf("assumeutxo: shadow validation REJECTED "
                               "block at height %d — snapshot chain is "
                               "invalid, promotion abandoned", h)
                    if self.connman is not None:
                        self.connman.cancel_backfill()
                    return
                if h in cps:
                    shadow.flush()
                    since_flush = 0
                    got = shadow_coins.muhash_digest().hex()
                    if got != cps[h]:
                        log_printf(
                            "assumeutxo: EPOCH DIGEST DIVERGENCE at "
                            "certified checkpoint %d (got %s, certificate "
                            "%s) — snapshot content is FORGED in epoch "
                            "ending here; hard abort for manual "
                            "intervention", h, got[:16], cps[h][:16])
                        if self.connman is not None:
                            self.connman.cancel_backfill()
                        self.shutdown_event.set()
                        return
                h += 1
                since_flush += 1
                if since_flush >= 64:
                    shadow.flush()
                    since_flush = 0
            if h <= target_h:
                return  # shutdown mid-validation: shadow resumes later
            shadow.flush()
            got = shadow_coins.muhash_digest().hex()
            want = state["digest"]
            if got != want or shadow.tip().hash != \
                    bytes.fromhex(state["hash"])[::-1]:
                log_printf("assumeutxo: DIGEST MISMATCH after full replay "
                           "(got %s, snapshot %s) — the snapshot was bad; "
                           "shutting down for manual intervention",
                           got[:16], want[:16])
                if self.connman is not None:
                    self.connman.cancel_backfill()
                self.shutdown_event.set()
                return
            with self.cs_main:
                cs = self.chainstate
                for hh in range(1, target_h + 1):
                    bidx = cs.chain[hh]
                    bidx.raise_validity(BlockStatus.VALID_SCRIPTS)
                    cs._dirty_index.add(bidx)
                state["validated"] = True
                self.coins_db.set_snapshot_state(state)
                self.snapshot_state = state
                self._snapshot_pending = False
                cs.flush()
            ok = True
            log_printf("assumeutxo: background validation complete at "
                       "height %d — digest matches, chain fully validated",
                       target_h)
        except Exception as e:  # noqa: BLE001 — thread must not die silent
            log_printf("assumeutxo: shadow validation error: %r", e)
        finally:
            shadow_coins.close()
            shadow_index_kv.close()
            if ok:
                shutil.rmtree(shadow_dir, ignore_errors=True)
                if os.path.exists(self._snapshot_cert_path()):
                    os.remove(self._snapshot_cert_path())

    def import_block_files(self, paths: Optional[list[str]] = None) -> int:
        """LoadExternalBlockFile (src/validation.cpp:~4000) over every
        blk?????.dat (or the explicit ``paths`` — the -loadblock /
        bootstrap.dat form): scan (netmagic, size, block) records,
        re-register data positions, and ProcessNewBlock each one.
        Out-of-order blocks park via accept-header failure and are retried
        once their parent lands.

        Two engines run this path. The NATIVE fast import (the reference's
        all-C++ pipeline shape: parse, sanity, merkle, UTXO apply, undo and
        the P2PKH sig scan in native/connect.cpp; TPU batch for the ECDSA
        math) handles the dominant linear case; the Python loop below is
        the reference implementation and handles everything the fast path
        declines (reorgs, invalid blocks, -loadblock, hook listeners) —
        every fast-path block still ends in a byte-identical chainstate
        (differential: tests/unit/test_native_connect.py)."""
        from .. import native as _nat

        if (paths is None
                and _nat.engine_available()
                and not os.environ.get("BCP_NO_NATIVE_IMPORT")
                and not self.chainstate.on_block_connected
                and not self.chainstate.on_block_disconnected):
            try:
                return self._import_block_files_native()
            except _NativeImportAbort as e:
                # rare: an in-flight signature batch failed after its block
                # was staged — rebuild the in-memory state from the last
                # flush and let the Python engine produce the verdict
                log_printf("native import aborted (%s); replaying through "
                           "the Python engine", e)
                self._rebuild_chainstate_from_disk()
        return self._import_block_files_python(paths)

    def _rebuild_chainstate_from_disk(self) -> None:
        """Reset the in-memory chain objects to the last flushed on-disk
        state (the native fast-import recovery path). Only callable before
        servers start — import runs during init."""
        verifier = BlockScriptVerifier(self.params,
                                       backend=self.connect_backend,
                                       sigcache=self.sigcache,
                                       kernel=self.ecdsa_kernel)
        self.block_store.positions.clear()
        self.block_store.undo_positions.clear()
        self.chainstate = ChainstateManager(
            self.params, self.coins_db, self.block_store,
            script_verifier=verifier, index_db=self.index_db,
        )
        self.chainstate.pipeline_depth = self.pipeline_depth
        self.chainstate.max_branches = self.spec_branches
        self.chainstate.spec_hold_s = self.spec_hold_s
        self.chainstate.sig_service = self.sigservice
        # the fresh manager re-registered the pipeline watchdog with the
        # env default quiet — restore this node's -watchdogquiet wiring
        from ..util import devicewatch as _dw

        _dw.WATCHDOG.register(
            "pipeline",
            pending_fn=lambda: len(self.chainstate._spec),
            quiet_s=getattr(self, "watchdog_quiet", None))
        self.chainstate.load_block_index()

    def _import_block_files_native(self) -> int:
        """The fast -reindex import: native connect engine + packed TPU
        signature batches, linear-extension blocks only (anything else
        flushes and defers to the Python engine per block).

        Every second of it is inside a span (util/telemetry) on the
        importing thread, nested under one ``import`` span, so the self
        times of ``last_import_stats["phases"]`` add up to ``wall_s`` and
        what no child covers reads as ``import``'s own. The stats' older
        times are sums of those spans (_IMPORT_LEGS). Under -telemetry=off
        spans are null: ``wall_s`` (a clock of its own) and the counters
        are all the import reports, ``phases`` is empty and the legs read
        0 for "not taken" (gettpuinfo.telemetry.span_times says so)."""
        t0 = time.monotonic()
        with telemetry.span("import", collect=True) as root:
            n_imported, stats = self._native_import()
        stats["wall_s"] = time.monotonic() - t0
        phases = root.totals or {}
        for key, names in _IMPORT_LEGS.items():
            stats[key] = sum(phases[n]["s"] for n in names if n in phases)
        stats["phases"] = phases
        bench = self.chainstate.bench
        bench["connect_ms"] += stats["native_connect_s"] * 1e3
        bench["verify_ms"] += stats["verify_s"] * 1e3
        bench["flush_ms"] += stats["flush_s"] * 1e3
        self.last_import_stats = stats
        # the operator's reading (README, Observability): the legs, the
        # dispatch queue, then every span's self seconds, largest first
        legs = ("connect %.1fs verify %.1fs flush %.1fs" % (
            stats["native_connect_s"], stats["verify_s"], stats["flush_s"])
            if phases else "-telemetry=off: no span times")
        log_printf(
            "native import: %d blocks (%d slow-path), %.1f MB in %.1fs "
            "(%s); %d flushes (%d rows: %d put, %d deleted), store reads "
            "%d of %d keys, %d dispatches (%d of them tails, %d lanes), "
            "unfinished at enqueue %s, queue seen empty %.2fs; self "
            "seconds: %s",
            n_imported, stats["slow_path_blocks"], stats["bytes"] / 1e6,
            stats["wall_s"], legs, stats["flushes"], stats["flush_rows"],
            stats["flush_puts"], stats["flush_deletes"],
            stats["store_read_rows"], stats["store_read_keys"],
            stats["dispatches"],
            stats["tail_dispatches"], stats["tail_lanes"],
            stats["inflight_at_enqueue"], stats["queue_empty_s"],
            " ".join(f"{name} {row['self_s']:.3f}" for name, row in sorted(
                phases.items(), key=lambda kv: -kv[1]["self_s"])))
        return n_imported

    def _native_import(self) -> tuple:
        """The body of _import_block_files_native, inside its ``import``
        span: returns (blocks imported, the stats its spans do not
        fill)."""
        import struct

        import numpy as np

        from .. import native
        from ..consensus.block import CBlockHeader
        from ..consensus.params import get_block_subsidy
        from ..consensus.serialize import ByteReader
        from ..consensus.tx import CTransaction
        from ..ops import ecdsa_batch
        from ..script.interpreter import (
            SCRIPT_VERIFY_NULLFAIL,
            DeferringSignatureChecker,
            MultisigGroup,
            ScriptError,
            TransactionSignatureChecker,
            VerifyScript,
        )
        from ..script.script import script_int
        from ..script.sighash import SighashCache
        from ..validation.chain import BlockStatus, CBlockIndex
        from ..validation.scriptcheck import (
            _InlineCountingChecker,
            block_script_flags,
        )

        span = telemetry.span
        cs = self.chainstate
        params = self.params
        consensus = params.consensus
        magic = params.netmagic
        # import runs before __init__ assigns the post-import knobs
        flush_interval = self.config.get_int("flushinterval",
                                             DEFAULT_FLUSH_INTERVAL)
        dbcache_bytes = max(
            1, self.config.get_int("dbcache", 300)) * 1024 * 1024
        # the sharded store keeps the rows it served until its next write
        # (store/sharded.py); the legacy single file keeps nothing
        served_bytes = getattr(self.coins_db, "served_bytes", lambda: 0)
        cs.flush()  # the engine's base view must be current before takeover

        eng = native.ConnectEngine()
        eng.set_best(cs.coins.best_block())
        MAX_INFLIGHT = 3
        # fallback_s (the generic-script leg) is inside verify_s too.
        # fallback_inputs: every input the P2PKH scan did not take =
        # template_inputs (a native script template wrote their lanes, its
        # time inside native_connect_s / sigscan_s) + interp_inputs (the
        # ones that went on to VerifyScript).
        # prefork_blocks: blocks connected here under flags without
        # NULLFAIL (history below the fork height); the scan's threads'
        # seconds (sigscan_thread_s; sigscan_s is its wall) and, of them,
        # those inside the legacy SignatureHash, with its digests and the
        # bytes of serialised transaction they hashed. schnorr_inputs: the
        # inputs whose 65-byte signature the scan took as a BCH Schnorr
        # lane (from the fork height on), schnorr_challenge_s the scan's
        # threads' seconds in their challenge hash and n - e (inside
        # sigscan_thread_s, as legacy_sighash_s is).
        # The times that are not the scan's own are sums of spans, filled
        # in when the import ends (_IMPORT_LEGS). At the dispatch queue, by
        # what the runtime says of each handle (BatchHandle.done, which
        # blocks on nothing; the list `inflight` itself is no guide: a
        # handle stays on it until a fourth is enqueued, long after the chip
        # has finished it): inflight_at_enqueue[k] counts the enqueues that
        # found k dispatches unfinished (the last slot: 3 or more; near 3
        # the chip paces the import, near 0 the host does). queue_empty_s,
        # for the log alone, is the wall from the moment the import saw
        # every dispatch finished (it looks after each native connect, block
        # and settle; not at all under -telemetry=off) until it has handed
        # the next one over: a lower bound of the chip's idle time by the
        # host's doing, since the import cannot look inside the native call
        # (the device trace has the time itself: tools/trace_view.py
        # --xplane). tail_dispatches and tail_lanes are the <= 2,046-lane
        # chunks of a drain, flushes the drains made. flush_puts and
        # flush_deletes are the rows those flushes handed the store
        # (flush_rows their sum; a tombstone counts, a FRESH coin spent
        # before a flush reaches neither), flush_log one entry a flush
        # (height, the store's epoch, rows, the commit's seconds);
        # store_read_keys the spent coins service_misses asked the store
        # for because a flush had cleared them, store_read_rows those found.
        stats = {"blocks": 0, "bytes": 0, "native_connect_s": 0.0,
                 "sigscan_s": 0.0, "verify_s": 0.0, "fallback_s": 0.0,
                 "flush_s": 0.0, "slow_path_blocks": 0,
                 "fallback_inputs": 0, "template_inputs": 0,
                 "interp_inputs": 0, "fast_inputs": 0,
                 "prefork_blocks": 0, "sigscan_thread_s": 0.0,
                 "legacy_digests": 0, "legacy_sighash_bytes": 0,
                 "legacy_sighash_s": 0.0,
                 "schnorr_inputs": 0, "schnorr_challenge_s": 0.0,
                 "dispatches": 0, "tail_dispatches": 0, "tail_lanes": 0,
                 "flushes": 0, "queue_empty_s": 0.0,
                 "inflight_at_enqueue": [0] * (MAX_INFLIGHT + 1),
                 "flush_rows": 0, "flush_puts": 0, "flush_deletes": 0,
                 "store_read_keys": 0, "store_read_rows": 0,
                 "flush_log": []}
        # the height the last flush left on disk: the cadence counts the
        # blocks connected since, across block files
        flushed_height = [cs.chain.tip().height]
        queue_empty_since: list = [None]
        watch_queue = telemetry.mode() != "off"

        def unfinished() -> int:
            return sum(1 for entry in inflight if not entry[1].done())

        def note_queue_empty() -> None:
            if (watch_queue and queue_empty_since[0] is None
                    and stats["dispatches"] and not unfinished()):
                queue_empty_since[0] = time.monotonic()

        scan_keys = ("sigscan_s", "sigscan_thread_s", "legacy_digests",
                     "legacy_sighash_bytes", "legacy_sighash_s",
                     "schnorr_inputs", "schnorr_challenge_s")
        # counters of gettpuinfo.batch reported as the import's own deltas
        delta_keys = ("multisig_groups", "multisig_lanes",
                      "multisig_group_confirms", "inline_legacy_sigs")
        delta0 = [getattr(ecdsa_batch.STATS, k) for k in delta_keys]
        n_imported = 0
        pending: dict[bytes, list[tuple[bytes, Optional[tuple]]]] = {}
        # in-flight signature batches: (block hash, BatchHandle, number of
        # its first lane, its candidate mask, its lane kind)
        inflight: list[tuple] = []
        # cross-block record aggregation: mainnet blocks carry ~2-5k sig
        # inputs, and per-dispatch latency amortizes over wider buckets
        # (the rate gain is not measured on the current machine) —
        # aggregate fast records across blocks and dispatch at AGG_LANES.
        # Failure
        # granularity stays sound: a bad batch aborts to the Python
        # replay, which re-derives the exact offending block.
        # 8190 = 8192-bucket minus the 2 supervised-dispatch KAT lanes
        # (ops/ecdsa_batch appends them per batch; an exact-8192 slice
        # would spill into the 10240 bucket and pay a fresh compile).
        AGG_LANES = 8190
        # A bucket is of one lane kind (ops/ecdsa_batch.dispatch_packed):
        # ECDSA lanes and the BCH Schnorr lanes of 65-byte signatures
        # aggregate apart, so a block with both fills two open buckets and
        # a flush drains both. Per kind: (pub, rs, msg, rn, wrap, cand) per
        # block; cand marks the candidate lanes of deferred
        # OP_CHECKMULTISIG groups (ECDSA only: OP_CHECKMULTISIG takes no
        # Schnorr signature), and a Schnorr lane's rn is (n - e) mod n
        ECDSA, SCHNORR = 0, 1
        agg: dict[int, list] = {ECDSA: [], SCHNORR: []}
        agg_count = {ECDSA: 0, SCHNORR: 0}
        agg_last_hash = [b""]
        # lane numbers run over the whole import, each kind its own
        lanes_dispatched = {ECDSA: 0, SCHNORR: 0}

        def confirm_on_host(owner) -> None:
            """A multisig group's walk failed on the batch's verdicts: the
            input runs again with the eager checker, and only its
            ScriptError aborts the import (the Python replay then names the
            block). ``owner`` is (block bytes, its connect result, input
            number, flags, block hash): the transaction is parsed only
            here."""
            raw, res, g, flags, h = owner
            t_i, in_i = (int(v) for v in res.sig_txin[g])
            tx = CTransaction.from_bytes(
                raw[int(res.tx_offsets[t_i, 0]):int(res.tx_offsets[t_i, 1])])
            spk = res.spent_spk_blob[int(res.spent_spk_offsets[g]):
                                     int(res.spent_spk_offsets[g + 1])]
            try:
                VerifyScript(tx.vin[in_i].script_sig, spk, flags,
                             TransactionSignatureChecker(
                                 tx, in_i, int(res.spent_values[g])))
            except ScriptError as e:
                raise _NativeImportAbort(
                    f"multisig input failed ({e.code}) in block "
                    f"{hash_to_hex(h)[:16]}") from e

        settler = _MultisigSettler(confirm_on_host)

        def dispatch(kind: int, arrays, sl: slice) -> None:
            stats["dispatches"] += 1
            if watch_queue:
                stats["inflight_at_enqueue"][min(unfinished(),
                                                 MAX_INFLIGHT)] += 1
            with span("import.enqueue", lanes=sl.stop - sl.start):
                cand = arrays[5][sl]
                handle = ecdsa_batch.dispatch_packed(
                    *(a[sl] for a in arrays[:5]),
                    backend=self.connect_backend,
                    candidate=cand if cand.any() else None,
                    schnorr=kind == SCHNORR)
            if queue_empty_since[0] is not None:
                # the chip had nothing until this program was handed over
                stats["queue_empty_s"] += (time.monotonic()
                                           - queue_empty_since[0])
                queue_empty_since[0] = None
            inflight.append((agg_last_hash[0], handle,
                             lanes_dispatched[kind] + sl.start, cand, kind))

        def flush_agg(everything: bool = True):
            for kind in (ECDSA, SCHNORR):
                if agg[kind] and (everything
                                  or agg_count[kind] >= AGG_LANES):
                    flush_kind(kind, everything)
            while len(inflight) > MAX_INFLIGHT:
                settle_oldest()

        def flush_kind(kind: int, everything: bool) -> None:
            blocks = agg[kind]
            with span("import.pack", blocks=len(blocks)):
                arrays = [np.concatenate([a[i] for a in blocks])
                          for i in range(6)]
            blocks.clear()
            pos = 0
            total = len(arrays[2])
            # dispatch EXACT AGG_LANES slices: the jit bakes the bucket
            # into the program, so steady-state flushes must reuse ONE
            # compiled shape (a stray 10240-lane flush pays a fresh
            # minutes-long compile); only the final sub-AGG_LANES tail
            # may hit a second bucket
            while total - pos >= AGG_LANES:
                dispatch(kind, arrays, slice(pos, pos + AGG_LANES))
                pos += AGG_LANES
            if everything:
                # drain the tail in <=2046-lane chunks (2048-bucket minus
                # the KAT lanes): together with the AGG_LANES slices this
                # bounds the compiled-shape set to {8192, 2048, 1024} for
                # the whole import
                while pos < total:
                    end = min(pos + 2046, total)
                    stats["tail_dispatches"] += 1
                    stats["tail_lanes"] += end - pos
                    dispatch(kind, arrays, slice(pos, end))
                    pos = end
            if pos < total:
                blocks.append(tuple(a[pos:] for a in arrays))
            agg_count[kind] = total - pos
            lanes_dispatched[kind] += pos

        def settle_oldest():
            h, handle, first, cand, kind = inflight.pop(0)
            # the thread blocked on the chip, and nothing else
            with span("import.settle_wait", inflight=len(inflight) + 1):
                ok = handle.result()
            note_queue_empty()
            with span("import.settle"):
                # a must-verify lane has to verify; a candidate lane's
                # verdict feeds its group's walk
                if not bool(np.all(ok | cand)):
                    raise _NativeImportAbort(
                        f"sig batch failed in block {hash_to_hex(h)[:16]}"
                    )
                if kind == ECDSA:  # multisig groups ride ECDSA lanes only
                    settler.settled(first, ok)

        def settle_all():
            flush_agg()
            while inflight:
                settle_oldest()
            # a deferred OP_CHECKMULTISIG succeeded speculatively: nothing
            # is written while one waits for verdicts that no dispatch in
            # flight will bring (a slip in the lane numbering, not a fault
            # of the chain: the Python engine decides)
            if settler.pending:
                raise _NativeImportAbort(
                    f"{len(settler.pending)} multisig group(s) left "
                    f"unsettled after the last dispatch, the first at lane "
                    f"{settler.pending[0][0]} of {lanes_dispatched[ECDSA]}")

        def fast_flush():
            with span("import.drain"):
                settle_all()
            stats["flushes"] += 1
            with span("import.flush", flush=stats["flushes"]):
                self.block_store.flush()
                cs.flush_index()
                best = eng.best()
                entries = eng.flush_entries()
                t_commit = time.monotonic()
                self.coins_db.batch_write_serialized(entries, best)
                commit_s = time.monotonic() - t_commit
                eng.clear()
                # keep the Python cache's best-block in step: a later
                # cs.flush() must not rewind the marker to its stale cached
                # value (it survives CoinsCache.flush)
                cs.coins.set_best_block(best)
            deletes = sum(1 for _, ser in entries if ser is None)
            stats["flush_deletes"] += deletes
            stats["flush_puts"] += len(entries) - deletes
            stats["flush_rows"] += len(entries)
            flushed_height[0] = cs.chain.tip().height
            stats["flush_log"].append({
                "height": flushed_height[0],
                "epoch": getattr(self.coins_db, "epoch", None),
                "rows": len(entries), "seconds": round(commit_s, 6)})

        def service_misses(missing_keys) -> int:
            from ..consensus.serialize import (
                deser_compact_size,
                deser_var_bytes,
            )

            with span("import.store_read", keys=len(missing_keys)):
                rows = self.coins_db.get_serialized_many(missing_keys)
                stats["store_read_keys"] += len(missing_keys)
                stats["store_read_rows"] += len(rows)
                for key, ser in rows.items():
                    r = ByteReader(ser)
                    code = deser_compact_size(r, range_check=False)
                    value = deser_compact_size(r, range_check=False)
                    spk = deser_var_bytes(r)
                    eng.insert(key, code, value, spk)
            return len(rows)

        def slow_path(raw: bytes, pos_info: Optional[tuple]) -> bool:
            """Flush engine state, process via the Python engine, resync."""
            stats["slow_path_blocks"] += 1
            with span("import.slow_path"):
                fast_flush()
                block = CBlock.from_bytes(raw)
                connected = try_process(block, pos_info)
                cs.flush()
                eng.set_best(cs.coins.best_block())
                flushed_height[0] = cs.chain.tip().height
            return connected

        def try_process(block: CBlock, pos_info: Optional[tuple]) -> bool:
            """The Python-engine leg (same parking semantics as the
            reference loop below)."""
            nonlocal n_imported
            h = block.get_hash()
            if pos_info is not None:
                self.block_store.positions.setdefault(h, pos_info)
            try:
                self.chainstate.process_new_block(block)
            except BlockValidationError as e:
                if e.reason == "prev-blk-not-found":
                    pending.setdefault(block.header.hash_prev_block,
                                       []).append((block.serialize(),
                                                   pos_info))
                elif e.reason != "duplicate":
                    log_printf("reindex: rejected %s: %s",
                               hash_to_hex(h)[:16], e.reason)
                return False
            n_imported += 1
            return True

        def interpret(raw: bytes, res, interp_idx, flags: int, h: bytes,
                      records: list, groups: list) -> None:
            """The inputs the templates declined, through VerifyScript.
            Under NULLFAIL their checks defer into ``records`` and
            ``groups``. Without it (history below the fork height) a failed
            check may push false into a script that goes on, so they are
            verified here and now, on this thread (inline_legacy_sigs counts
            the checks). Raises ScriptError."""
            defer = bool(flags & SCRIPT_VERIFY_NULLFAIL)
            tx_cache: dict[int, tuple] = {}
            spk_off = res.spent_spk_offsets
            for g in interp_idx:
                t_i, in_i = int(res.sig_txin[g, 0]), int(res.sig_txin[g, 1])
                if t_i not in tx_cache:
                    s, e_ = (int(res.tx_offsets[t_i, 0]),
                             int(res.tx_offsets[t_i, 1]))
                    tx = CTransaction.from_bytes(raw[s:e_])
                    tx_cache[t_i] = (tx, SighashCache(tx))
                tx, cache = tx_cache[t_i]
                spk = res.spent_spk_blob[int(spk_off[g]):int(spk_off[g + 1])]
                amount = int(res.spent_values[g])
                seen = len(groups)
                if defer:
                    checker = DeferringSignatureChecker(
                        tx, in_i, amount, records, cache, groups)
                else:
                    checker = _InlineCountingChecker(tx, in_i, amount, cache)
                VerifyScript(tx.vin[in_i].script_sig, spk, flags, checker)
                for grp in groups[seen:]:
                    grp.owner = (raw, res, int(g), flags, h)

        def script_leg(raw: bytes, res, interp_idx, flags: int, h: bytes):
            """The generic-script leg of one block: the lanes the native
            scan's templates wrote for the inputs they fit, then every
            input they declined (``interp_idx``) through the Python
            interpreter. Returns (the ECDSA lanes' six arrays, multisig
            groups, the Schnorr lanes' six arrays or None), or None where the block
            has to take the Python path: a script failed. A Schnorr record
            (a 65-byte signature in a script no template fits, deferred by
            the interpreter) joins the Schnorr lanes the templates wrote."""
            records: list = []
            groups: list = []
            inline = len(interp_idx) and not flags & SCRIPT_VERIFY_NULLFAIL
            try:
                with (telemetry.span("import.inline_legacy",
                                     inputs=len(interp_idx))
                      if inline else nullcontext()):
                    interpret(raw, res, interp_idx, flags, h, records, groups)
            except ScriptError:
                return None
            *lanes, cand, kind = res.leg_lanes
            cand = cand.view(bool)
            table = res.leg_table
            schnorr = None
            if kind.any():
                # each kind's lanes apart, and a group's first lane counted
                # among the ECDSA lanes alone
                is_ecdsa = kind == ECDSA
                schnorr = [a[~is_ecdsa] for a in (*lanes, cand)]
                lanes = [a[is_ecdsa] for a in lanes]
                cand = cand[is_ecdsa]
                table = table.copy()
                table[:, 1] = (np.cumsum(is_ecdsa) - 1)[table[:, 1]]
            # the templates' groups, counted as defer_multisig counts its own
            native_groups = [
                MultisigGroup(first, m, n, (raw, res, g, flags, h))
                for g, first, m, n in table[table[:, 2] > 0].tolist()]
            ecdsa_batch.STATS.multisig_groups += len(native_groups)
            ecdsa_batch.STATS.multisig_lanes += int(cand.sum())
            deferred = [r for r in records if r.algo == "schnorr"]
            if deferred:
                # the groups' starts count ECDSA records alone (a group
                # holds no Schnorr record: defer_multisig)
                before = np.cumsum([r.algo == "schnorr" for r in records])
                for grp in groups:
                    grp.start -= int(before[grp.start])
                records = [r for r in records if r.algo != "schnorr"]
                blobs = (*ecdsa_batch.schnorr_records_to_blobs(deferred),
                         np.zeros(len(deferred), bool))
                schnorr = blobs if schnorr is None else [
                    np.concatenate(pair) for pair in zip(schnorr, blobs)]
            if records:
                ecand = np.zeros(len(records), bool)
                for grp in groups:
                    ecand[grp.start:grp.start + grp.lanes] = True
                    grp.start += len(cand)  # behind the templates' lanes
                lanes = [np.concatenate(pair) for pair in zip(
                    lanes, ecdsa_batch.records_to_blobs(records))]
                cand = np.concatenate([cand, ecand])
            return (*lanes, cand), native_groups + groups, schnorr

        def join_lanes(raw: bytes, res, h: bytes, height: int, flags: int,
                       prefork: bool) -> bool:
            """One block's lanes into the aggregations, each kind into its
            own: those the P2PKH scan wrote, then the script leg's. False
            where the leg sends the block to the Python path."""
            status = res.sig_status
            taken = status == 0
            n_fast = int(taken.sum())
            stats["fast_inputs"] += n_fast
            ecdsa_batch.STATS.p2pkh_fast_path += n_fast
            if res.schnorr_inputs:
                fast_idx = np.nonzero(taken & (res.sig_kind == ECDSA))[0]
                s_idx = np.nonzero(taken & (res.sig_kind == SCHNORR))[0]
                schnorr = [res.sig_pub[s_idx], res.sig_rs[s_idx],
                           res.sig_msg[s_idx], res.sig_rn[s_idx],
                           res.sig_wrap[s_idx], np.zeros(len(s_idx), bool)]
            else:
                fast_idx = np.nonzero(taken)[0]
                schnorr = None
            pub = res.sig_pub[fast_idx]
            rs = res.sig_rs[fast_idx]
            msg = res.sig_msg[fast_idx]
            rn = res.sig_rn[fast_idx]
            wrap = res.sig_wrap[fast_idx]
            cand = np.zeros(len(msg), bool)
            n_leg = res.n_inputs - n_fast
            if n_leg:
                # generic-script inputs: the lanes of those a native
                # template fits, the Python interpreter the authority
                # for the rest; both join the same batch
                interp_idx = np.nonzero(status == 1)[0]
                stats["fallback_inputs"] += n_leg
                stats["template_inputs"] += len(res.leg_table)
                stats["interp_inputs"] += int(interp_idx.size)
                with span("import.script_leg", height=height, inputs=n_leg):
                    leg = script_leg(raw, res, interp_idx, flags, h)
                if leg is None:
                    return False
                leg_lanes, groups, leg_schnorr = leg
                settler.add(lanes_dispatched[ECDSA] + agg_count[ECDSA]
                            + len(msg), groups)
                pub, rs, msg, rn, wrap, cand = (
                    np.concatenate(pair) for pair in zip(
                        (pub, rs, msg, rn, wrap, cand), leg_lanes))
                if leg_schnorr is not None:
                    schnorr = leg_schnorr if schnorr is None else [
                        np.concatenate(pair)
                        for pair in zip(schnorr, leg_schnorr)]
            if len(msg):
                agg[ECDSA].append((pub, rs, msg, rn, wrap, cand))
                agg_count[ECDSA] += len(msg)
                agg_last_hash[0] = h
                if prefork:
                    ecdsa_batch.STATS.prefork_lanes += len(msg)
            if schnorr is not None and len(schnorr[2]):
                agg[SCHNORR].append(tuple(schnorr))
                agg_count[SCHNORR] += len(schnorr[2])
                agg_last_hash[0] = h
            return True

        def fast_connect(raw: bytes, h: bytes, prev, pos_info) -> bool:
            """One linear-extension block through the native engine.
            Returns False when the block must go through the Python path."""
            nonlocal n_imported
            with span("import.header"):
                header = CBlockHeader.deserialize(ByteReader(raw[:80]))
                try:
                    cs.check_block_header(header)
                    cs.contextual_check_block_header(header, prev)
                except BlockValidationError:
                    return False  # Python path gives the verdict
                height = prev.height + 1
                idx = CBlockIndex(header, h, prev)
                check_scripts = (cs.script_checks_needed(idx)
                                 and cs.script_verifier is not None)
                flags = block_script_flags(height, header.time, params)
                prefork = check_scripts and not flags & SCRIPT_VERIFY_NULLFAIL
                bip34 = (script_int(height)
                         if height >= consensus.bip34_height else None)
                mtp = prev.get_median_time_past()
                subsidy = get_block_subsidy(height, consensus)
            # native/connect.cpp's own stopwatches (scan_keys) stay what
            # they are, inside this span
            with span("import.connect", height=height):
                try:
                    try:
                        res = eng.connect_block(
                            raw, height, subsidy, params.max_block_size,
                            consensus.coinbase_maturity, mtp, bip34, flags,
                            want_sigs=check_scripts, commit=False,
                            nthreads=native.PAR_THREADS)
                    except native.EngineMissing as miss:
                        if service_misses(miss.keys) == 0:
                            return False  # truly missing inputs: Python path
                        res = eng.connect_block(
                            raw, height, subsidy, params.max_block_size,
                            consensus.coinbase_maturity, mtp, bip34, flags,
                            want_sigs=check_scripts, commit=False,
                            nthreads=native.PAR_THREADS)
                except (native.EngineMissing, native.EngineError):
                    eng.abort()
                    return False
            for key in scan_keys:
                stats[key] += getattr(res, key)
            note_queue_empty()  # the native call is where a kernel ends

            # BIP30 base-store leg: only pre-BIP34 heights can mint
            # duplicate txids (the engine checked its in-memory map; rows
            # flushed out of it need the batched base lookup)
            if height < consensus.bip34_height and res.n_tx:
                with span("import.store_read", bip30=res.n_tx):
                    keys = []
                    for i in range(res.n_tx):
                        txid = res.txid(i)
                        for o in range(int(res.tx_out_counts[i])):
                            keys.append(txid + struct.pack("<I", o))
                    clash = self.coins_db.get_serialized_many(keys)
                if clash:
                    eng.abort()
                    return False  # Python path raises bad-txns-BIP30

            if check_scripts and res.n_inputs:
                with span("import.lanes", inputs=res.n_inputs):
                    joined = join_lanes(raw, res, h, height, flags, prefork)
                if not joined:
                    eng.abort()
                    return False  # Python path re-derives the verdict

            with span("import.index", height=height):
                eng.commit()
                # -- Python bookkeeping (index, chain, stores) --
                idx.n_tx = res.n_tx
                cs._seq += 1
                idx.sequence_id = cs._seq
                idx.status |= BlockStatus.HAVE_DATA | BlockStatus.HAVE_UNDO
                idx.raise_validity(
                    BlockStatus.VALID_SCRIPTS if check_scripts
                    else BlockStatus.VALID_CHAIN)
                idx.chain_tx = prev.chain_tx + idx.n_tx
                cs.block_index[h] = idx
                cs._dirty_index.add(idx)
                if pos_info is not None:
                    self.block_store.positions.setdefault(h, pos_info)
                self.block_store.put_undo(h, res.undo)
                cs.chain.set_tip(idx)
                cs.bench["blocks"] += 1
                n_imported += 1
                stats["blocks"] += 1
                stats["prefork_blocks"] += prefork
            note_queue_empty()
            if max(agg_count.values()) >= AGG_LANES:
                flush_agg(everything=False)
            return True

        def classify(raw: bytes, pos_info: Optional[tuple]) -> tuple:
            """Where one record goes, from the index alone: (route, block
            hash, parent's index entry); a duplicate and a block without
            its parent are dealt with here (route None)."""
            h = sha256d_py(raw[:80])
            idx = cs.block_index.get(h)
            if idx is not None and (idx.status & BlockStatus.HAVE_DATA):
                if pos_info is not None:
                    self.block_store.positions.setdefault(h, pos_info)
                return None, h, None  # duplicate
            prev_hash = raw[4:36]
            prev = cs.block_index.get(prev_hash)
            if prev is None:
                pending.setdefault(prev_hash, []).append((raw, pos_info))
                return None, h, None
            fast = prev is cs.chain.tip() and idx is None
            return ("fast" if fast else "slow"), h, prev

        def process_raw(raw: bytes, pos_info: Optional[tuple],
                        route: Optional[tuple] = None) -> bool:
            if route is None:  # a parked child: the loop has not read it
                with span("import.read"):
                    route = classify(raw, pos_info)
            kind, h, prev = route
            if kind is None:
                return False
            if kind == "fast" and fast_connect(raw, h, prev, pos_info):
                return True
            return slow_path(raw, pos_info)

        def flush_if_due() -> None:
            """After a block has connected: -flushinterval counts the
            connected blocks the last flush does not hold (a duplicate or a
            parked record is none, a new block file changes nothing), so no
            more than that many are ever ahead of the store; -dbcache
            pressure flushes too, the engine's coins and the store's memory
            of the ones it served counted together."""
            if (cs.chain.tip().height - flushed_height[0] >= flush_interval
                    or eng.mem_bytes() + served_bytes() >= dbcache_bytes):
                fast_flush()

        from ..crypto.hashes import sha256d as sha256d_py

        # enumerate the store's own blk files (reindex source of truth).
        # The whole walk is wrapped so an abort (settle_oldest raising
        # _NativeImportAbort) still settles every in-flight BatchHandle —
        # an abandoned handle would leak STATS.in_flight and, worse,
        # strand the ecdsa breaker in HALF_OPEN forever if the dropped
        # dispatch was its recovery probe (allow() blocks until the probe
        # reports, and only handle settlement reports).
        try:
            n_file = 0
            while True:
                path = os.path.join(self.datadir, "blocks",
                                    f"blk{n_file:05d}.dat")
                if not os.path.exists(path):
                    break
                with span("import.read", file=n_file):
                    with open(path, "rb") as f:
                        data = f.read()
                pos = 0
                while pos + 8 <= len(data):
                    if data[pos:pos + 4] != magic:
                        pos += 1
                        continue
                    with span("import.read"):
                        (size,) = struct.unpack_from("<I", data, pos + 4)
                        start = pos + 8
                        if start + size > len(data):
                            break  # truncated tail record (crash mid-append)
                        raw = data[start:start + size]
                        pos_info = (n_file, start, size)
                        stats["bytes"] += size
                        route = classify(raw, pos_info)
                    if process_raw(raw, pos_info, route):
                        flush_if_due()
                        # cascade children parked on this block
                        queue = [route[1]]
                        while queue:
                            hh = queue.pop()
                            for c_raw, c_pos in pending.pop(hh, ()):
                                if process_raw(c_raw, c_pos):
                                    flush_if_due()
                                    queue.append(sha256d_py(c_raw[:80]))
                    pos = start + size
                n_file += 1

            fast_flush()
        finally:
            while inflight:
                handle = inflight.pop(0)[1]
                try:
                    handle.result()
                except Exception:  # noqa: BLE001 — abort-path drain
                    pass
        with span("import.close"):
            # safety: settle any side-chain candidates
            cs.activate_best_chain()
            cs.flush()
            eng.close()
        for key, was in zip(delta_keys, delta0):
            stats[key] = getattr(ecdsa_batch.STATS, key) - was
        return n_imported, stats

    def _import_block_files_python(self, paths: Optional[list[str]] = None) -> int:
        """The Python-engine import loop (reference implementation) — and
        the pipelined IBD driver: with -pipelinedepth > 1 each linear
        extension goes through ChainstateManager.process_new_block_pipelined,
        which overlaps the host script scan, the device signature settle,
        and the chainstate commit across up to ``pipelinedepth`` in-flight
        blocks (backpressure settles the oldest). The horizon is drained
        before the final flush, so the on-disk state a crash could observe
        is always a settled prefix of the import."""
        import struct

        magic = self.params.netmagic
        n_imported = 0
        pending: dict[bytes, list[CBlock]] = {}  # prev_hash -> blocks
        cs = self.chainstate

        def try_process(block: CBlock) -> bool:
            nonlocal n_imported
            try:
                cs.process_new_block_pipelined(block)
            except BlockValidationError as e:
                if e.reason == "prev-blk-not-found":
                    pending.setdefault(block.header.hash_prev_block, []).append(block)
                elif e.reason != "duplicate":
                    log_printf("reindex: rejected %s: %s",
                               hash_to_hex(block.get_hash())[:16], e.reason)
                return False
            n_imported += 1
            # cascade any children that were waiting on this block
            queue = [block.get_hash()]
            while queue:
                h = queue.pop()
                for child in pending.pop(h, ()):
                    try:
                        cs.process_new_block_pipelined(child)
                    except BlockValidationError:
                        continue
                    n_imported += 1
                    queue.append(child.get_hash())
            return True

        # (path, store file number | None). Scanning the store's OWN blk
        # files re-registers positions in place; re-appending each block
        # via put_block would double the on-disk chain every -reindex.
        # Explicit -loadblock files are foreign: those DO append.
        file_list: list[tuple[str, Optional[int]]]
        if paths is None:
            file_list = []
            n_file = 0
            while True:
                p = os.path.join(self.datadir, "blocks",
                                 f"blk{n_file:05d}.dat")
                if not os.path.exists(p):
                    break
                file_list.append((p, n_file))
                n_file += 1
        else:
            file_list = [(p, None) for p in paths]
        positions = getattr(self.block_store, "positions", None)
        for path, n_file in file_list:
            if not os.path.exists(path):
                log_printf("loadblock: %s not found, skipping", path)
                continue
            with open(path, "rb") as f:
                data = f.read()
            pos = 0
            while pos + 8 <= len(data):
                if data[pos:pos + 4] != magic:
                    pos += 1  # scan forward (reference tolerates garbage)
                    continue
                (size,) = struct.unpack_from("<I", data, pos + 4)
                start = pos + 8
                if start + size > len(data):
                    break  # truncated tail record (crash mid-append)
                try:
                    block = CBlock.from_bytes(data[start:start + size])
                except Exception:
                    pos += 1
                    continue
                if n_file is not None and positions is not None:
                    positions.setdefault(block.get_hash(),
                                         (n_file, start, size))
                try_process(block)
                pos = start + size
        # drain the settle horizon (flush() would too, but be explicit:
        # import ends with every block settled or unwound) then persist
        self.chainstate.settle_horizon()
        self.chainstate.flush()
        return n_imported

    # -- pruning (-prune / pruneblockchain) -----------------------------

    MIN_BLOCKS_TO_KEEP = 288  # validation.h MIN_BLOCKS_TO_KEEP

    def prune_block_files(self, prune_height: int, stop_when=None) -> int:
        """FindFilesToPrune + UnlinkPrunedFiles (src/validation.cpp):
        delete whole block files whose every block sits below
        prune_height, clearing HAVE_DATA/HAVE_UNDO on their index rows.
        ``stop_when()`` (checked after each pruned file) lets the -prune
        target mode stop as soon as usage is back under budget instead of
        shedding everything prunable. Returns the number of files pruned.
        Caller holds cs_main."""
        store = self.block_store
        if not hasattr(store, "prune_file"):
            return 0  # memory-backed store (tests)
        cs = self.chainstate
        prune_height = min(prune_height,
                           cs.tip().height - self.MIN_BLOCKS_TO_KEEP)
        pruned = 0
        for n in range(store._cur_file):
            hashes = store.blocks_in_file(n)
            if not hashes:
                continue
            heights = [cs.block_index[h].height
                       for h in hashes if h in cs.block_index]
            if not heights or max(heights) >= prune_height:
                continue
            for h in store.prune_file(n):
                idx = cs.block_index.get(h)
                if idx is not None:
                    idx.status &= ~(BlockStatus.HAVE_DATA
                                    | BlockStatus.HAVE_UNDO)
                    cs._dirty_index.add(idx)
            pruned += 1
            if stop_when is not None and stop_when():
                break
        if pruned:
            self._set_prune_height(max(self.prune_height, prune_height))
            cs.flush()
            log_printf("pruned %d block file(s) below height %d",
                       pruned, prune_height)
        return pruned

    def _set_prune_height(self, height: int) -> None:
        self.prune_height = height
        # survive restarts so pruneblockchain/getblockchaininfo stay right
        self._index_kv.write_batch({b"Fpruneheight": str(height).encode()})

    def auto_prune(self) -> None:
        """-prune=<MB> target mode: shed the OLDEST files until usage is
        back under the target (FindFilesToPrune stops at the budget — it
        never strips the chain down to the 288-block floor)."""
        if self.prune_target_bytes <= 0:
            return
        store = self.block_store
        if not hasattr(store, "file_usage"):
            return
        if store.file_usage() > self.prune_target_bytes:
            self.prune_block_files(
                self.chainstate.tip().height,
                stop_when=lambda: store.file_usage()
                <= self.prune_target_bytes,
            )

    # -- txindex (-txindex) --------------------------------------------

    _TXINDEX_PREFIX = b"t"

    def _txindex_add(self, block: CBlock, idx) -> None:
        puts = {
            self._TXINDEX_PREFIX + tx.txid: idx.hash for tx in block.vtx
        }
        self._index_kv.write_batch(puts)

    def _start_txindex_backfill(self) -> None:
        """-txindex on a synced datadir: backfill runs on a BACKGROUND
        thread in SCAN_CHUNK-height chunks taking cs_main per chunk — the
        reference's TxIndex::ThreadSync shape (init is not blocked; lookups
        can miss until synced, like the reference's 'syncing' txindex).
        New blocks connecting during backfill are indexed by the normal
        _txindex_add hook; re-writing a key is idempotent."""
        if self.index_db.kv.get(b"Ftxindex") == b"1":
            self._txindex_synced = True
            return
        self._txindex_thread = threading.Thread(
            target=self._txindex_backfill, name="txindex-sync", daemon=True
        )
        self._txindex_thread.start()

    def _txindex_backfill(self) -> None:
        try:
            self._txindex_backfill_inner()
        except Exception as e:  # noqa: BLE001 - daemon thread boundary
            # a silently-dead backfill thread would leave txindex
            # 'syncing' forever with no cause on record; the next restart
            # resumes from the persisted rows
            log_printf("txindex backfill aborted: %r", e)

    def _txindex_backfill_inner(self) -> None:
        """Uses the native wire scanner when available (txids without full
        Python deserialization — the reference keeps this path in C++ too);
        falls back to the Python deserializer per block."""
        from .. import native

        use_native = native.available()
        cs = self.chainstate
        height = 0
        while not self.shutdown_event.is_set():
            with self.cs_main:
                tip = cs.chain.height()
                if height > tip:
                    self.index_db.put_flag(b"txindex", True)
                    self._txindex_synced = True
                    log_printf("txindex backfill complete at height %d", tip)
                    return
                end = min(height + self.SCAN_CHUNK, tip + 1)
                for h in range(height, end):
                    idx = cs.chain[h]
                    txids = None
                    if use_native:
                        raw = self.block_store.get_block(idx.hash)
                        if raw is not None:
                            scan = native.scan_block(raw)
                            if scan is not None:
                                txids = scan.txids
                    if txids is None:
                        block = cs.get_block(idx.hash)
                        if block is None:
                            continue
                        txids = [tx.txid for tx in block.vtx]
                    self._index_kv.write_batch({
                        self._TXINDEX_PREFIX + txid: idx.hash
                        for txid in txids
                    })
                height = end
                if height <= tip:
                    log_print("txindex", "backfill: %d/%d blocks",
                              height, tip)
            # lock released between chunks: validation/RPC interleave

    def txindex_lookup(self, txid: bytes) -> Optional[bytes]:
        """GetTransaction's txindex path: txid -> containing block hash."""
        return self._index_kv.get(self._TXINDEX_PREFIX + txid)

    # -- servers --------------------------------------------------------

    def start_rpc(self) -> int:
        """AppInitServers: bind the JSON-RPC server; returns the bound port."""
        from ..rpc.server import RPCServer

        port = self.config.rpc_port(self.params)
        bind = self.config.get("rpcbind", "127.0.0.1")
        self.rpc_server = RPCServer(self, bind, port)
        self.rpc_server.start()
        log_printf("RPC server listening on %s:%d", bind, self.rpc_server.port)
        return self.rpc_server.port

    def start_p2p(self) -> int:
        """CConnman::Start: bind the P2P listener, dial -connect peers."""
        from ..p2p.connman import CConnman

        port = self.config.p2p_port(self.params)
        listen = self.config.get_bool("listen", True)
        self.connman = CConnman(self, "127.0.0.1", port if listen else 0)
        self.connman.start()
        for target in self.config.get_multi("connect"):
            host, _, p = target.rpartition(":")
            self.connman.connect_to(host or "127.0.0.1", int(p))
        return self.connman.port

    def start_gateway(self) -> int:
        """Bind the fleet serving front door (-gateway) over the
        -replicas pool; returns the bound port. The validator leg
        executes RPC handlers in-process (same dispatch as rpc/server);
        the replica legs speak JSON-RPC HTTP with the node's own
        -rpcuser/-rpcpassword — a fleet shares RPC credentials."""
        import base64

        from ..rpc.registry import RPC_METHODS, RPCError
        from ..serving.gateway import BackendRPCError, Gateway
        from ..serving.replicas import Replica, ReplicaPool, http_transport

        def _backend(method, params):
            handler = RPC_METHODS.get(method)
            if handler is None:
                raise BackendRPCError(
                    {"code": -32601, "message": "Method not found"})
            try:
                if getattr(handler, "no_cs_main", False):
                    return handler(self, list(params))
                with self.cs_main:
                    return handler(self, list(params))
            except RPCError as e:
                raise BackendRPCError(
                    {"code": e.code, "message": e.message}) from e

        def _tip_height() -> int:
            with self.cs_main:
                return self.chainstate.tip().height

        user = self.config.get("rpcuser")
        password = self.config.get("rpcpassword")
        if user and password:
            auth = base64.b64encode(f"{user}:{password}".encode()).decode()
        elif self.rpc_server is not None:
            auth = self.rpc_server._auth  # cookie-auth fleet (tests)
        else:
            raise InitError("-gateway needs -rpcuser/-rpcpassword (or a "
                            "running RPC server's cookie) for replica auth")
        replicas = [
            Replica(f"{host}:{port}", http_transport(host, port, auth))
            for host, port in self.replica_addrs
        ]
        pool = ReplicaPool(
            replicas, max_lag=self.max_replica_lag,
            probe_interval=self.config.get_int("gatewayprobems", 500) / 1e3,
            validator_tip=_tip_height)
        self.gateway = Gateway(
            _backend, pool,
            rate=self.config.get_int("gatewayrate", 500),
            burst=self.config.get_int("gatewayburst", 200),
            soft_inflight=self.config.get_int("gatewaysoft", 64),
            hard_inflight=self.config.get_int("gatewayhard", 256),
            bind=self.config.get("gatewaybind", "127.0.0.1"),
            port=self.gateway_port, auth_b64=auth)
        self.gateway.start()
        return self.gateway.port

    def load_wallet(self):
        from ..wallet.wallet import Wallet

        if self.wallet is not None and self._wallet_ready.is_set():
            return self.wallet
        if self._wallet_loader == threading.get_ident():
            return self.wallet  # re-entrant call from our own load path
        if self.wallet is None:
            # first loader: callers hold cs_main, so the None check and the
            # assignment below are mutually exclusive — a second thread can
            # only arrive once we yield mid-rescan, and then takes the
            # wait branch
            self._wallet_loader = threading.get_ident()
            try:
                path = os.path.join(self.datadir, "wallet.json")
                self.wallet = Wallet(params=self.params, path=path)
                self.wallet.load()
                if self.wallet._pkh_index or self.wallet.keys_by_pubkey:
                    self._rescan_wallet()  # ScanForWalletTransactions
                # replay the (possibly mempool.dat-reloaded) pool so pending
                # spends of wallet coins are marked before CreateTransaction
                for e in self.mempool.entries.values():
                    self.wallet.add_tx_if_mine(e.tx, -1, False)
                self.chainstate.on_block_connected.append(
                    self.wallet.block_connected)
                self.chainstate.on_block_disconnected.append(
                    self.wallet.block_disconnected)
                # -walletnotify=<cmd>: shell hook per wallet-affecting tx as
                # it confirms (init.cpp/wallet.cpp BlockConnected notify);
                # registered AFTER wallet.block_connected so tx_log is
                # current
                notify = self.config.get("walletnotify")
                if notify:
                    self.chainstate.on_block_connected.append(
                        lambda block, idx: self._walletnotify(notify, block)
                    )
                self._wallet_ready.set()
            except BaseException:
                # a failed load (corrupt wallet.json, rescan error) must
                # not leave self.wallet half-set with _wallet_ready never
                # signaled — every later wallet RPC would spin in the wait
                # loop forever (ADVICE r4). Reset so a retry can load.
                bad = self.wallet
                self.wallet = None
                if bad is not None:
                    for lst in (self.chainstate.on_block_connected,
                                self.chainstate.on_block_disconnected):
                        for cb in (bad.block_connected,
                                   bad.block_disconnected):
                            if cb in lst:
                                lst.remove(cb)
                raise
            finally:
                self._wallet_loader = None
            return self.wallet
        # another thread is mid-load/rescan: wait for it WITH cs_main
        # released (waiting while holding would deadlock the rescanner's
        # chunk reacquire); non-wallet RPCs keep running in those windows
        while not self._wallet_ready.is_set():
            if self.shutdown_event.is_set():
                break
            released = False
            try:
                self.cs_main.release()
                released = True
            except RuntimeError:
                pass
            try:
                self._wallet_ready.wait(0.05)
            finally:
                if released:
                    self.cs_main.acquire()
        return self.wallet

    def _walletnotify(self, cmd: str, block: CBlock) -> None:
        import subprocess

        from ..consensus.serialize import hash_to_hex as _h2h

        for tx in block.vtx:
            if tx.txid in self.wallet.tx_log:
                try:
                    subprocess.Popen(
                        cmd.replace("%s", _h2h(tx.txid)), shell=True
                    )
                except OSError as e:
                    log_printf("walletnotify failed: %r", e)

    # blocks per cs_main hold during rescan/backfill (liveness knob: the
    # O(height) scans must not starve RPC on a long chain — VERDICT r3 #10)
    SCAN_CHUNK = 200

    def _cs_yield(self) -> bool:
        """Release cs_main (if held exactly once by this thread), give a
        waiting thread a chance to take it, and reacquire. Returns whether
        a yield actually happened. The RPC layer acquires cs_main exactly
        once around handlers; a deeper reentrant hold just skips the yield
        (correct, only less live)."""
        try:
            self.cs_main.release()
        except RuntimeError:
            return False  # not held by us: nothing to yield
        try:
            time.sleep(0)  # scheduler hint: let a blocked RPC thread in
        finally:
            self.cs_main.acquire()
        return True

    def _rescan_wallet(self) -> None:
        """CWallet::ScanForWalletTransactions over the active chain — a
        reloaded wallet file has keys but no coin state. Chunked: cs_main
        is yielded between SCAN_CHUNK-block chunks so concurrent RPC stays
        responsive on a long chain (the reference takes cs_main per block
        in ScanForWalletTransactions, not across the whole scan)."""
        cs = self.chainstate
        height = 0
        total = cs.tip().height
        while height <= total:
            end = min(height + self.SCAN_CHUNK, total + 1)
            for h in range(height, end):
                idx = cs.chain[h]
                block = cs.get_block(idx.hash)
                if block is not None:
                    self.wallet.block_connected(block, idx)
            height = end
            if height <= total:
                log_printf("wallet rescan: %d/%d blocks", height, total)
                self._cs_yield()
                # the tip may have advanced while unlocked; extend the scan
                total = cs.tip().height

    # -- lifecycle ------------------------------------------------------

    def wait_for_shutdown(self) -> None:
        self.shutdown_event.wait()

    def stop(self) -> None:
        self.shutdown_event.set()

    def close(self) -> None:
        """Shutdown (src/init.cpp): stop servers, flush, close stores."""
        self.shutdown_event.set()
        if self._snapshot_thread is not None:
            # the verify thread checks shutdown_event between blocks and
            # persists its shadow progress; it must not race the store
            # closes below
            self._snapshot_thread.join(timeout=30)
            self._snapshot_thread = None
        if self._txindex_thread is not None:
            # the backfill thread checks shutdown_event between chunks and
            # must not race the kv-store closes below
            self._txindex_thread.join(timeout=30)
            self._txindex_thread = None
        if self.zmq_publishers:
            for pub in self.zmq_publishers:
                pub.close()
            self.zmq_publishers = []
            # unregister so a block connecting mid-shutdown can't reach a
            # closed publisher (the guard in _zmq_block is the backstop)
            try:
                self.chainstate.on_block_connected.remove(self._zmq_block)
            except ValueError:
                pass
        if self.gateway is not None:
            # front door first: stop admitting before the backends close
            # (also unregisters the gateway's registry collector)
            self.gateway.close()
            self.gateway = None
        if self.rpc_server is not None:
            self.rpc_server.close()
            self.rpc_server = None
        if self.connman is not None:
            self.connman.close()
            self.connman = None
        if self.sigservice is not None:
            # drain pending lanes before the stores close (a late settle
            # still inserts into the in-memory sigcache — harmless)
            self.sigservice.stop()
        with self.miner_lock:
            # a generate_to_script call in flight searches under no chain
            # lock: it sees shutdown_event before its next block, and must
            # have connected its last one before the stores close below
            if self.resident_miner is not None:
                # drops the device template buffers and the miner watchdog
                # registration (same closure-leak lesson as the collectors)
                self.resident_miner.close()
                self.resident_miner = None
        with self.cs_main:
            if self.persist_mempool:
                from ..mempool.persist import dump_mempool

                try:
                    n = dump_mempool(self.mempool, self._mempool_dat)
                    log_print("mempool", "DumpMempool: %d entries", n)
                except OSError as e:
                    # a failed dump must not abort the rest of shutdown
                    # (chainstate flush + store closes still run)
                    log_printf("DumpMempool failed: %r", e)
            try:
                self.fee_estimator.flush()  # fee_estimates.dat analogue
            except OSError as e:
                log_printf("fee estimator flush failed: %r", e)
            self.chainstate.flush()
            self.block_store.close()
            self._index_kv.close()
            if self._coins_kv is not None:
                self._coins_kv.close()
            else:
                self.coins_db.close()
        # drop this node's registry collectors: the bound methods would
        # otherwise keep the closed node's whole object graph (coins
        # cache, mempool, block index) alive in the process-global
        # REGISTRY for the rest of the process
        for name in ("sigcache", "pipeline", "mempool", "mempool_perf",
                     "serving", "mining", "store", "lockwatch"):
            telemetry.REGISTRY.unregister_collector(name)
        # same lesson for the watchdog: its pending_fn closures must not
        # keep a closed node alive (sigservice.stop() already dropped its
        # own registration above)
        from ..util import devicewatch as _dw

        _dw.WATCHDOG.unregister("pipeline")
        if self.tracefile:
            # -tracefile: the span ring buffer as Chrome/perfetto JSON,
            # written LAST so shutdown's own flush spans are included
            try:
                n = telemetry.TRACER.dump(self.tracefile)
                log_printf("-tracefile: %d span(s) -> %s", n, self.tracefile)
            except OSError as e:
                log_printf("-tracefile dump failed: %r", e)
        log_printf("bcpd shutdown complete")
