"""The "dead backend" end-to-end acceptance test: with fail-always faults
armed on EVERY TPU subsystem, a multi-block connect run (including signed
spends, a large-ish merkle block, mining, and batched header PoW) must
complete with verdicts and a final coin set byte-identical to the pure-CPU
reference engine, while every circuit breaker reports open with nonzero
fallback counts — the whole robustness tentpole in one scenario."""

import pytest

from bitcoincashplus_tpu.consensus.tx import COutPoint, CTransaction, CTxIn, CTxOut
from bitcoincashplus_tpu.consensus.params import regtest_params
from bitcoincashplus_tpu.mining.generate import generate_blocks
from bitcoincashplus_tpu.ops import dispatch, ecdsa_batch
from bitcoincashplus_tpu.store.blockstore import MemoryBlockStore
from bitcoincashplus_tpu.validation.chainstate import ChainstateManager
from bitcoincashplus_tpu.validation.coins import MemoryCoinsView
from bitcoincashplus_tpu.validation.scriptcheck import BlockScriptVerifier
from bitcoincashplus_tpu.wallet.keys import CKey
from bitcoincashplus_tpu.wallet.signing import sign_transaction

from test_validation import TILE, _hand_mine

pytestmark = pytest.mark.faults

KEY = CKey(0xFEEDFACE1234)
SPK_KEY = KEY.p2pkh_script()
SPK_SINK = bytes.fromhex("76a914") + b"\x99" * 20 + bytes.fromhex("88ac")


def _build_chainstate(backend: str, start: int = 1_600_000_000):
    params = regtest_params()
    t = [start]

    def fake_time():
        t[0] += 60
        return t[0]

    base = MemoryCoinsView()
    cs = ChainstateManager(
        params, base, MemoryBlockStore(),
        script_verifier=BlockScriptVerifier(params, backend=backend),
        get_time=fake_time,
    )
    cs.test_base = base
    cs.test_clock = t
    return cs


def _coin_set(cs) -> dict:
    """Byte-exact snapshot of the flushed UTXO set + best-block marker."""
    cs.coins.flush()
    coins = {
        (op.hash, op.n): coin.serialize()
        for op, coin in cs.test_base.all_coins()
    }
    coins["best"] = cs.test_base.best_block()
    return coins


@pytest.fixture
def fake_ecdsa_kernel(stub_verify_kernels):
    """Oracle-backed stand-in for the device verify programs (the real
    ones cost minutes of compile on the CPU test backend; the supervision
    plumbing under test is identical). Only reachable through half-open
    probes — with fail-always armed the injector kills the dispatch
    first."""
    return stub_verify_kernels


def test_dead_backend_end_to_end(fault_harness, fake_ecdsa_kernel,
                                 monkeypatch):
    # -- 1. reference run: pure-CPU engine mines the canonical chain ------
    dispatch.reset()
    ref = _build_chainstate(backend="cpu")
    generate_blocks(ref, SPK_KEY, 102, tile=TILE)
    spends = []
    for h in (1, 2):
        blk = ref.get_block(ref.chain[h].hash)
        cb = blk.vtx[0]
        tx = CTransaction(
            vin=(CTxIn(COutPoint(cb.txid, 0)),),
            vout=(CTxOut(cb.vout[0].value - 10_000, SPK_SINK),),
        )
        spends.append(sign_transaction(
            tx, [(SPK_KEY, cb.vout[0].value)],
            lambda i: KEY if i == KEY.pubkey_hash else None,
            enable_forkid=True,
        ))
    tip = ref.tip()
    spend_block = _hand_mine(
        tip.hash, tip.height + 1, ref.get_time() + 10, tip.bits,
        tuple(spends),
    )
    ref.process_new_block(spend_block)
    assert ref.tip().hash == spend_block.get_hash()
    chain_blocks = [ref.get_block(ref.chain[h].hash)
                    for h in range(1, ref.tip().height + 1)]

    # -- 2. faulty run: every TPU op dead, device backend forced ----------
    # breaker: first failure opens, no probes — the dead device stays dead
    dispatch.configure(threshold=1, retries=0, cooldown=1e9, probe=0.0)
    fault_harness("fail-always", ops="all")
    # force the device merkle path even for small blocks so the merkle
    # breaker is exercised during connect
    monkeypatch.setenv("BCP_TPU_MERKLE_MIN", "2")

    # start the faulty node's clock where the reference's ended — the
    # mined headers carry the reference clock's timestamps
    faulty = _build_chainstate(backend="device", start=ref.test_clock[0])
    for blk in chain_blocks:
        faulty.process_new_block(blk)
    assert faulty.tip().hash == ref.tip().hash

    # mining still works on the dead backend (scalar CPU loop under the
    # miner breaker) and the mined block is valid on the reference engine
    mined = generate_blocks(faulty, SPK_SINK, 1, tile=TILE)
    assert len(mined) == 1
    extra = faulty.get_block(mined[0])
    ref.test_clock[0] = faulty.test_clock[0]  # keep the clocks in step
    ref.process_new_block(extra)
    assert ref.tip().hash == faulty.tip().hash

    # batched header PoW (sha256 subsystem) under the dead backend
    from bitcoincashplus_tpu.consensus.pow import check_headers_pow_batch

    headers = [b.header.serialize() for b in chain_blocks[:8]]
    assert check_headers_pow_batch(
        headers, regtest_params().consensus) == [True] * len(headers)

    # -- 3. acceptance: verdicts + coin set byte-identical ----------------
    assert _coin_set(faulty) == _coin_set(ref)

    # -- 4. gettpuinfo: open breakers with nonzero fallback counts --------
    snap = dispatch.snapshot()
    for site in ("ecdsa", "merkle", "miner", "sha256"):
        assert snap[site]["state"] == "open", (site, snap[site])
        assert snap[site]["fallback_items"] > 0, (site, snap[site])
    assert ecdsa_batch.STATS.fault_fallback_sigs >= 2
