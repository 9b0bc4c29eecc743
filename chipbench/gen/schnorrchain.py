"""Seeded signature-dense regtest chain under BCH Schnorr signatures: the
traffic generator of the cell reindex.schnorr_dense.

gen/sigchain.py's deck (the runway, the fan-out, the dense blocks of
``txs_per_block`` transactions of ``inputs_per_tx`` pay-to-pubkey-hash
inputs, the exact signature count, the seed's key, clock and coinbase tag),
with every signature of the chain, the fan-out's too, in the 65-byte form of
the 2019-05-15 upgrade: r || s || 0x41 over the same SIGHASH_ALL|FORKID
digest, signed on the workers by native/secp256k1.cpp's bcp_schnorr_sign
(the deterministic nonce of crypto/secp256k1.schnorr_sign).

sigchain.py is imported, not edited: its ``generate`` finds the signing
worker's two functions and the list of faults by their module names, and
this file puts its own there before it calls it. The faulted input is the
one sigchain.py chooses for ``wrong-key-sig`` (the first input of the last
dense block's first transaction); which fault the worker writes there
reaches it through the environment, since the pool's initializer takes the
seed alone:

* ``wrong-key-sig``: the right public key in the scriptSig, another secret
  behind the signature, so the verify equation fails;
* ``wrong-jacobi``: the right secret and a nonce k whose R = k*G has a
  non-residue y, NOT negated: s*G = R + e*P and R.x = r hold, only
  jacobi(R.y) = 1 fails. A verifier without the Jacobi test accepts it.

    python chipbench/gen/schnorrchain.py --datadir D --seed N --sigs T [--fault F]

prints one JSON line: what a -reindex of D has to reproduce.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import sigchain  # noqa: E402  (puts the repo's root on sys.path)

FAULTS = ("wrong-key-sig", "wrong-jacobi")
FAULT_ENV = "CHIPBENCH_SCHNORR_FAULT"
_W: dict = {}


def _worker_init(seed: int) -> None:
    from bitcoincashplus_tpu.wallet.keys import CKey

    key = CKey(sigchain.secret_from_seed(seed), compressed=True)
    bad = CKey(sigchain.secret_from_seed(seed, b"other"), compressed=True)
    bad.pubkey = key.pubkey
    _W.update(key=key, bad=bad, spk=key.p2pkh_script(),
              fault=os.environ.get(FAULT_ENV, ""))


def _unnegated_signature(key, digest: bytes) -> bytes:
    """r || s under ``key`` for the first RFC 6979 candidate nonce whose R
    has a non-residue y, kept as it is."""
    from bitcoincashplus_tpu.crypto import secp256k1 as secp

    e = int.from_bytes(digest, "big")
    for attempt in range(256):
        k = secp.rfc6979_nonce(key.secret, e,
                               extra=b"Schnorr+SHA256  " + bytes([attempt]))
        if secp.jacobi(secp.point_mul(k, secp.G)[1]) != 1:
            r, s = secp.schnorr_sign_with_nonce(key.secret, e, k,
                                                negate=False)
            return r.to_bytes(32, "big") + s.to_bytes(32, "big")
    raise RuntimeError("no candidate nonce with a non-residue R.y")


def _sign_spend(job: tuple) -> bytes:
    """sigchain._sign_spend's job under Schnorr: (inputs [(txid, index,
    value)], output value, output count, faulted input or None) -> the
    signed transaction's bytes."""
    from bitcoincashplus_tpu.consensus.tx import (
        COutPoint,
        CTransaction,
        CTxIn,
        CTxOut,
    )
    from bitcoincashplus_tpu.script.script import push_data_raw
    from bitcoincashplus_tpu.script.sighash import signature_hash
    from bitcoincashplus_tpu.wallet.signing import sign_transaction

    inputs, out_value, out_count, bad_input = job
    key, spk = _W["key"], _W["spk"]
    unsigned = CTransaction(
        version=1,
        vin=tuple(CTxIn(COutPoint(t, i), b"", 0xFFFFFFFE)
                  for t, i, _ in inputs),
        vout=tuple(CTxOut(out_value, spk) for _ in range(out_count)),
    )
    spent = [(spk, v) for _, _, v in inputs]
    signed = sign_transaction(unsigned, spent, lambda ident: key,
                              enable_forkid=True, schnorr=True)
    if bad_input is not None:
        vin = list(signed.vin)
        if _W["fault"] == "wrong-jacobi":
            digest = signature_hash(spk, unsigned, bad_input, 0x41,
                                    inputs[bad_input][2], enable_forkid=True)
            sig = _unnegated_signature(key, digest) + b"\x41"
            vin[bad_input] = CTxIn(
                vin[bad_input].prevout,
                push_data_raw(sig) + push_data_raw(key.pubkey),
                vin[bad_input].sequence)
        else:
            forged = sign_transaction(
                unsigned, spent, lambda ident: _W["bad"],
                enable_forkid=True, schnorr=True)
            vin[bad_input] = forged.vin[bad_input]
        signed = CTransaction(signed.version, tuple(vin), signed.vout,
                              signed.locktime)
    return signed.serialize()


def generate(datadir: str, seed: int, total_sigs: int, *, fault: str = "",
             **deck) -> dict:
    """sigchain.generate with this file's signer in its worker's place."""
    if fault and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; known: {FAULTS}")
    os.environ[FAULT_ENV] = fault
    sigchain._worker_init = _worker_init
    sigchain._sign_spend = _sign_spend
    # sigchain places its one fault; the worker writes this file's there
    summary = sigchain.generate(datadir, seed, total_sigs,
                                fault="wrong-key-sig" if fault else "",
                                **deck)
    return dict(summary, fault=fault or None, scheme="schnorr")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--datadir", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--sigs", type=int, required=True)
    ap.add_argument("--inputs-per-tx", type=int, default=250)
    ap.add_argument("--txs-per-block", type=int, default=27)
    ap.add_argument("--fan-k", type=int, default=2000)
    ap.add_argument("--fault", default="")
    ap.add_argument("--workers", type=int, default=0)
    args = ap.parse_args()
    print(json.dumps(generate(
        args.datadir, args.seed, args.sigs,
        inputs_per_tx=args.inputs_per_tx, txs_per_block=args.txs_per_block,
        fan_k=args.fan_k, fault=args.fault, workers=args.workers)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
