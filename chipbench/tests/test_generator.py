"""The chain generator: the same seed makes the same chain, another seed
another, and the signature count is an exact multiple of the lane count."""

import json
import os
import subprocess
import sys

import pytest
import reference

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GEN = os.path.join(BENCH, "gen", "sigchain.py")
sys.path.insert(0, os.path.join(BENCH, "gen"))
SMALL = ["--inputs-per-tx", "25", "--txs-per-block", "4", "--fan-k", "100",
         "--workers", "2"]


def generate(datadir, seed, sigs, *extra) -> dict:
    out = subprocess.run(
        [sys.executable, GEN, "--datadir", str(datadir), "--seed",
         str(seed), "--sigs", str(sigs), *SMALL, *extra],
        check=True, capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_split_is_exact_for_every_bucket_count():
    import sigchain

    for buckets in (1, 2, 4, 8, 16, 21, 26, 43):
        dense, fan = sigchain.split_sigs(8190 * buckets, 2000)
        assert dense + fan == 8190 * buckets
        assert fan == -(-dense // 2000)
    assert sigchain.split_sigs(8190 * 16, 2000) == (130974, 66)
    assert sigchain.split_sigs(8190, 2000) == (8185, 5)


def test_same_seed_same_chain_other_seed_other_chain(tmp_path):
    a = generate(tmp_path / "a", 2**31 + 11, 600)
    b = generate(tmp_path / "b", 2**31 + 11, 600, "--workers", "1")
    c = generate(tmp_path / "c", 2**31 + 12, 600)
    assert a["tip_hash"] == b["tip_hash"] and a["txouts"] == b["txouts"]
    assert a["tip_hash"] != c["tip_hash"]
    assert a["sigs"] == 600 == a["dense_sigs"] + a["fan_sigs"]
    # the plain reference replays the files to the same answers and finds
    # exactly as many signed inputs as the generator says it signed
    ref = reference.scan_chain(str(tmp_path / "a" / "regtest" / "blocks"),
                               seed=1, sample=8)
    assert (ref["height"], ref["tip_hash"], ref["utxos"]) == (
        a["tip_height"], a["tip_hash"], a["txouts"])
    assert ref["signed_inputs"] == 600 and ref["first_bad_height"] is None


def test_the_fault_is_one_signature_the_reference_refuses(tmp_path):
    bad = generate(tmp_path / "bad", 5, 300, "--fault", "wrong-key-sig")
    ref = reference.scan_chain(str(tmp_path / "bad" / "regtest" / "blocks"),
                               seed=5, sample=10**6)
    assert bad["fault_at"] is not None
    assert ref["first_bad_height"] == bad["tip_height"]
    assert ref["height"] == bad["tip_height"] - 1
    with pytest.raises(subprocess.CalledProcessError):
        generate(tmp_path / "x", 5, 300, "--fault", "no-such-fault")
