"""The cell reindex.mixed_era: it loads from BENCHMARK.json by name, its
control flow runs on the CPU (--rehearse), the harness's own faults come out
not correct, a program without multisig lanes is refused at once, and the
generator is a function of its seed."""

import json
import os
import subprocess
import sys

import pytest
import run

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GEN = os.path.join(BENCH, "gen", "mixedchain.py")
TRAFFIC = os.path.join(BENCH, "traffic", "mixed_era.json")
sys.path.insert(0, os.path.join(BENCH, "gen"))
NEW_READERS = ("import.script_leg_share", "interp.us_per_input",
               "multisig.lanes_per_sig")


def test_the_cell_loads_with_at_least_its_ten_per_layer_metrics():
    """The ten it was added with, in their order; a later PR may append
    (PR 31 did: script_leg.template_share)."""
    loaded = run.load_cell("reindex.mixed_era")
    assert loaded["cell"]["chips"] == 1
    assert loaded["config"]["driver"] == "reindex_mixed"
    assert loaded["config"]["name"] == "archival-reindex-mixed"
    assert [m["name"] for m in loaded["end_to_end"]] == [
        "reindex_sigs_per_s", "setup_s"]
    names = [m["name"] for m in loaded["per_layer"]]
    assert names[:10] == [
        "compile.listener_s", "import.verify_share", "import.host_share",
        "dispatch.lane_fill", "glv.kernel_ms", "glv_roofline",
        "device_idle.reindex", *NEW_READERS]
    for name in names:
        assert callable(run.load_module("layer_metrics", name).read)
    traffic = loaded["traffic"]
    assert traffic["input_mix"] == {"p2pkh": 74, "p2sh_multisig": 24,
                                    "p2pk": 1, "bare_multisig": 1}
    assert sum(traffic["inputs_per_tx"].values()) == 100
    assert traffic["lanes"] == 8190 and traffic["keys"] == 64
    # a 30 s call gets a window of 27-33 s only on a whole number of
    # twentieths
    assert round(traffic["buckets_per_window_second"] * 20, 9) % 1 == 0


def test_the_new_readers_find_nothing_on_a_program_without_the_counters():
    """The parent commit has neither fallback_s nor multisig_lanes, and an
    aborted import leaves no stopwatch at all: the readers return None and
    do not raise."""
    older = {"wall_s": 2.0, "verify_s": 1.0, "fallback_inputs": 5}
    for stats in (older, None):
        obs = {"after": {"import": stats},
               "result": {"report": {"multisig_sigs": 40}}}
        for name in NEW_READERS:
            assert run.load_module("layer_metrics", name).read(obs) is None
    obs = {"after": {"import": {
        "wall_s": 2.0, "fallback_s": 0.5, "fallback_inputs": 5,
        "multisig_lanes": 80}},
        "result": {"report": {"multisig_sigs": 40}}}
    read = {name: run.load_module("layer_metrics", name).read(obs)
            for name in NEW_READERS}
    assert read["import.script_leg_share"] == pytest.approx(25.0)
    assert read["interp.us_per_input"] == pytest.approx(100000.0)
    assert read["multisig.lanes_per_sig"] == pytest.approx(2.0)


def rehearse(capsys, seed, *extra):
    rc = run.main(["--workload", "reindex.mixed_era", "--seed", str(seed),
                   "--seconds", "1", "--rehearse", *extra])
    lines = [json.loads(line) for line in
             capsys.readouterr().out.strip().splitlines()]
    compared = {n["name"]: n for n in lines if n.get("phase") == "compared"}
    return rc, lines[-1], compared


def test_sound_run_is_correct(capsys):
    rc, last, compared = rehearse(capsys, 2**31 + 27)
    assert rc == 0 and last["correct"] is True and last["failed"] == 0
    assert all(n["ok"] for n in compared.values())
    assert set(compared) == {
        "tip_height_gap", "tip_hash_differs", "utxo_count_gap",
        "lanes_not_verified_on_device", "multisig_lanes_gap",
        "eager_multisig_sigs_moved", "reject_confirm_sigs_moved",
        "multisig_group_confirms_moved", "sampled_multisig_walks_differ",
        "slow_path_blocks", "fallback_inputs_gap",
        "sampled_inputs_refused_by_reference"}


def test_wrong_key_multisig_is_not_correct(capsys):
    """The chain's last pay-to-script-hash input signed by a key outside its
    redeem script: the reference refuses it; the node's walk fails, the host
    confirms that, and the node stops one block short, where the reference
    does."""
    rc, last, compared = rehearse(capsys, 2**31 + 28, "--fault",
                                  "wrong-key-multisig")
    assert last["correct"] is False
    assert not compared["sampled_inputs_refused_by_reference"]["ok"]
    assert not compared["sampled_multisig_walks_differ"]["ok"]
    assert compared["multisig_group_confirms_moved"]["value"] == 1
    assert compared["tip_height_gap"]["ok"]


def test_wrong_key_sig_is_not_correct(capsys):
    """The sibling's fault on this chain: the last pay-to-pubkey-hash input
    carries its script's key and a signature made with another secret."""
    rc, last, compared = rehearse(capsys, 2**31 + 29, "--fault",
                                  "wrong-key-sig")
    assert last["correct"] is False
    assert not compared["sampled_inputs_refused_by_reference"]["ok"]
    assert compared["multisig_group_confirms_moved"]["value"] == 0
    assert compared["tip_height_gap"]["ok"]


def test_a_program_without_multisig_lanes_is_refused_before_any_chain(
        capsys, monkeypatch):
    """What the parent commit looks like to the driver: gettpuinfo.batch
    without the counter. Nothing is generated, nothing imported."""
    from bitcoincashplus_tpu.ops import ecdsa_batch

    real = ecdsa_batch.BatchStats.snapshot

    def older(self):
        return {k: v for k, v in real(self).items()
                if not k.startswith("multisig_")}

    monkeypatch.setattr(ecdsa_batch.BatchStats, "snapshot", older)
    with pytest.raises(RuntimeError, match="multisig_lanes"):
        run.main(["--workload", "reindex.mixed_era", "--seed", "5",
                  "--seconds", "1", "--rehearse"])
    assert '"phase": "setup"' not in capsys.readouterr().out


def generate(datadir, seed, lanes, *extra) -> dict:
    out = subprocess.run(
        [sys.executable, GEN, "--datadir", str(datadir), "--seed",
         str(seed), "--lanes", str(lanes), "--traffic", TRAFFIC,
         "--rehearse", "--workers", "2", *extra],
        check=True, capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_same_seed_same_chain_other_seed_other_chain(tmp_path):
    a = generate(tmp_path / "a", 2**31 + 11, 300)
    b = generate(tmp_path / "b", 2**31 + 11, 300, "--workers", "1")
    c = generate(tmp_path / "c", 2**31 + 12, 300)
    assert a["tip_hash"] == b["tip_hash"] and a["txouts"] == b["txouts"]
    assert a["tip_hash"] != c["tip_hash"]
    kinds = a["inputs_by_kind"]
    assert a["device_lanes"] == 300 == (
        kinds["p2pkh"] + kinds["p2pk"] + a["multisig_lanes"])
    assert a["multisig_lanes"] == (4 * kinds["p2sh_multisig"]
                                   + 2 * kinds["bare_multisig"])
    assert a["sigs"] == (kinds["p2pkh"] + kinds["p2pk"]
                         + a["multisig_sigs"])
    with open(tmp_path / "a" / "signers.json") as f:
        signers = json.load(f)
    assert {k: len(v) for k, v in signers.items()} == {
        "p2sh_multisig": kinds["p2sh_multisig"],
        "bare_multisig": kinds["bare_multisig"]}
    with pytest.raises(subprocess.CalledProcessError):
        generate(tmp_path / "x", 5, 300, "--fault", "no-such-fault")


def test_the_plan_is_exact_for_every_bucket_count_at_the_real_sizes():
    """The plan alone (nothing signed) at the cell's own parameters: the
    lanes come out exact, the mix within a point, signer pairs in thirds."""
    import mixedchain

    with open(TRAFFIC) as f:
        traffic = json.load(f)
    for buckets, seed in ((1, 2**31 + 1), (4, 7), (17, 2**32 + 5),
                          (48, 2**31 + 9)):
        plan = mixedchain.make_plan(seed, 8190 * buckets, traffic)
        inputs = [i for tx in plan["txs"] for i in tx]
        device = sum(mixedchain.lanes_of(i[0]) for i in inputs)
        assert device + plan["fan"] == 8190 * buckets
        assert plan["fan"] == -(-len(inputs) // traffic["fan_k"])
        assert plan["padded_inputs"] <= 3 * 250
        for kind, share in traffic["input_mix"].items():
            got = 100.0 * sum(1 for i in inputs if i[0] == kind) / len(inputs)
            assert abs(got - share) <= 1.0 + 100.0 * 750 / len(inputs), (
                kind, got)
        pairs = {}
        for kind, keys, signers in inputs:
            assert len(set(keys)) == len(keys)
            if kind == "p2sh_multisig":
                pairs[signers] = pairs.get(signers, 0) + 1
        assert max(pairs.values()) - min(pairs.values()) <= 1
        assert max(len(tx) for tx in plan["txs"]) == 250
