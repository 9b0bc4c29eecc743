"""The cell reindex.pre_fork: it loads from BENCHMARK.json by name, its
control flow runs on the CPU (--rehearse), the generator's own faults come
out not correct, and a program that cannot connect blocks below the fork
height natively is refused before anything is generated."""

import json

import pytest
import run

NEW_READERS = ("sighash.legacy_kb_per_sig", "sigscan.legacy_sighash_share",
               "prefork.device_lane_share")


def test_the_cell_loads_with_at_least_its_per_layer_metrics():
    loaded = run.load_cell("reindex.pre_fork")
    assert loaded["cell"]["chips"] == 1
    assert loaded["config"]["driver"] == "reindex_prefork"
    assert loaded["config"]["name"] == "archival-reindex-prefork"
    assert "-uahfheight=1000000000" in loaded["config"]["flags"]
    assert [m["name"] for m in loaded["end_to_end"]] == [
        "reindex_sigs_per_s", "setup_s"]
    names = {m["name"] for m in loaded["per_layer"]}
    # a superset: a later PR may append the cell to another metric's list
    assert names >= {
        "compile.listener_s", "import.verify_share", "import.host_share",
        "import.script_leg_share", "dispatch.lane_fill", "glv.kernel_ms",
        "glv_roofline", "device_idle.reindex", "script_leg.template_share",
        "multisig.lanes_per_sig", "interp.us_per_input", *NEW_READERS}
    for name in names:
        assert callable(run.load_module("layer_metrics", name).read)
    traffic, sibling = loaded["traffic"], run.load_cell(
        "reindex.mixed_era")["traffic"]
    # the sibling's deck unchanged: the two cells differ by the era alone
    for key in ("lanes", "input_mix", "inputs_per_tx", "block_bytes", "keys",
                "fan_k", "sample_sigs", "warm_buckets", "trace_buckets",
                "rehearse"):
        assert traffic[key] == sibling[key], key
    # a 30 s call gets a whole number of buckets only on a whole number of
    # twentieths
    assert round(traffic["buckets_per_window_second"] * 20, 9) % 1 == 0


def rehearse(capsys, seed, *extra):
    rc = run.main(["--workload", "reindex.pre_fork", "--seed", str(seed),
                   "--seconds", "1", "--rehearse", *extra])
    lines = [json.loads(line) for line in
             capsys.readouterr().out.strip().splitlines()]
    compared = {n["name"]: n for n in lines if n.get("phase") == "compared"}
    return rc, lines[-1], compared


def test_sound_run_is_correct(capsys):
    rc, last, compared = rehearse(capsys, 2**31 + 32)
    assert rc == 0 and last["correct"] is True and last["failed"] == 0
    assert all(n["ok"] for n in compared.values())
    assert set(compared) >= {
        "tip_hash_differs", "lanes_not_verified_on_device",
        "prefork_lanes_gap", "prefork_blocks_gap",
        "inline_legacy_sigs_moved", "slow_path_blocks", "interp_inputs",
        "legacy_digests_gap", "legacy_sighash_bytes_gap",
        "sampled_inputs_refused_by_reference"}


@pytest.mark.parametrize("fault,confirms", [("wrong-key-multisig", 1),
                                            ("wrong-key-sig", 0)])
def test_a_fault_of_the_generators_is_not_correct(capsys, fault, confirms):
    """The chain's last pay-to-script-hash (or pay-to-pubkey-hash) input
    signed by a key that is not the script's: the reference refuses it, the
    node's lane or walk fails, the import aborts and the replay stops one
    block short, where the reference does."""
    rc, last, compared = rehearse(capsys, 2**31 + 33, "--fault", fault)
    assert last["correct"] is False
    assert not compared["sampled_inputs_refused_by_reference"]["ok"]
    assert compared["multisig_group_confirms_moved"]["value"] == confirms
    assert compared["tip_height_gap"]["ok"]


def test_a_program_without_prefork_lanes_is_refused_before_any_chain(
        capsys, monkeypatch):
    """What the parent commit looks like to the driver: gettpuinfo.batch
    without the counter. Nothing is generated, nothing imported."""
    from bitcoincashplus_tpu.ops import ecdsa_batch

    real = ecdsa_batch.BatchStats.snapshot

    def older(self):
        return {k: v for k, v in real(self).items() if k != "prefork_lanes"}

    monkeypatch.setattr(ecdsa_batch.BatchStats, "snapshot", older)
    with pytest.raises(RuntimeError, match="prefork_lanes"):
        run.main(["--workload", "reindex.pre_fork", "--seed", "5",
                  "--seconds", "1", "--rehearse"])
    assert '"phase": "setup"' not in capsys.readouterr().out
