"""The comparison that decides ``correct`` can come out false. Each test
skips the harness's look for a chip (--rehearse) and drives the rest of a
run: once sound, once with the harness's own fault, once with the timed
path broken underneath."""

import json

import run


def rehearse(capsys, workload, seed, *extra):
    rc = run.main(["--workload", workload, "--seed", str(seed),
                   "--seconds", "1", "--rehearse", *extra])
    lines = [json.loads(line) for line in
             capsys.readouterr().out.strip().splitlines()]
    compared = {n["name"]: n for n in lines if n.get("phase") == "compared"}
    return rc, lines[-1], compared


def test_reindex_sound_run_is_correct(capsys):
    rc, last, compared = rehearse(capsys, "reindex.p2pkh_dense", 2**31 + 5)
    assert rc == 0 and last["correct"] is True
    assert all(n["ok"] for n in compared.values())
    assert compared["sampled_signatures_refused_by_reference"]["note"] \
        == "600 sampled; worst of 3 windows"


def test_reindex_fault_chain_is_not_correct(capsys):
    """One wrong-key signature in the last dense block: the node stops one
    block short of what the generator promised."""
    rc, last, compared = rehearse(capsys, "reindex.p2pkh_dense", 2**31 + 6,
                                  "--fault", "wrong-key-sig")
    assert last["correct"] is False
    assert not compared["sampled_signatures_refused_by_reference"]["ok"]
    # the sound node and the reference (which here verifies every input)
    # stop at the same block
    assert compared["tip_height_gap"]["ok"]


def test_reindex_verifier_that_verifies_nothing_is_not_correct(
        capsys, monkeypatch):
    """The timed path broken underneath: the verify leg answers 'valid' for
    every lane, so the node walks over the bad signature to the full tip."""
    import numpy as np
    from bitcoincashplus_tpu import native

    monkeypatch.setattr(
        native, "ecdsa_verify_batch_blobs",
        lambda pub, rs, msg, n: np.ones(n, bool))
    rc, last, compared = rehearse(capsys, "reindex.p2pkh_dense", 2**31 + 6,
                                  "--fault", "wrong-key-sig")
    assert last["correct"] is False
    assert compared["tip_height_gap"]["value"] == 1
    assert not compared["tip_hash_differs"]["ok"]
    assert not compared["utxo_count_gap"]["ok"]


def test_mine_sound_run_is_correct(capsys):
    rc, last, compared = rehearse(capsys, "mine.diff1_solo", 2**31 + 7)
    assert rc == 0 and last["correct"] is True
    assert compared["pow_hash_over_target_worst"]["value"] <= 1.0


def test_mine_harder_target_is_not_correct(capsys):
    rc, last, compared = rehearse(capsys, "mine.diff1_solo", 2**31 + 8,
                                  "--fault", "harder-target")
    assert last["correct"] is False
    assert not compared["pow_hash_over_target_worst"]["ok"]


def test_mine_altered_answer_is_not_correct(capsys, monkeypatch):
    """An answer altered where it is produced: the node reports a block
    hash with one bit flipped."""
    from bitcoincashplus_tpu.node.node import Node

    real = Node.generate_to_script

    def altered(self, script, n_blocks, max_tries):
        return [bytes([h[0] ^ 1]) + h[1:]
                for h in real(self, script, n_blocks, max_tries)]

    monkeypatch.setattr(Node, "generate_to_script", altered)
    rc, last, compared = rehearse(capsys, "mine.diff1_solo", 2**31 + 9)
    assert last["correct"] is False
    assert compared["headers_hash_mismatched"]["value"] > 0
