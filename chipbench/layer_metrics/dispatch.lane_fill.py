"""Signatures verified per lane dispatched: batch.sigs_verified over
batch.sigs_verified + batch.sigs_padded, as deltas over the window."""


def read(obs):
    b, a = obs["before"]["batch"], obs["after"]["batch"]
    real = a["sigs_verified"] - b["sigs_verified"]
    lanes = real + a["sigs_padded"] - b["sigs_padded"]
    return 100.0 * real / lanes if lanes else None
