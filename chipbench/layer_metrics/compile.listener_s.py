"""Seconds the jax.monitoring listener counted for tracing, lowering and
compiling every watched program during set-up (gettpuinfo.device.programs,
read when the warm-up ends)."""


def read(obs):
    programs = obs["setup"]["device"]["programs"]
    return sum(p["compile_seconds"] for p in programs.values())
