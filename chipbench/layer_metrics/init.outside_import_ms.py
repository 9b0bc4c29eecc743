"""What ``Node(config)`` of the measured window costs around the import:
the sum of gettpuinfo["startup"]'s stages other than ``import`` (the
node.init.* spans), in milliseconds. Nothing to read in a program without
the stages."""


def read(obs):
    startup = obs["after"].get("startup")
    if not startup or "import" not in startup:
        return None
    return 1e3 * sum(seconds for phase, seconds in startup.items()
                     if phase != "import")
