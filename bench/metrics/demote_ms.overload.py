"""``demote_ms`` in the cell above the knee, where it moves
``tokens_per_s``."""


def read(run):
    return run.metric("demote_ms")
