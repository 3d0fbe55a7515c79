"""``decode_host_ms`` in the cell above the knee, where it moves
``tokens_per_s``."""


def read(run):
    return run.metric("decode_host_ms")
