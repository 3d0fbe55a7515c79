"""Bytes over seconds of the window's KV swap-ins from host DRAM to the
device, as the payload plane times them (``device_put`` closed by
``block_until_ready``), in GB/s."""


def read(run):
    secs = run.stats.get("dram->hbm.seconds", 0.0)
    return run.stats["dram->hbm.bytes"] / secs / 1e9 if secs > 0 else None
