"""ops: the kernel block pairs a masked flash call VISITS, of all its block
pairs — the program's own static counters ``flash_live_pairs`` over
``flash_block_pairs`` (from the kernels' block chooser), as the loss reported
them on the check's sequences: with ``n`` blocks a half ``n² + 2 n`` of ``4
n²``, 28.1 at 16,384 rows in blocks of 512. Lower is less work for the same
mask; nothing where the program reports no such counters."""


def read(artifacts):
    counters = artifacts.get("check", {}).get("counters", {})
    every = counters.get("flash_block_pairs")
    if not every:
        return None
    return 100.0 * counters["flash_live_pairs"] / every
