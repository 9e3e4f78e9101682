"""ops: the attention kernels' causal tiles (128 keys x 128 queries) that hold
a SELECTED pair, of all of them — the program's own counters
``index_live_tiles`` (dynamic: counted from the packed selection, every index
layer of the step) over ``index_tiles`` as the loss reported them on the
check's sequences. What a kernel that skipped dead tiles under a
data-dependent block list would still visit: 100 while the selection is
near uniform (seeded weights), lower as a trained index's locality shows.
Nothing where the program reports no such counters."""


def read(artifacts):
    counters = artifacts.get("check", {}).get("counters", {})
    every = counters.get("index_tiles")
    if not every:
        return None
    return 100.0 * counters["index_live_tiles"] / every
