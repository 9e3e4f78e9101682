"""ops: the pairs a learned index SELECTS, of the causal pairs — the program's
own static counters ``index_selected_pairs`` over ``index_causal_pairs`` as
the loss reported them on the check's sequences: ``sum_t min(t + 1, topk)`` of
``L (L + 1) / 2``, 23.4 at 16,384 rows under top-2,048. What attention may
skip is the rest; nothing where the program reports no such counters."""


def read(artifacts):
    counters = artifacts.get("check", {}).get("counters", {})
    every = counters.get("index_causal_pairs")
    if not every:
        return None
    return 100.0 * counters["index_selected_pairs"] / every
